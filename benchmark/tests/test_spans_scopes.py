"""The two reductions PR 24 added (`reduce/host_spans.py`,
`reduce/scopes.py`) and the nine readers on top of them: on made-up
planes where the answer can be worked by hand, and on a trace recorded
on the chip.

`data/tiny_traced.xplane.pb.gz` + `data/tiny_traced.spans.jsonl` are the
profiler's file and the span record of the tiny loop (`lm_train` at the
size of `tiny_lm.json`, bf16, fused loss, a sharded checkpoint every 5
steps) on a TPU v5e with the profiler around steps 7-10 and the save at
step 10 (my chip run, PR 24): four device steps, three whole periods,
host-bound."""

import gzip
import importlib
import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmark.reduce import host_spans, scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = ["startup_s", "launcher_reform_s", "ckpt_snapshot_ms", "ckpt_write_s",
       "host_gap_explained_share", "loader_wait_share",
       "fused_xent_time_share", "optimizer_time_share",
       "flash_bwd_time_share"]


# -- scopes: by hand ----------------------------------------------------------

@pytest.mark.parametrize("tf_op, scope", [
    ("jit(train_step)/jvp(xent)/while/body/closed_call/dot_general:", "xent"),
    ("jit(train_step)/transpose(jvp(xent))/while/body/mul:", "xent"),
    ("jit(train_step)/opt_update/add:", "opt_update"),
    ("jit(train_step)/jvp(Transformer)/block0/attn/out/dot_general:", "attn"),
    ("jit(train_step)/transpose(jvp(Transformer))/block23/attn/shard_map/"
     "flash_bwd_dq/pallas_call:", "attn"),
    ("jit(train_step)/jvp(Transformer)/block3/mlp/mlp_in/dot_general:",
     "mlp"),
    ("jit(train_step)/jvp(Transformer)/block3/ln/ln_mlp/mul:", "ln"),
    ("jit(train_step)/jvp(Transformer)/ln/ln_final/add_any:", "ln"),
    ("jit(train_step)/jvp(Transformer)/embed/tok_embed/jit(_take)/gather:",
     "embed"),
    ("jit(train_step)/jvp(Transformer)/lm_head/dot_general:", "lm_head"),
    # no scope of the program's: the model's outermost module, unnumbered
    ("jit(train_step)/jvp(Transformer)/block7/add:", "block"),
    ("jit(train_step)/jvp(Transformer)/add:", "unscoped"),
    ("", "unscoped"), (None, "unscoped"),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields) -> bytes:
    """(number, int | bytes) pairs as one protobuf message."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_tf_ops_are_decoded_from_the_planes_event_metadata(tmp_path):
    def plane(name, ops):
        out = _msg((1, 3), (2, name.encode()),
                   (3, b"\x08\x01"))  # a line: skipped, whatever it holds
        for i, (text, op) in enumerate(ops, 1):
            stats = [(5, _msg((1, 9), (4, 12)))]           # flops = 12
            if op:
                stats.append((5, _msg((1, 26), (5, op.encode()))))
            meta = _msg((1, i), (2, text.encode()), *stats)
            out += _msg((4, _msg((1, i), (2, meta))))       # map entry
        for i, stat in ((26, b"tf_op"), (9, b"flops")):
            out += _msg((5, _msg((1, i), (2, _msg((1, i), (2, stat))))))
        return out
    space = _msg(
        (1, plane("/device:TPU:1", [
            ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             "jit(train_step)/opt_update/add:"),
            ("%copy.3 = f32[8]{0} copy(f32[8]{0} %q)", None)])),
        (1, plane("/host:CPU", [("$loop.py:1 run", "jit(x)/y/z:")])))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert scopes.tf_ops(str(path)) == {
        1: {"%fusion.12": "jit(train_step)/opt_update/add:"}}


def _trace(ops, busy, window=(0, 1000), path=""):
    return {"path": path, "busy_s": xplane.length(busy) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9,
            "devices": {0: {"window_ns": window, "ops": ops, "busy": busy,
                            "steps": [], "whole_steps": 1}}}


def test_seconds_by_scope_and_kernel(monkeypatch):
    ops = [(0, 100, "%fusion.1 fusion f32[8]", "other"),
           (100, 400, "%flash_bwd_dkdv.2 tpu_custom_call bf16[8]", "pallas"),
           (400, 500, "%flash_fwd tpu_custom_call bf16[8]", "pallas"),
           (500, 600, "%copy.9 copy f32[8]", "other"),
           (0, 600, "%while.1 while (f32[8])", "container")]
    monkeypatch.setattr(scopes, "tf_ops", lambda path: {0: {
        "%fusion.1": "jit(train_step)/opt_update/add:",
        "%flash_bwd_dkdv.2": "jit(train_step)/transpose(jvp(T))/block0/attn/"
                             "flash_bwd_dkdv/pallas_call:",
        "%flash_fwd": "jit(train_step)/jvp(T)/block0/attn/flash_fwd/"
                      "pallas_call:"}})
    ev = {"trace": _trace(ops, [(0, 600)])}
    got = scopes.of(ev)
    assert got["by_scope"] == pytest.approx(
        {"opt_update": 1e-7, "attn": 4e-7, "unscoped": 1e-7})
    assert got["by_kernel"] == pytest.approx(
        {"flash_bwd_dkdv": 3e-7, "flash_fwd": 1e-7})
    assert scopes.of(ev) is got                    # reduced once
    assert scopes.share(ev, "by_scope", "opt_update") == pytest.approx(
        100 / 6)
    assert scopes.share(ev, "by_kernel", "flash_bwd_dkdv",
                        "flash_bwd_dq") == pytest.approx(50.0)
    # a program without the name reports nothing, not 0
    assert scopes.share(ev, "by_scope", "xent") is None
    assert scopes.share({}, "by_scope", "xent") is None


# -- host spans: by hand ------------------------------------------------------

def _span(name, start, end, thread="MainThread", **attrs):
    return {"name": name, "start": start, "end": end, "thread": thread,
            "attrs": attrs, "source": "plane"}


def test_idle_time_goes_to_the_loop_threads_spans():
    spans = [
        _span("train", 90, 410),                      # step annotation
        _span("train.dispatch", 100, 120, step=3),
        _span("ckpt.snapshot", 200, 400),
        _span("ckpt.d2h", 200, 250),
        _span("ckpt.stage", 250, 390),
        _span("ckpt.write", 150, 900, thread="edl-ckpt-writer"),
        _span("train.loader_wait", 420, 440),
    ]
    # busy 0-210 and 500-1000: idle 210-500
    trace = _trace([], [(0, 210), (500, 1000)])
    idle = host_spans.idle_by_span(trace, spans)
    assert idle == pytest.approx({
        "ckpt.snapshot > ckpt.d2h": 40e-9, "ckpt.snapshot > ckpt.stage":
        140e-9, "ckpt.snapshot": 10e-9, "train.loader_wait": 20e-9,
        host_spans.NO_SPAN: 80e-9})
    assert sum(idle.values()) == pytest.approx(290e-9)
    assert host_spans.thread_seconds(trace, spans, "train.loader_wait") \
        == pytest.approx(20e-9)
    # another thread's span explains nothing of the loop's waiting
    assert not any("ckpt.write" in k for k in idle)


def test_records_move_onto_the_traces_clock(tmp_path, monkeypatch):
    events = [
        {"name": "train.dispatch", "start": 1000.0, "end": 1500.0,
         "line": 4, "stats": {"step": 7}},
        {"name": "train.dispatch", "start": 3000.0, "end": 3400.0,
         "line": 4, "stats": {"step": 8}},
        {"name": "ckpt.seal", "start": 3100.0, "end": 3200.0, "line": 6,
         "stats": {}},
        {"name": "train", "start": 900.0, "end": 1600.0, "line": 4,
         "stats": {"step_num": 7}},
    ]
    monkeypatch.setattr(host_spans, "plane_events",
                        lambda path, names: sorted(
                            events, key=lambda e: e["start"]))
    wall = 1.7e9   # seconds: the records' clock

    def rec(name, at_ns, dur_ns, thread="MainThread", **attrs):
        return {"name": name, "t0": wall + at_ns / 1e9, "dur": dur_ns / 1e9,
                "thread": thread, "attrs": attrs}
    recs = [rec("train.startup", -9e9, 5e9),
            rec("train.dispatch", 1000, 500, step=7),
            rec("train.dispatch", 3000, 400, step=8),
            rec("ckpt.seal", 3100, 100, thread="edl-ckpt-writer"),
            rec("ckpt.write", 2000, 9e9, thread="edl-ckpt-writer", step=5)]
    spans = host_spans.spans_on_trace_clock("ignored", recs)
    by = {(s["name"], s["source"]): s for s in spans}
    assert by[("train.dispatch", "plane")]["thread"] == "MainThread"
    assert by[("ckpt.seal", "plane")]["thread"] == "edl-ckpt-writer"
    assert by[("train", "plane")]["thread"] == "MainThread"  # its line's
    # known from the record alone: before the profiler, and outliving it
    assert by[("train.startup", "record")]["start"] == pytest.approx(
        -9e9, abs=1e3)
    write = by[("ckpt.write", "record")]
    assert write["start"] == pytest.approx(2000, abs=1e3)
    assert write["end"] - write["start"] == pytest.approx(9e9)
    assert ("train.dispatch", "record") not in by
    assert host_spans.loop_thread(spans) == "MainThread"
    # no dispatch in common: no clock, no spans
    assert host_spans.spans_on_trace_clock("ignored", recs[:1]) == []


def test_records_of_a_missing_or_torn_file(tmp_path):
    assert host_spans.records(str(tmp_path)) == []
    assert host_spans.records(str(tmp_path), pid=5) == []
    (tmp_path / "spans-5.jsonl").write_text(
        '{"name": "train.startup", "t0": 1.0, "dur": 2.0}\n{"name": "tr')
    (tmp_path / "spans-6.jsonl").write_text(
        '{"name": "ckpt.write", "t0": 3.0, "dur": 1.0}\n[1]\n')
    os.utime(tmp_path / "spans-6.jsonl", (1, 1))      # not by file time:
    assert [r["name"] for r in host_spans.records(str(tmp_path))] == [
        "ckpt.write"]                      # the one whose spans end last
    assert [r["name"] for r in host_spans.records(str(tmp_path), 5)] == [
        "train.startup"]


# -- the recorded run ----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A cell's work directory as a traced run leaves it."""
    work = tmp_path_factory.mktemp("work")
    trace_dir = work / "trace" / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data",
                                "tiny_traced.xplane.pb.gz")) as src, \
            open(trace_dir / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.copy(os.path.join(HERE, "data", "tiny_traced.spans.jsonl"),
                work / "trace" / "spans-4242.jsonl")
    (work / "job").mkdir()
    (work / "job" / "launcher.log").write_text(
        "2026-09-27 05:14:31,188 WARNING edl_tpu.collective.launch [17] "
        "trainer crashed rc=-9 (1/5)\n"
        "2026-09-27 05:14:36,894 INFO edl_tpu.collective.launch [17] "
        "reform: exit_seen→spawn 5.706s (rejoin_wait 3.681s, barrier "
        "2.022s, spawn 0.003s)\n")
    cell = NS(work=str(work), trace_dir=str(work / "trace"),
              traffic={"ckpt_steps": 5}, config={})
    trace = xplane.reduce_dir(cell.trace_dir, 1)
    # log windows of the measured window: steps 4..12, log every 4
    ev = {"trace": trace, "quiet_windows": [
        ((0.0, 4, 6.0), (1.0, 8, 6.0)), ((1.0, 8, 6.0), (2.0, 12, 6.0))]}
    return cell, ev


def test_recorded_steps_are_named_and_dispatched_before_they_run(recorded):
    cell, ev = recorded
    dev = ev["trace"]["devices"][0]
    assert dev["step_name"].startswith("jit_train_step(")
    assert dev["whole_steps"] == 3
    spans = host_spans.of(cell, ev)["spans"]
    dispatch = [s for s in spans if s["name"] == "train.dispatch"
                and s["source"] == "plane"]
    assert [s["attrs"]["step"] for s in dispatch] == [7, 8, 9, 10]
    # One clock. The first module event of the file is step 6, which
    # was dispatched before the profiler started; from there on step n
    # runs after its own dispatch began and before the next one's did
    # (the tiny loop is host-bound: the device waits for every dispatch).
    runs = [start for start, _ in dev["steps"][1:]]
    for s, nxt, start in zip(dispatch, dispatch[1:], runs):
        assert s["start"] < start < nxt["start"]
    assert len(runs) == 3
    assert {s["thread"] for s in dispatch} == {"MainThread"}


def test_recorded_idle_time_is_explained_by_spans(recorded):
    cell, ev = recorded
    idle = host_spans.of(cell, ev)["idle"]
    # the tiny loop is host-bound: the device waits while the loop's
    # thread dispatches, fetches the next batch or reads the loss; the
    # save at step 10 falls after the last whole step of this file
    assert idle == pytest.approx({
        "train.dispatch": 0.004436139, "train.loader_wait": 0.002195385,
        host_spans.NO_SPAN: 0.002093249, "train.log_fetch": 0.000627351},
        rel=1e-6)
    total = sum(idle.values())
    gaps = sum(b - a for a, b, _ in xplane.idle_gaps(ev["trace"], 10 ** 6))
    assert total == pytest.approx(gaps / 1e9, rel=1e-9)


def test_recorded_kernels_are_three_rows_and_scopes_cover(recorded):
    _, ev = recorded
    rows = [k for k, _ in xplane.breakdown(ev["trace"])["device_ops"]]
    got = scopes.of(ev)
    assert set(got["by_kernel"]) == {"flash_fwd", "flash_bwd_dkdv",
                                     "flash_bwd_dq"}
    for kernel in got["by_kernel"]:
        assert sum(r.startswith(f"%{kernel} ") for r in rows) <= 1
    assert {"xent", "opt_update", "attn", "mlp"} <= set(got["by_scope"])
    assert sum(got["by_scope"].values()) == pytest.approx(
        sum(xplane.seconds_by(ev["trace"], lambda op: 0).values()))
    assert got["by_scope"].get("unscoped", 0.0) < 0.1 * got["busy_s"]


@pytest.mark.parametrize("name", NEW)
def test_reader_on_the_recorded_run(recorded, name):
    cell, ev = recorded
    value = importlib.import_module(
        "benchmark.layer_metrics." + name).read(cell, ev)
    assert isinstance(value, float) and value > 0, (name, value)
    if name.endswith("_share"):
        assert value <= 100.0
    if name == "launcher_reform_s":
        assert value == 5.706
    if name == "host_gap_explained_share":
        assert value == pytest.approx(77.6174, abs=1e-3)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["source"] in ("program_span", "device_trace")


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_for_a_program_without_spans(
        recorded, name, tmp_path):
    """The parent of PR 24 writes no span, no `reform:` line and no
    scope of ours: a reader returns nothing there and does not raise."""
    _, ev = recorded
    old = dict(ev["trace"], path=ev["trace"]["path"])
    bare = NS(work=str(tmp_path), trace_dir=str(tmp_path), traffic={},
              config={})
    scopeless = {"trace": old, "scopes": {
        "by_scope": {"block": 1.0, "while": 0.5}, "by_kernel": {},
        "busy_s": 2.0}}
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.read(bare, scopeless) is None
    assert reader.read(bare, {}) is None
