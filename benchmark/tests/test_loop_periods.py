"""`reduce/loop_periods.py` and the five readers of PR 38 on recorded
windows where the answer can be worked by hand.

`data/loop_*.spans.jsonl` are span records in the trainer's shape, made
by hand on a round clock: a step is 1.0 s (`train.loader_wait` 0.1,
`train.dispatch` 0.9 with the log line's two spans in its last tenth on
even steps), a save every 4 steps is `train.save` 0.5 s around
`ckpt.snapshot` 0.4 around `ckpt.d2h` 0.3, and the writer's thread has
`ckpt.write` with its four phases; the first record is the sampler's
`host.clock_sampler`. The measured window is the step lines 4..12, so
two whole save periods.

- `loop_quiet`: writes of 2.0 s, nothing else: two periods of 4.5 s;
- `loop_gap`: no saves; 0.7 s under no span before step 8 with a
  `host.clock_gap` of that length beside it (nothing of ours ran);
- `loop_overlap`: writes of 5.0 s, so each is still running when the
  next snapshot begins (0.9 s, then 1.4 s with the queue's wait), and
  the save at step 12 takes 0.6 s longer with a gap full of our own CPU
  inside it (a held interpreter lock).
"""

import importlib
import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmark.reduce import host_spans, loop_periods

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = ["host_clock_gap_share", "save_clock_gap_ms", "save_period_spread_ms",
       "ckpt_write_overlap_s", "loop_span_coverage_share"]
LINES = [(float(i), step, 6.0) for i, step in enumerate(range(4, 13, 2))]


def _cell(tmp_path, name, traffic, drop=lambda r: False, more=()):
    trace_dir = tmp_path / name
    trace_dir.mkdir()
    with open(os.path.join(HERE, "data", name + ".spans.jsonl")) as src, \
            open(trace_dir / "spans-4242.jsonl", "w") as dst:
        for line in src:
            if not drop(json.loads(line)):
                dst.write(line)
        for rec in more:
            dst.write(json.dumps(rec) + "\n")
    return NS(trace_dir=str(trace_dir), work=str(tmp_path), traffic=traffic,
              config={})


def _ev():
    return {"quiet_windows": list(zip(LINES, LINES[1:]))}


def _read(name, cell, ev):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(cell, ev)


SAVING = {"ckpt_steps": 4, "log_every": 2}
STEADY = {"log_every": 2}


def test_a_quiet_window(tmp_path):
    cell, ev = _cell(tmp_path, "loop_quiet", SAVING), _ev()
    got = loop_periods.of(cell, ev)
    assert loop_periods.of(cell, ev) is got            # reduced once
    assert got["window_s"] == pytest.approx(9.0)
    assert [r["steps"] for r in got["periods"]] == [(4, 8), (8, 12)]
    assert [r["length_s"] for r in got["periods"]] == pytest.approx(
        [4.5, 4.5])
    first = got["periods"][0]
    # self time: a span's length less what its children cover
    assert first["self_s"] == pytest.approx({
        "train.dispatch": 3.4, "train.loader_wait": 0.4,
        "train.log_fetch": 0.12, "train.log_line": 0.08,
        "train.save": 0.1, "ckpt.snapshot": 0.1, "ckpt.d2h": 0.3,
        host_spans.NO_SPAN: 0.0}, abs=1e-6)
    # the period runs 4.6 -> 9.1: the write of step 4's save (4.45 ->
    # 6.45) is inside it but for its first 0.15 s, which hold its clean;
    # the write of step 8's save begins at 8.95 with its own
    assert first["writer_s"] == pytest.approx({
        "ckpt.write": 1.85 + 0.15, "ckpt.clean": 0.1, "ckpt.chunks": 1.5,
        "ckpt.seal": 0.1, "ckpt.gc": 0.2})
    assert first["gc_removed"] == 1
    assert got["quiet_periods"] == got["periods"]
    assert first["save"] == {"writer_inflight": False, "superseded": False,
                             "queued_s": 0.0}
    assert _read("save_period_spread_ms", cell, ev) == pytest.approx(0.0)
    assert _read("save_clock_gap_ms", cell, ev) == 0.0
    assert _read("ckpt_write_overlap_s", cell, ev) == 0.0
    assert _read("loop_span_coverage_share", cell, ev) == pytest.approx(100)
    assert _read("host_clock_gap_share", cell, ev) == 0.0


def test_a_window_with_a_gap(tmp_path, capsys):
    cell, ev = _cell(tmp_path, "loop_gap", STEADY), _ev()
    assert _read("host_clock_gap_share", cell, ev) == pytest.approx(
        100 * 0.7 / 8.7)
    assert _read("loop_span_coverage_share", cell, ev) == pytest.approx(
        100 * 8.0 / 8.7)
    got = loop_periods.of(cell, ev)
    assert got["periods"] == []
    assert [r["length_s"] for r in got["windows"]] == pytest.approx(
        [2.0, 2.7, 2.0, 2.0])
    long = got["windows"][1]
    assert long["steps"] == (6, 8) and long["gap_s"] == pytest.approx(0.7)
    assert long["self_s"][host_spans.NO_SPAN] == pytest.approx(0.7)
    assert long["gap_cpu_s"] == 0.0
    # the table: the one log window over 1.05 x the median, no other
    err = capsys.readouterr().err
    assert "1 of 4 log windows over 1.05 x their median" in err
    assert err.count("steps 7-8: 2.700 s") == 1 and "steps 5-6" not in err
    # no save: the save cell's readers still give numbers, not nothing
    assert _read("save_period_spread_ms", cell, ev) == 0.0
    assert _read("save_clock_gap_ms", cell, ev) == 0.0
    assert _read("ckpt_write_overlap_s", cell, ev) == 0.0


def test_a_write_that_runs_into_the_next_snapshot(tmp_path, capsys):
    cell, ev = _cell(tmp_path, "loop_overlap", SAVING), _ev()
    assert _read("ckpt_write_overlap_s", cell, ev) == pytest.approx(
        0.9 + 1.4)
    assert _read("save_period_spread_ms", cell, ev) == pytest.approx(600)
    assert _read("save_clock_gap_ms", cell, ev) == pytest.approx(600)
    assert _read("loop_span_coverage_share", cell, ev) == pytest.approx(100)
    first, second = loop_periods.of(cell, ev)["periods"]
    assert first["save"] == {"writer_inflight": True, "superseded": False,
                             "queued_s": 0.5}
    assert second["length_s"] == pytest.approx(5.1)
    assert second["gap_cpu_s"] == pytest.approx(0.6)
    assert second["self_s"]["ckpt.d2h"] == pytest.approx(0.9)
    err = capsys.readouterr().err
    assert "2 save periods" in err and "writer_inflight True" in err
    assert "ckpt.chunks" in err and "cpu_s 0.600" in err
    assert "(versions removed 1)" in err and "profiler's" not in err
    # the write of step 8's save cut by a kill just after its chunks
    # (9.45 + 0.15 + 4.5 = 14.1): no `ckpt.write` record, the process's
    # last record ends at 14.1, and 1.05 s of the write lie after the
    # snapshot of step 12 began (13.05)
    def killed_at(t, r):
        return r["t0"] + r["dur"] > 1790800000.0 + t + 1e-6
    shutil.rmtree(tmp_path / "loop_overlap")
    cell = _cell(tmp_path, "loop_overlap", SAVING,
                 lambda r: killed_at(14.1, r))
    assert _read("ckpt_write_overlap_s", cell, _ev()) == pytest.approx(
        0.9 + 1.05)
    # killed inside the chunks, as that snapshot ends (14.05): of the
    # write only `ckpt.clean` is on record, and it was still running
    # when the process made its last record
    shutil.rmtree(tmp_path / "loop_overlap")
    cell, ev = _cell(tmp_path, "loop_overlap", SAVING,
                     lambda r: killed_at(14.05, r)), _ev()
    capsys.readouterr()
    assert _read("ckpt_write_overlap_s", cell, ev) == pytest.approx(
        0.9 + 1.0)
    assert "ckpt.write (cut by the kill) 4.550" in capsys.readouterr().err


def test_the_profilers_period_is_not_the_loops(tmp_path, capsys):
    """The second save at step 12 takes 0.6 s longer, with a clock gap
    of that length; with the profiler stopping inside that save (its
    span a child of nothing, over the stretched fetch) the period is
    the profiler's: both of the save cell's readers that go by periods
    leave it out, and the table says whose it is."""
    cell, ev = _cell(tmp_path, "loop_overlap", SAVING), _ev()
    assert _read("save_period_spread_ms", cell, ev) == pytest.approx(600)
    assert _read("save_clock_gap_ms", cell, ev) == pytest.approx(600)
    shutil.rmtree(tmp_path / "loop_overlap")
    stop = {"tid": "t", "sid": "p1", "parent": None, "pid": 4242,
            "name": "train.profiler", "thread": "MainThread",
            "t0": 1790800000.0 + 12.0, "dur": 0.06, "attrs": {}}
    # 0.06 s of a 5.1 s period is over 1 %: the period goes; under it
    # (the profiler's start, 0.03 s) it stays
    cell, ev = _cell(tmp_path, "loop_overlap", SAVING, more=[stop]), _ev()
    capsys.readouterr()
    assert _read("save_period_spread_ms", cell, ev) == 0.0
    assert _read("save_clock_gap_ms", cell, ev) == 0.0
    assert len(loop_periods.of(cell, ev)["periods"]) == 2
    assert capsys.readouterr().err.count("(the profiler's period)") == 1
    shutil.rmtree(tmp_path / "loop_overlap")
    cell, ev = _cell(tmp_path, "loop_overlap", SAVING,
                     more=[dict(stop, dur=0.03)]), _ev()
    assert _read("save_period_spread_ms", cell, ev) == pytest.approx(600)
    assert _read("save_clock_gap_ms", cell, ev) == pytest.approx(600)


def test_a_kill_that_took_the_next_dispatch(tmp_path):
    """The run ends with SIGKILL a few ms after the closing step line:
    the records end with the last snapshot, which the writer's thread
    put on disk as it took it over."""
    def after_the_last_snapshot(r):
        return r["t0"] + r["dur"] > 1790800000.0 + 13.45 + 1e-6
    cell = _cell(tmp_path, "loop_quiet", SAVING, after_the_last_snapshot)
    ev = _ev()
    assert _read("save_period_spread_ms", cell, ev) == pytest.approx(150)
    # the last period ends where the last finished span of the loop's
    # thread does, the snapshot's end at 13.45: short of the next
    # dispatch (13.6) by the save's tail and a loader wait, milliseconds
    # on the chip; `train.save` itself was still open, so its first
    # 0.05 s lie under no span
    assert [r["length_s"] for r in loop_periods.of(cell, ev)["periods"]] \
        == pytest.approx([4.5, 4.35])
    assert _read("loop_span_coverage_share", cell, ev) == pytest.approx(
        100 * (1 - 0.05 / 8.85))


@pytest.mark.parametrize("name", NEW)
def test_always_a_number_once_the_record_file_exists(tmp_path, name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span" and entry["workloads"]
    for data, traffic in (("loop_quiet", SAVING), ("loop_gap", STEADY),
                          ("loop_overlap", SAVING)):
        value = _read(name, _cell(tmp_path, data, traffic), _ev())
        assert isinstance(value, float) and value >= 0.0, (data, value)
        if name.endswith("_share"):
            assert value <= 100.0
        shutil.rmtree(tmp_path / data)


@pytest.mark.parametrize("name", NEW)
def test_nothing_for_a_program_without_the_spans(tmp_path, name):
    """The parent of PR 38 has no sampler: its window without gaps is
    not a reading of 0. A program without spans, or a run whose steps
    the records do not hold, gives nothing and does not raise."""
    def new_in_pr_38(r):
        return r["name"] in ("train.save", "train.log_line",
                             "host.clock_gap", "host.clock_sampler")
    cell = _cell(tmp_path, "loop_quiet", SAVING, new_in_pr_38)
    value = _read(name, cell, _ev())
    if name in ("host_clock_gap_share", "save_clock_gap_ms"):
        assert value is None
    else:
        assert isinstance(value, float)
    bare = NS(trace_dir=str(tmp_path / "none"), work=str(tmp_path),
              traffic={}, config={})
    assert _read(name, bare, _ev()) is None
    assert _read(name, bare, {}) is None
    assert _read(name, cell, {}) is None
    far = [(0.0, 400, 6.0), (1.0, 410, 6.0)]
    assert _read(name, cell, {"quiet_windows": [tuple(far)]}) is None


def test_the_recorded_run_of_pr_24_reads_without_raising(tmp_path):
    """The tiny loop recorded on the chip before this PR: snapshots and
    writes are there, the sampler's first record is not."""
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(os.path.join(HERE, "data", "tiny_traced.spans.jsonl"),
                trace_dir / "spans-4242.jsonl")
    cell = NS(trace_dir=str(trace_dir), work=str(tmp_path),
              traffic={"ckpt_steps": 5, "log_every": 4}, config={})
    ev = {"quiet_windows": [((0.0, 4, 6.0), (1.0, 8, 6.0)),
                            ((1.0, 8, 6.0), (2.0, 12, 6.0))]}
    got = loop_periods.of(cell, ev)
    assert got and not got["sampled"] and len(got["periods"]) == 1
    assert _read("host_clock_gap_share", cell, ev) is None
    assert 0 < _read("loop_span_coverage_share", cell, ev) < 100
    assert _read("ckpt_write_overlap_s", cell, ev) >= 0.0
