"""One trace, one clock: the trainer's spans inside the profiler's file,
their record beside it, the two switches, and the names on device work.
"""

import ast
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from edl_tpu.obs import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_SPANS = ("train.dispatch", "train.loader_wait", "train.save",
              "ckpt.snapshot", "ckpt.d2h", "ckpt.stage")
WRITER_SPANS = ("ckpt.write", "ckpt.clean", "ckpt.chunks", "ckpt.seal",
                "ckpt.gc")
START_SAMPLER = trace._start_sampler
STARTUP = ("imports", "args", "runtime", "mesh_model", "state_init",
           "loop_init")


@pytest.fixture(autouse=True)
def _clean_trace_state(monkeypatch):
    monkeypatch.delenv("EDL_TPU_TRACE", raising=False)
    monkeypatch.delenv("EDL_TPU_PROFILE_DIR", raising=False)
    # a test process keeps its own SIGTERM and exit behaviour, and its
    # records are those the test made (the sampler's tests start it)
    monkeypatch.setattr(trace, "_arm_exit_flush", lambda: None)
    monkeypatch.setattr(trace, "_start_sampler", lambda: None)
    trace.reconfigure()
    yield
    trace.reconfigure()


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """`lm_train` at a tiny size with a profile directory: 16 steps, the
    profiler around steps 4-11, a sharded checkpoint every 5 steps."""
    from edl_tpu.examples.lm_train import main

    tmp = tmp_path_factory.mktemp("traced")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "_arm_exit_flush", lambda: None)
        mp.delenv("EDL_TPU_TRACE", raising=False)
        mp.setenv("EDL_TPU_PROFILE_START", "3")
        mp.setenv("EDL_TPU_PROFILE_STEPS", "8")
        mp.setenv("EDL_TPU_LOG_EVERY", "4")
        trace.reconfigure()
        rc = main(["--data-dir", str(tmp / "d"), "--make-synthetic", "2",
                   "--rows-per-file", "64", "--vocab", "64", "--seq-len",
                   "32", "--d-model", "32", "--n-heads", "2", "--n-layers",
                   "1", "--d-ff", "64", "--epochs", "1", "--batch-size",
                   "8", "--fused-loss", "--ckpt-dir", str(tmp / "ckpt"),
                   "--ckpt-steps", "5", "--ckpt-sharded",
                   "--profile", str(tmp / "prof")])
        trace.flush()
        trace.reconfigure()
    assert rc == 0
    prof = str(tmp / "prof")
    (path,) = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                        recursive=True)
    (span_file,) = glob.glob(os.path.join(prof, "spans-*.jsonl"))
    with open(span_file) as f:
        records = [json.loads(line) for line in f]
    import warnings

    from jax.profiler import ProfileData
    lines = {}   # line number of the host plane -> its events
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for i, line in enumerate(plane.lines):
                    lines[i] = [(e.name, e.start_ns, e.duration_ns,
                                 dict(e.stats)) for e in line.events]
    return {"records": records, "lines": lines, "span_file": span_file}


def _line_of(lines, name):
    found = [i for i, evs in lines.items() if any(e[0] == name for e in evs)]
    assert len(found) == 1, (name, found)
    return found[0]


@pytest.mark.parametrize("name", LOOP_SPANS)
def test_loop_spans_are_events_of_the_loops_thread(traced_run, name):
    lines = traced_run["lines"]
    assert _line_of(lines, name) == _line_of(lines, "train.dispatch")
    mine = [r for r in traced_run["records"] if r["name"] == name]
    assert mine and {r["thread"] for r in mine} == {"MainThread"}


@pytest.mark.parametrize("name", WRITER_SPANS)
def test_writer_spans_are_events_of_the_writer_thread(traced_run, name):
    lines = traced_run["lines"]
    assert _line_of(lines, name) == _line_of(lines, "ckpt.write")
    assert _line_of(lines, name) != _line_of(lines, "train.dispatch")
    mine = [r for r in traced_run["records"] if r["name"] == name]
    assert mine and {r["thread"] for r in mine} == {"edl-ckpt-writer"}


def test_events_and_records_of_a_span_agree(traced_run):
    """The same span, once on the profiler's clock and once on the wall
    clock: the same steps, the same durations (perf_counter both)."""
    events = [e for e in traced_run["lines"][_line_of(
        traced_run["lines"], "train.dispatch")] if e[0] == "train.dispatch"]
    by_step = {r["attrs"]["step"]: r for r in traced_run["records"]
               if r["name"] == "train.dispatch"}
    assert [e[3]["step"] for e in events] == list(range(4, 12))
    offsets = []
    for name, start, dur, stats in events:
        rec = by_step[stats["step"]]
        assert abs(rec["dur"] * 1e9 - dur) < 2e6   # within 2 ms
        offsets.append(rec["t0"] * 1e9 - start)
    assert max(offsets) - min(offsets) < 5e6       # one clock offset


def test_step_annotation_wraps_each_traced_dispatch(traced_run):
    evs = traced_run["lines"][_line_of(traced_run["lines"],
                                       "train.dispatch")]
    steps = [e for e in evs if e[0] == "train"]
    assert [e[3]["step_num"] for e in steps] == list(range(4, 12))
    for (_, s0, d0, _), (_, s1, d1, _) in zip(
            steps, [e for e in evs if e[0] == "train.dispatch"]):
        assert s0 <= s1 and s1 + d1 <= s0 + d0


def test_snapshot_record_carries_bytes_and_children(traced_run):
    recs = traced_run["records"]
    snaps = [r for r in recs if r["name"] == "ckpt.snapshot"]
    assert [r["attrs"]["step"] for r in snaps] == [5, 10, 15, 16]
    assert all(r["attrs"]["bytes"] > 0 and r["attrs"]["superseded"] is False
               for r in snaps)
    # on the cpu platform every fetched array may alias a live buffer, so
    # all of it went through a private copy (on a chip: 0, no ckpt.stage)
    assert all(r["attrs"]["copied_bytes"] == r["attrs"]["bytes"]
               for r in snaps)
    for parent in snaps:
        kids = [r for r in recs if r["parent"] == parent["sid"]]
        assert sorted(k["name"] for k in kids) == ["ckpt.d2h", "ckpt.stage"]
        assert sum(k["dur"] for k in kids) <= parent["dur"] + 1e-3
    writes = [r for r in recs if r["name"] == "ckpt.write"]
    assert len(writes) == 4 and all(w["attrs"]["files"] > 0 for w in writes)
    seals = [r for r in recs if r["name"] == "ckpt.seal"]
    assert {s["parent"] for s in seals} == {w["sid"] for w in writes}


def test_a_save_is_one_span_and_the_writers_phases_have_names(traced_run):
    recs = traced_run["records"]
    saves = [r for r in recs if r["name"] == "train.save"]
    snaps = [r for r in recs if r["name"] == "ckpt.snapshot"]
    assert [s["attrs"]["step"] for s in snaps] == [5, 10, 15, 16]
    assert [s["parent"] for s in snaps] == [r["sid"] for r in saves]
    assert all(isinstance(s["attrs"]["writer_inflight"], bool)
               for s in snaps)
    for write in (r for r in recs if r["name"] == "ckpt.write"):
        kids = [r for r in recs if r["parent"] == write["sid"]]
        assert [k["name"] for k in kids] == [
            "ckpt.clean", "ckpt.chunks", "ckpt.seal", "ckpt.gc"]
        assert kids[3]["attrs"]["removed"] >= 0
        assert sum(k["dur"] for k in kids) <= write["dur"] + 1e-3
        # the job lay in the slot from the snapshot's end until the
        # writer took it: the write begins that much after the hand-over
        assert 0 <= write["attrs"]["queued_s"] < 60
        snap = next(s for s in snaps
                    if s["attrs"]["step"] == write["attrs"]["step"])
        assert write["t0"] >= snap["t0"] + snap["dur"] - 1e-3
    # the profiler's start and its stop
    assert [r["name"] for r in recs].count("train.profiler") == 2


def test_the_loops_thread_is_tiled_by_its_own_spans(traced_run):
    """Between the second and the last dispatch (the first is followed
    by the wait for its compile, which is start-up's and no window's)
    the loop's thread is under one of its own spans at least 95 % of
    the time (a CPU's steps are short; on the chip 99 %, PERF.md)."""
    recs = traced_run["records"]
    dispatch = [r for r in recs if r["name"] == "train.dispatch"]
    lo = dispatch[1]["t0"]
    hi = dispatch[-1]["t0"] + dispatch[-1]["dur"]
    mine = sorted((max(r["t0"], lo), min(r["t0"] + r["dur"], hi))
                  for r in recs if r["thread"] == dispatch[0]["thread"])
    covered, at = 0.0, lo
    for a, b in mine:
        if b > max(a, at):
            covered += b - max(a, at)
            at = b
    assert covered / (hi - lo) >= 0.95, covered / (hi - lo)


def test_first_dispatch_carries_the_cache_counts(traced_run):
    first, second = [r for r in traced_run["records"]
                     if r["name"] == "train.dispatch"][:2]
    assert first["attrs"]["step"] == 1
    assert {"cache_hits", "cache_misses"} <= set(first["attrs"])
    assert set(second["attrs"]) == {"step"}
    assert [r["name"] for r in traced_run["records"]].count(
        "train.log_fetch") == 4


def test_restore_leaves_a_span_only_when_it_restored(traced_run, tmp_path):
    import numpy as np

    from edl_tpu.train.checkpoint import CheckpointManager
    from edl_tpu.train.state import TrainStatus

    # the fresh run of the fixture looked for a checkpoint and found none
    assert not [r for r in traced_run["records"]
                if r["name"] == "ckpt.restore"]
    trace.collect(str(tmp_path / "p"))
    mgr = CheckpointManager(str(tmp_path / "ck"), 2)
    state = {"w": np.arange(8, dtype=np.float32)}
    assert mgr.restore(state) is None
    assert trace.finished("ckpt.restore") == []
    mgr.save(state, TrainStatus(step=3))
    restored, status = mgr.restore(state)
    mgr.close()
    (span,) = trace.finished("ckpt.restore")
    assert span["attrs"] == {"source": "disk", "version": 0, "bytes": 32}
    assert status.step == 3 and restored["w"][7] == 7
    names = [r["name"] for r in trace.finished("ckpt.")]
    assert names == ["ckpt.snapshot", "ckpt.chunks", "ckpt.seal", "ckpt.gc",
                     "ckpt.write", "ckpt.restore"]


def test_startup_children_sum_to_their_parent(traced_run):
    recs = traced_run["records"]
    (parent,) = [r for r in recs if r["name"] == "train.startup"]
    kids = [r for r in recs if r["parent"] == parent["sid"]]
    assert [k["name"] for k in kids] == ["startup." + p for p in STARTUP]
    assert sum(k["dur"] for k in kids) == pytest.approx(parent["dur"],
                                                       abs=1e-3)
    # consecutive: each begins where the one before it ended
    for a, b in zip(kids, kids[1:]):
        assert b["t0"] == pytest.approx(a["t0"] + a["dur"], abs=1e-3)
    first = min(r["t0"] for r in recs if r["name"] == "train.loader_wait")
    assert parent["t0"] + parent["dur"] <= first + 1e-3


def test_process_age_reads_the_kernels_clock():
    age = trace.process_age_s()
    assert age is not None and 0 < age < 86400
    time.sleep(0.05)
    assert 0.03 < trace.process_age_s() - age < 1.0


# -- the two switches, and neither ------------------------------------------

def _no_clock(*_a, **_k):
    raise AssertionError("a span site read a clock while spans are off")


def test_off_makes_no_record_reads_no_clock_touches_no_file(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(trace, "_start_sampler", START_SAMPLER)
    emitted = []
    monkeypatch.setattr(trace, "_emit", emitted.append)
    fake_time = type("T", (), {"time": _no_clock,
                               "perf_counter": _no_clock})
    monkeypatch.setattr(trace, "time", fake_time)
    annotated = []
    trace.set_annotator(lambda *a: annotated.append(a))
    assert not trace.enabled()
    with trace.span("train.dispatch", attrs={"step": 1}) as sp:
        assert sp is None
    assert trace.start_span("ckpt.write") is None
    trace.instant("launch.exit_seen")
    assert trace.event("startup.imports", 1.0) is None
    trace.flush()
    trace.flush_soon()
    assert emitted == [] and annotated == []
    assert trace.finished() == [] and os.listdir(tmp_path) == []
    _assert_no_sampler()


def _assert_no_sampler():
    assert trace._sampler is None
    assert trace.SAMPLER_THREAD not in [
        t.name for t in threading.enumerate()]


def test_loop_without_switches_emits_nothing(tmp_path, monkeypatch):
    """A TrainLoop with checkpoints and no profile directory: every span
    site of loop and checkpoint manager runs, none records."""
    import jax.numpy as jnp

    from edl_tpu.train.loop import LoopConfig, TrainLoop

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(trace, "_start_sampler", START_SAMPLER)
    calls = []
    monkeypatch.setattr(trace, "_emit", calls.append)

    def step(state, batch):
        return state + 1, {"loss": jnp.float32(0.0)}

    loop = TrainLoop(step, jnp.zeros(()), config=LoopConfig(
        num_epochs=1, log_every_steps=2, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every_steps=2))
    loop.run(lambda epoch: ({"x": jnp.ones((2,))} for _ in range(4)))
    assert loop.status.step == 4 and loop.ckpt_saves >= 2
    assert calls == []
    assert not glob.glob(str(tmp_path / "**" / "spans-*.jsonl"),
                         recursive=True)
    _assert_no_sampler()
    # the stall the loop's line reports is the manager's count
    stats = loop.ckpt_stats()
    assert stats["ckpt_save_stall_ms_total"] == round(
        loop.ckpt.stats()["save_stall_ms_total"], 3) > 0


def test_profile_dir_buffers_until_flush(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_TPU_PROFILE_DIR", str(tmp_path / "p"))
    trace.reconfigure()
    assert trace.enabled()
    with trace.span("train.dispatch", attrs={"step": 3}):
        pass
    assert not os.path.exists(tmp_path / "p")          # nothing per span
    assert [s["name"] for s in trace.finished()] == ["train.dispatch"]
    trace.flush()
    path = tmp_path / "p" / f"spans-{os.getpid()}.jsonl"
    (rec,) = [json.loads(line) for line in open(path)]
    assert rec["thread"] == threading.current_thread().name
    assert rec["attrs"] == {"step": 3} and rec["dur"] >= 0
    trace.flush()                                       # nothing new
    with trace.span("train.log_fetch"):
        pass
    trace.flush()                                       # appended once
    assert [json.loads(line)["name"] for line in open(path)] == [
        "train.dispatch", "train.log_fetch"]


def test_collect_is_the_command_lines_switch(tmp_path):
    assert not trace.enabled()
    trace.collect(str(tmp_path / "p"))
    assert trace.enabled() and trace.sink_dir() == str(tmp_path / "p")
    trace.instant("launch.exit_seen", attrs={"rc": -9})
    trace.flush()
    assert os.listdir(tmp_path / "p") == [f"spans-{os.getpid()}.jsonl"]


def test_edl_tpu_trace_keeps_its_write_per_span(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_TPU_TRACE", str(tmp_path / "t"))
    trace.reconfigure()
    trace.collect(str(tmp_path / "p"))      # the control-plane sink stays
    with trace.span("resize.adopt"):
        pass
    path = tmp_path / "t" / f"spans-{os.getpid()}.jsonl"
    assert [json.loads(line)["name"] for line in open(path)] == [
        "resize.adopt"]
    assert not os.path.exists(tmp_path / "p")


def test_annotator_wraps_scoped_spans_on_every_thread(tmp_path):
    import contextlib

    trace.collect(str(tmp_path))
    seen = []

    @contextlib.contextmanager
    def annotate(name, attrs):
        seen.append(("in", name, dict(attrs),
                     threading.current_thread().name))
        yield
        seen.append(("out", name))

    trace.set_annotator(annotate)
    with trace.span("ckpt.snapshot", attrs={"step": 7}):
        t = threading.Thread(target=lambda: trace.span(
            "ckpt.write").__enter__(), name="edl-ckpt-writer")
        t.start()
        t.join()
    trace.set_annotator(None)
    with trace.span("train.dispatch"):
        pass
    assert seen == [("in", "ckpt.snapshot", {"step": 7}, "MainThread"),
                    ("in", "ckpt.write", {}, "edl-ckpt-writer"),
                    ("out", "ckpt.snapshot")]


def test_phases_emit_parent_children_and_the_line(tmp_path):
    trace.collect(str(tmp_path))
    phases = trace.Phases("launch.reform", "launch", attrs={"who": "pod0"})
    phases.mark("exit_seen", {"rc": -9})
    time.sleep(0.02)
    phases.done("rejoin_wait")
    phases.done("barrier")
    phases.done("spawn", {"pid": 42})
    total, text = phases.emit()
    assert text.startswith("rejoin_wait 0.0") and "exit_seen" not in text
    parent, *kids = trace.finished("launch.")
    assert parent["name"] == "launch.reform" and parent["dur"] == round(
        total, 6) and parent["attrs"] == {"who": "pod0"}
    assert [k["name"] for k in kids] == [
        "launch.exit_seen", "launch.rejoin_wait", "launch.barrier",
        "launch.spawn"]
    assert all(k["parent"] == parent["sid"] for k in kids)
    assert kids[0]["dur"] == 0 and kids[0]["attrs"] == {"rc": -9}
    assert sum(k["dur"] for k in kids) == pytest.approx(total, abs=1e-4)
    # off: the line's numbers are still there, and no record is made
    trace.reconfigure()
    quiet = trace.Phases("train.startup", "startup", age_s=1.5)
    quiet.done("imports")
    total, text = quiet.emit()
    assert total >= 1.5 and text.startswith("imports 1.5")
    assert trace.finished() == []


def test_sigterm_flushes_a_profiled_process(tmp_path):
    """A trainer without a checkpoint directory dies of SIGTERM: its
    spans reach the profile directory first."""
    code = (
        "import os, sys, time\n"
        "from edl_tpu.obs import trace\n"
        "trace._start_sampler = lambda: None   # SIGTERM alone flushes\n"
        "with trace.span('train.dispatch', attrs={'step': 1}):\n"
        "    pass\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n")
    env = {**os.environ, "EDL_TPU_PROFILE_DIR": str(tmp_path),
           "PYTHONPATH": ROOT}
    env.pop("EDL_TPU_TRACE", None)
    child = subprocess.Popen([sys.executable, "-c", code], env=env,
                             stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "ready"
    assert os.listdir(tmp_path) == []
    child.send_signal(signal.SIGTERM)
    assert child.wait(timeout=30) == -signal.SIGTERM
    (rec,) = [json.loads(line) for line in
              open(tmp_path / f"spans-{child.pid}.jsonl")]
    assert rec["name"] == "train.dispatch" and rec["pid"] == child.pid


# -- the clock sampler -------------------------------------------------------

def test_a_switch_starts_one_sampler_and_reconfigure_stops_it(
        tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "_start_sampler", START_SAMPLER)
    _assert_no_sampler()
    trace.collect(str(tmp_path))
    trace.collect(str(tmp_path))
    assert trace.enabled()
    assert [t.name for t in threading.enumerate()].count(
        trace.SAMPLER_THREAD) == 1
    trace.reconfigure()
    _assert_no_sampler()
    monkeypatch.setenv("EDL_TPU_TRACE", str(tmp_path / "t"))
    assert trace.enabled()
    assert [t.name for t in threading.enumerate()].count(
        trace.SAMPLER_THREAD) == 1


def _child(code: str, env_switch: dict):
    env = {**os.environ, "PYTHONPATH": ROOT, **env_switch}
    for other in {"EDL_TPU_TRACE", "EDL_TPU_PROFILE_DIR"} - set(env_switch):
        env.pop(other, None)
    child = subprocess.Popen([sys.executable, "-c", code], env=env,
                             stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "ready"
    return child


def _records_of(child, directory) -> list[dict]:
    with open(os.path.join(directory, f"spans-{child.pid}.jsonl")) as f:
        return [json.loads(line) for line in f]


def _largest_gap(child, directory) -> dict:
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            gaps = [r for r in _records_of(child, directory)
                    if r["name"] == "host.clock_gap"]
        except FileNotFoundError:
            gaps = []
        if gaps:
            return max(gaps, key=lambda r: r["dur"])
        time.sleep(0.05)
    raise AssertionError("the sampler recorded no gap")


def test_a_stopped_process_leaves_a_gap_with_no_cpu_in_it(tmp_path):
    code = ("import time\n"
            "from edl_tpu.obs import trace\n"
            "assert trace.enabled()\n"
            "time.sleep(0.3)\n"           # the sampler is in its loop
            "print('ready', flush=True)\n"
            "time.sleep(60)\n")
    child = _child(code, {"EDL_TPU_TRACE": str(tmp_path)})
    try:
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.6)
        child.send_signal(signal.SIGCONT)
        gap = _largest_gap(child, tmp_path)
    finally:
        child.kill()
        child.wait(timeout=30)
    assert gap["thread"] == trace.SAMPLER_THREAD and gap["parent"] is None
    assert 0.3 < gap["dur"] < 5.0, gap
    # nothing of the process ran while it was stopped
    assert gap["attrs"]["cpu_s"] < 0.1, gap


@pytest.mark.parametrize("switch, asked", [
    ("EDL_TPU_TRACE", ""),
    # woken to flush and then kept from the lock: the wake is late as a
    # tick would be (a save's writer serialises right after `_save`
    # asked for the flush)
    ("EDL_TPU_PROFILE_DIR", "trace.flush_soon(), ")])
def test_a_held_interpreter_lock_leaves_a_gap_full_of_our_cpu(
        tmp_path, switch, asked):
    code = ("import threading, time\n"
            "from edl_tpu.obs import trace\n"
            "assert trace.enabled()\n"
            "t0 = time.perf_counter(); sum(range(2_000_000))\n"
            "n = int(2_000_000 * 0.8 / (time.perf_counter() - t0))\n"
            "time.sleep(0.3)\n"
            "print('ready', flush=True)\n"
            # one C call that never lets go of the lock
            f"t = threading.Thread(target=lambda: ({asked}sum(range(n))))\n"
            "t.start(); t.join(); time.sleep(60)\n")
    child = _child(code, {switch: str(tmp_path)})
    try:
        gap = _largest_gap(child, tmp_path)
    finally:
        child.kill()
        child.wait(timeout=30)
    assert 0.3 < gap["dur"] < 10.0, gap
    # one of our threads ran all through the gap
    assert gap["attrs"]["cpu_s"] > 0.5 * gap["dur"], gap


def test_a_flush_that_stands_still_is_a_gap_too(tmp_path, monkeypatch):
    """The next tick is due from before the sampler's own flush."""
    monkeypatch.setattr(trace, "_start_sampler", START_SAMPLER)
    write, slow = trace.flush, []

    def flush():
        if not slow:
            slow.append(time.sleep(0.5))
        write()
    monkeypatch.setattr(trace, "flush", flush)
    trace.collect(str(tmp_path))
    trace.flush_soon()
    deadline = time.monotonic() + 30
    while not trace.finished("host.clock_gap"):
        assert time.monotonic() < deadline
        time.sleep(0.05)
    gap = trace.finished("host.clock_gap")[0]
    assert slow and 0.2 < gap["dur"] < 5.0, gap
    # the sampler's first record says that the clock was watched
    assert trace.finished()[0]["name"] == "host.clock_sampler"
    assert trace.finished()[0]["thread"] == trace.SAMPLER_THREAD


@pytest.mark.parametrize("asked, after_s", [("", 2.0),
                                            ("trace.flush_soon()", 0.25)])
def test_a_buffered_span_outlives_sigkill_by_the_samplers_flush(
        tmp_path, asked, after_s):
    """Within a second of its end by the sampler's own flush; at once
    where the program asked (a save's records, whose step line a
    supervisor may answer with SIGKILL)."""
    code = ("import time\n"
            "from edl_tpu.obs import trace\n"
            "with trace.span('ckpt.snapshot', attrs={'step': 60}):\n"
            "    pass\n"
            f"{asked}\n"
            "print('ready', flush=True)\n"
            "time.sleep(60)\n")
    child = _child(code, {"EDL_TPU_PROFILE_DIR": str(tmp_path)})
    time.sleep(after_s)
    child.kill()
    assert child.wait(timeout=30) == -signal.SIGKILL
    names = [r["name"] for r in _records_of(child, tmp_path)]
    assert "ckpt.snapshot" in names


# -- names on the device work ------------------------------------------------

def _pallas_calls():
    ops = os.path.join(ROOT, "edl_tpu", "ops")
    for fname in sorted(os.listdir(ops)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(ops, fname)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "attr", getattr(node.func, "id", "")) \
                    == "pallas_call":
                yield f"{fname}:{node.lineno}", node


def test_every_pallas_call_has_a_name():
    calls = dict(_pallas_calls())
    assert len(calls) >= 4
    unnamed = [where for where, node in calls.items()
               if "name" not in {k.arg for k in node.keywords}]
    assert unnamed == []


def test_every_row_block_call_names_its_kernel():
    named = []
    for fname in ("pack.py", "opt_kernels.py"):
        with open(os.path.join(ROOT, "edl_tpu", "ops", fname)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "id", "") == "row_block_call":
                first = node.args[0]
                assert isinstance(first, ast.Constant) and isinstance(
                    first.value, str), (fname, node.lineno)
                named.append(first.value)
    assert set(named) == {"pack_amax", "pack_quantize", "opt_requant",
                          "opt_quantize", "opt_sgdm", "opt_adam"}


@pytest.fixture(scope="module")
def step_hlo():
    """Compiled HLO of a tiny LM step, as the trainer builds it."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.core import meta

    from edl_tpu.models.transformer import (Transformer, TransformerConfig,
                                            lm_loss_fused)
    from edl_tpu.train.state import TrainState
    from edl_tpu.train.step import make_train_step

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)
    model = Transformer(cfg)
    toks = jnp.zeros((2, 32), jnp.int32)
    state = TrainState.create(
        apply_fn=model.apply,
        params=meta.unbox(model.init(jax.random.PRNGKey(0), toks,
                                     train=False))["params"],
        tx=optax.adamw(1e-3))
    step = make_train_step(lm_loss_fused, donate=False)
    return step.lower(state, {"tokens": toks}).compile().as_text()


@pytest.mark.parametrize("scope", ["xent", "opt_update", "attn", "mlp",
                                   "ln", "embed"])
def test_step_hlo_carries_the_scope(step_hlo, scope):
    import re
    names = re.findall(r'op_name="([^"]+)"', step_hlo)
    assert any(f"/{scope}/" in n or f"({scope})/" in n for n in names), scope


def test_step_is_named_train_step_and_xent_names_its_three_matmuls(step_hlo):
    import re
    assert "HloModule jit_train_step" in step_hlo
    names = re.findall(r'op_name="([^"]+)"', step_hlo)
    xent = [n for n in names if "(xent)/" in n]
    # one sweep makes the loss and its gradient: the three products are
    # named inside the forward rule's loop, and the backward pass
    # replays nothing of it
    for inner in ("xent_logits", "xent_dh", "xent_dk"):
        assert any(n.endswith(f"/while/body/closed_call/{inner}/dot_general")
                   for n in xent), inner
    assert sum(n.endswith("dot_general") for n in set(xent)) == 3
    assert not any("transpose" in n for n in xent)
