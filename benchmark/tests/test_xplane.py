"""The trace reduction: on a trace recorded on the chip, and on made-up
planes where the answer can be worked by hand.

`data/tiny_step.xplane.pb.gz` is the profiler's file of five steps of
the tiny rehearsal model (`tiny_lm.json`) on a TPU v5e (my chip run,
PR 22): host-bound, so most of the window is idle; four whole steps;
24 Mosaic calls (2 layers x fwd, dK/dV, dQ x 4 steps)."""

import gzip
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmark.reduce import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny_step.xplane.pb"
    with gzip.open(os.path.join(HERE, "data",
                                "tiny_step.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce_file(str(path))


def test_recorded_trace_window_steps_and_busy(recorded):
    assert list(recorded["devices"]) == [0]
    dev = recorded["devices"][0]
    assert dev["step_name"].startswith("jit_step(")
    assert dev["whole_steps"] == 4 and len(dev["ops"]) == 1688
    assert recorded["window_s"] == pytest.approx(0.006142633, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(0.000436875, rel=1e-6)
    assert xplane.step_ms(recorded) == pytest.approx(1.4808485, rel=1e-6)


def test_recorded_trace_kernels_and_breakdown(recorded):
    dev = recorded["devices"][0]
    kernels = [op for op in dev["ops"] if op[3] == "pallas"]
    assert len(kernels) == 24
    assert all(op[2].startswith("%attn") for op in kernels)
    by_kind = xplane.seconds_by(recorded, lambda op: op[3])
    assert by_kind["pallas"] == pytest.approx(0.000162843, rel=1e-6)
    assert by_kind["pallas"] + by_kind["other"] <= recorded["busy_s"] * (
        1 + 1e-9)
    assert xplane.exposed_collective_s(recorded) == 0.0
    out = xplane.breakdown(recorded)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == \
        "%attn tpu_custom_call (f32[16,128,32], f32[16,128,1])"
    where, seconds = out["idle_gaps"][0]
    assert where.startswith("between steps")
    assert seconds == pytest.approx(0.005702229, rel=1e-6)


def test_interval_arithmetic():
    cover = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert cover == [(0, 3), (5, 8)]
    assert xplane.length(cover) == 6
    assert xplane.clip(cover, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(0, 3), (5, 8)]) == [(3, 5), (8, 10)]
    assert xplane.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]


@pytest.mark.parametrize("text, label, kind", [
    ('%attn.24 = (bf16[96,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
     'f32[96,2048,1]{2,1,0:T(8,128)}) custom-call(bf16[96,2048,128]{2,1,0} '
     '%bitcast.1563), custom_call_target="tpu_custom_call"',
     "%attn.24 tpu_custom_call (bf16[96,2048,128], f32[96,2048,1])",
     "pallas"),
    ('%custom-call.2 = f32[12282]{0:T(1024)} custom-call(), '
     'custom_call_target="AllocateBuffer"',
     "%custom-call.2 AllocateBuffer f32[12282]", "other"),
    ("%all-gather-start.3 = (f32[512,2048]{1,0}, f32[2048,2048]{1,0}) "
     "all-gather-start(f32[512,2048]{1,0} %p), dimensions={0}",
     "%all-gather-start.3 all-gather-start (f32[512,2048], f32[2048,2048])",
     "collective"),
    ("%all-reduce.7 = f32[2048]{0:T(1024)} all-reduce(f32[2048]{0} %x), "
     "to_apply=%add", "%all-reduce.7 all-reduce f32[2048]", "collective"),
    ("%while.7 = (s32[]{:T(128)}, f32[12282,2048]{1,0:T(8,128)}) "
     "while((s32[]{:T(128)}, f32[12282,2048]{1,0}) %tuple), body=%b",
     "%while.7 while (s32[], f32[12282,2048])", "container"),
    ("%fusion.1 = bf16[6,2048]{1,0} fusion(bf16[6,2048]{1,0} %a), "
     "kind=kLoop", "%fusion.1 fusion bf16[6,2048]", "other"),
])
def test_parse_op(text, label, kind):
    got_label, _, got_kind = xplane.parse_op(text)
    assert (got_label, got_kind) == (label, kind)


def event(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def test_made_up_plane_with_a_collective_and_a_loop():
    # two steps of 100 ns at 0 and 100, a third starts at 200: the window
    # is 0..200. In each: a fusion 0-40, an all-reduce 40-60 with nothing
    # beside it (exposed), a while 60-90 holding a fusion 60-80 and an
    # all-gather-done 70-90, of which 80-90 is exposed; 90-100 is idle.
    fus = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    red = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %a), to_apply=%s"
    loop = "%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), body=%b"
    gat = "%all-gather-done.1 = f32[8]{0} all-gather-done(f32[2]{0} %a)"
    ops = []
    for t in (0, 100, 200):
        ops += [event(fus, t, 40), event(red, t + 40, 20),
                event(loop, t + 60, 30), event(fus, t + 60, 20),
                event(gat, t + 70, 20)]
    plane = NS(lines=[
        NS(name="XLA Modules", events=[event("jit_step(1)", t, 100)
                                       for t in (0, 100, 200)]
           + [event("jit_other(2)", 96, 2)]),
        NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[event(gat, 0, 300)])])
    dev = xplane.reduce_plane(plane)
    assert dev["step_name"] == "jit_step(1)" and dev["whole_steps"] == 2
    assert dev["window_ns"] == (0.0, 200.0)
    assert xplane.length(dev["busy"]) == 180.0
    trace = {"devices": {0: dev}, "busy_s": 180e-9, "window_s": 200e-9}
    assert xplane.step_ms(trace) == pytest.approx(100e-6)
    assert xplane.exposed_collective_s(trace) == pytest.approx(60e-9)
    by_kind = xplane.seconds_by(trace, lambda op: op[3])
    assert by_kind == {"other": pytest.approx(120e-9),
                       "collective": pytest.approx(80e-9)}
    gaps = xplane.breakdown(trace)["idle_gaps"]
    assert gaps == [["inside the step, after %all-gather-done "
                     "all-gather-done f32[8]", pytest.approx(20e-9)]]
