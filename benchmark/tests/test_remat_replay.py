"""`layer_metrics/remat_replay_time_share.py` on made-up planes where
the answer can be worked by hand, and on the recorded trace of a step
that replays nothing."""

import gzip
import os
import shutil

import pytest

from benchmark.layer_metrics import remat_replay_time_share as reader
from benchmark.reduce import scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
REPLAY = ("jit(train_step)/transpose(jvp(Transformer))/jvp(Transformer)/"
          "checkpoint/rematted_computation/block3/")


@pytest.mark.parametrize("tf_op, inside", [
    (REPLAY + "mlp/mlp_up/dot_general:", True),
    (REPLAY + "ssm/ssm_scan/jit(_forward_pallas)/ssd_fwd/pallas_call:", True),
    # the backward of the same block, and its first forward
    ("jit(train_step)/transpose(jvp(Transformer))/checkpoint/block3/mlp/"
     "mlp_up/dot_general:", False),
    ("jit(train_step)/jvp(Transformer)/checkpoint/block3/mlp/mlp_up/"
     "dot_general:", False),
    ("jit(train_step)/opt_update/rematted_computation_of_mine/add:", False),
    ("", False), (None, False),
])
def test_an_operation_is_replayed_by_its_name_stack(tf_op, inside):
    assert reader.replayed(tf_op) is inside


def _trace(ops_by_device, busy_ns):
    return {"path": "", "busy_s": busy_ns / 1e9, "devices": {
        dev: {"window_ns": (0, 1000), "ops": ops}
        for dev, ops in ops_by_device.items()}}


def test_share_of_busy_time_averaged_over_the_chips(monkeypatch):
    ops = [(0, 100, "%fusion.1 fusion f32[8]", "other"),
           (100, 400, "%ssd_fwd.2 tpu_custom_call bf16[8]", "pallas"),
           (400, 500, "%fusion.7 fusion bf16[8]", "other"),
           (500, 1400, "%copy.9 copy f32[8]", "other"),     # cut at 1000
           (0, 600, "%while.1 while (f32[8])", "container")]
    names = {"%fusion.1": "jit(train_step)/opt_update/add:",
             "%ssd_fwd.2": REPLAY + "ssm/ssm_scan/ssd_fwd/pallas_call:",
             "%fusion.7": REPLAY + "mlp/mlp_up/dot_general:",
             "%copy.9": REPLAY + "ln/ln_mlp/mul:",
             "%while.1": REPLAY + "while:"}
    monkeypatch.setattr(scopes, "tf_ops", lambda path: {0: names, 1: {}})
    # chip 0 replays 300 + 100 + 500 ns, chip 1 names nothing
    ev = {"trace": _trace({0: ops, 1: ops}, 1000)}
    assert reader.read(None, ev) == pytest.approx(100 * 450 / 1000)


def test_nothing_where_nothing_is_replayed(monkeypatch, tmp_path):
    assert reader.read(None, {}) is None
    ops = [(0, 100, "%fusion.1 fusion f32[8]", "other")]
    monkeypatch.setattr(scopes, "tf_ops", lambda path: {
        0: {"%fusion.1": "jit(train_step)/jvp(T)/block0/mlp/add:"}})
    assert reader.read(None, {"trace": _trace({0: ops}, 100)}) is None
    monkeypatch.undo()
    # the tiny loop recorded on a chip trains without remat
    trace_dir = tmp_path / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data",
                                "tiny_traced.xplane.pb.gz")) as src, \
            open(trace_dir / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = xplane.reduce_dir(str(tmp_path), 1)
    assert trace["busy_s"] > 0
    assert reader.read(None, {"trace": trace}) is None
