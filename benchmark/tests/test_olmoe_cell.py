"""What PR 26 added to the benchmark: the mixture-of-experts cell's
driver walked on the CPU at a tiny size, `flops_moe.py` against a hand
count, and the five new readers on made-up evidence."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import flops_moe
from benchmark.reduce import moe_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
# the published widths, one layer
CFG = {"n_embd": 2048, "n_head": 16, "n_layer": 1, "n_inner": 1024,
       "vocab_size": 50304, "num_experts": 64, "num_experts_per_tok": 8}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cells_of(model_type: str) -> list[str]:
    """The cells whose configuration file is of ``model_type``."""
    out = []
    for w in BENCH["workloads"]:
        path = next(c["file"] for c in BENCH["configs"]
                    if c["name"] == w["config"])
        with open(os.path.join(ROOT, path)) as f:
            if json.load(f).get("model_type") == model_type:
                out.append(w["name"])
    return out


def test_cpu_rehearsal_of_the_moe_cell_is_refused():
    (cell,) = cells_of("olmoe")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483655", "--seconds", "6", "--trace", "1", "--rehearse",
         "benchmark/tests/tiny_olmoe.json"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]
    assert "moved_between_experts" in out.stderr


def test_flops_a_token_by_hand():
    # head 2048 x 50304; per layer q,k,v,o 4 x 2048^2, router 2048 x 64,
    # 8 experts x 3 matrices x 2048 x 1024
    head, attn, router = 103_022_592, 16_777_216, 131_072
    experts = 8 * 3 * 2048 * 1024
    assert experts == 50_331_648
    assert flops_moe.active_matmul_params(CFG) == head + attn + router \
        + experts
    scores = 0.5 * 12 * 1 * 4096 * 2048
    assert flops_moe.train_flops_per_token(CFG, 4096) == 6.0 * (
        head + attn + router + experts) + scores
    # 1.07 GFLOP a trained token, 60 % of it the head
    assert round(flops_moe.train_flops_per_token(CFG, 4096) / 1e9, 2) == 1.07
    two = flops_moe.train_flops_per_token({**CFG, "n_layer": 2}, 4096)
    assert two - flops_moe.train_flops_per_token(CFG, 4096) == 6.0 * (
        attn + router + experts) + scores


def test_grouped_matmuls_by_hand():
    flops, nbytes = flops_moe.grouped_matmuls_train(16384, CFG)
    rows = 16384 * 8
    # gate, up, down; forward, d-lhs, d-rhs; 2 x rows x 2048 x 1024 each
    assert flops == 9 * 2 * rows * 2048 * 1024 == 4_947_802_324_992
    assert nbytes == 9 * 2 * (rows * 3072 + 64 * 2048 * 1024)
    # compute-bound on a v5e: 25.1 ms against 11.8 ms of traffic
    assert flops / PEAK["bf16_flops_per_s"] > nbytes / PEAK["hbm_bytes_per_s"]


@pytest.mark.parametrize("instruction, tf_op, scope", [
    ("%ragged-dot-none.7", "ragged-dot-none", "grouped_matmul"),
    ("%fusion.3", "jit(train_step)/jvp(Transformer)/block0/mlp/moe_mlp/"
     "moe_router/top_k:", "moe_router"),
    ("%fusion.4", "jit(train_step)/transpose(jvp(Transformer))/block0/mlp/"
     "moe_mlp/moe_dispatch/gather:", "moe_dispatch"),
    ("%fusion.5", "jit(train_step)/jvp(Transformer)/block0/mlp/moe_mlp/"
     "moe_experts/mul:", "moe_experts"),
    ("%fusion.6", "jit(train_step)/jvp(Transformer)/block0/mlp/moe_mlp/"
     "moe_combine/dot_general:", "moe_combine"),
    ("%fusion.7", "jit(train_step)/jvp(Transformer)/block0/attn/rope/cos:",
     "rope"),
    ("%fusion.8", "jit(train_step)/jvp(Transformer)/block0/mlp/mlp_in/"
     "dot_general:", None),
    ("%flash_fwd.1", "jit(train_step)/jvp(Transformer)/block0/attn/"
     "flash_fwd/pallas_call", None),
    ("%copy.1", None, None),
])
def test_scope_of_an_operation(instruction, tf_op, scope):
    assert moe_scopes.scope_of(instruction, tf_op) == scope


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def evidence(by_scope, busy=1.0):
    trace = {"busy_s": busy,
             "devices": {0: {"whole_steps": 4}}}
    return {"trace": trace, "peak": PEAK,
            "moe_scopes": {"by_scope": by_scope, "busy_s": busy}}


def test_the_trace_readers_on_a_made_up_table():
    cell = NS(config={**CFG, "run": {"seq_len": 4096}},
              tokens_per_step=16384)
    ev = evidence({"moe_router": 0.02, "moe_dispatch": 0.05,
                   "moe_combine": 0.03, "moe_experts": 0.04,
                   "grouped_matmul": 0.25, "rope": 0.01})
    assert reader("moe_time_share").read(cell, ev) == pytest.approx(39.0)
    assert reader("moe_dispatch_time_share").read(cell, ev) \
        == pytest.approx(10.0)
    # 4 steps in 0.25 s: 62.5 ms a step against 25.1 ms at the peak
    least = 4_947_802_324_992 / 197e12
    assert reader("moe_ffn_roofline").read(cell, ev) == pytest.approx(
        100 * least / 0.0625)
    assert ev["moe_roofline_bound"] == "compute"


@pytest.mark.parametrize("name", ["moe_time_share",
                                  "moe_dispatch_time_share",
                                  "moe_ffn_roofline", "moe_max_load",
                                  "active_mfu"])
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent commit, or a dense model: no such scope, counter or
    key; the reader returns nothing and does not raise."""
    dense = NS(config={"n_embd": 2048, "n_head": 16, "n_layer": 8,
                       "n_inner": 8192, "vocab_size": 50257,
                       "run": {"seq_len": 2048}}, tokens_per_step=12288)
    assert reader(name).read(dense, evidence({})) is None
    assert reader(name).read(dense, {}) is None


def test_counters_of_the_step_lines():
    from benchmark.drivers.train_steady_ref import step_counters
    lines = [(1.0, "I edl_tpu.train.loop [7] epoch 0 step 10: loss=11.4012 "
              "moe_balance=1.0312 moe_dropped=0.0000 moe_max_load=1.4375 "
              "moe_z=0.0412 ppl=89421.1 4.1 samples/s"),
             (2.0, "I edl_tpu.train.loop [7] first-step-complete"),
             (3.0, "I edl_tpu.train.loop [7] epoch 0 step 20: loss=11.39 "
              "moe_balance=1.03 moe_dropped=0.0000 moe_max_load=1.5625 "
              "moe_z=0.04 ppl=8e4 4.1 samples/s")]
    got = step_counters(lines)
    assert [c["step"] for c in got] == [10, 20]
    assert got[0]["moe_max_load"] == 1.4375 and got[0]["loss"] == 11.4012
    assert reader("moe_max_load").read(None, {"step_counters": got}) \
        == pytest.approx(1.5)


def test_active_mfu_by_hand():
    cell = NS(config={**CFG, "run": {"seq_len": 4096}},
              tokens_per_step=16384)
    # two log windows of 10 steps in 2 s each: 81,920 tokens/s
    quiet = [((0.0, 10, 0.0), (2.0, 20, 0.0)), ((2.0, 20, 0.0),
                                                (4.0, 30, 0.0))]
    ev = {"quiet_windows": quiet, "peak": PEAK, "device": {"count": 1}}
    per_token = flops_moe.train_flops_per_token(CFG, 4096)
    assert reader("active_mfu").read(cell, ev) == pytest.approx(
        100 * 81920 * per_token / 197e12)


def test_the_trace_readers_on_the_recorded_trace(tmp_path):
    """`data/olmoe_d1_traced.xplane.pb.gz` is the profiler's file of the
    cell's first traced run on a TPU v5e (steps 40-45 of `lm_train
    --arch olmoe` at the published widths; my chip run, PR 26, seed
    2262483702): five device steps, four whole periods of 249.8 ms. The
    run's own result line read `moe_time_share` 29.41, `moe_dispatch_
    time_share` 7.59, `moe_ffn_roofline` 54.21."""
    import gzip
    import shutil

    from benchmark.reduce import xplane
    here = os.path.dirname(os.path.abspath(__file__))
    (tmp_path / "trace").mkdir()
    with gzip.open(os.path.join(here, "data",
                                "olmoe_d1_traced.xplane.pb.gz")) as src, \
            open(tmp_path / "trace" / "d1.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = xplane.reduce_dir(str(tmp_path / "trace"), 1)
    assert trace["devices"][0]["step_name"].startswith("jit_train_step(")
    assert trace["devices"][0]["whole_steps"] == 4
    cell = NS(config={**CFG, "run": {"seq_len": 4096}},
              tokens_per_step=16384)
    ev = {"trace": trace, "peak": PEAK}
    assert reader("moe_time_share").read(cell, ev) == pytest.approx(
        29.41, abs=0.01)
    assert reader("moe_dispatch_time_share").read(cell, ev) \
        == pytest.approx(7.59, abs=0.01)
    assert reader("moe_ffn_roofline").read(cell, ev) == pytest.approx(
        54.21, abs=0.01)
    table = moe_scopes.of(ev)["by_scope"]
    assert set(table) == {"grouped_matmul", "moe_combine", "moe_experts",
                          "moe_dispatch", "moe_router", "rope"}
    # nine grouped matmuls a step: 46 ms of the 250
    assert table["grouped_matmul"] / 4 == pytest.approx(0.0463, abs=2e-4)
