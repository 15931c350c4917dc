"""What PR 33 added to the benchmark: the hybrid state-space cell's
driver walked on the CPU at a tiny size, faults sent through the
driver's `reference_check`, `flops_hybrid.py` against a hand count, and
the five new readers on made-up evidence."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import flops_hybrid
from benchmark.harness.flops import roofline_seconds
from benchmark.reduce import ssm_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def hybrid_configs() -> list[tuple[str, dict]]:
    """(cell, configuration file) of the cells with state-space layers."""
    out = []
    for w in BENCH["workloads"]:
        path = next(c["file"] for c in BENCH["configs"]
                    if c["name"] == w["config"])
        with open(os.path.join(ROOT, path)) as f:
            cfg = json.load(f)
        if "mamba_n_heads" in cfg:
            out.append((w["name"], cfg))
    return out


((CELL, CFG),) = hybrid_configs()


def test_cpu_rehearsal_of_the_hybrid_cell_is_refused():
    # one device, also where tests/conftest.py's eight are in the air
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483655", "--seconds", "12", "--trace", "1", "--rehearse",
         "benchmark/tests/tiny_granite_hybrid.json"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]
    for reading in ("token_loss_rms_diff", "grad_rel_err",
                    "step_size_grad_rel_err", "update_rel_err",
                    "timed_loss_diff"):
        assert reading in out.stderr


TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_granite_hybrid.json")


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, stand_in_cell):
    """What `train_steady_ref.reference_check` reads of a cell, on
    shards of the tiny configuration, with the checker replaced by
    `tools/hybrid_controls.py` (the checker itself unless
    EDL_BENCH_CONTROL names a fault)."""
    from benchmark.harness.shards import make_shards
    work = tmp_path_factory.mktemp("hybrid_controls")
    with open(TINY) as f:
        config = json.load(f)
    config["reference"]["checker"] = "benchmark.tools.hybrid_controls"
    config["run"]["seq_len"] = 512  # two chunks: a state crosses over
    path = work / "config.json"
    path.write_text(json.dumps(config))
    data = work / "data"
    make_shards(str(data), 1, 8, config["run"]["seq_len"],
                config["vocab_size"], 2290033100)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PYTHONPATH": ROOT}
    return stand_in_cell(work, config, path, data, env)


@pytest.fixture(scope="module")
def honest(tiny_cell):
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env.pop("EDL_BENCH_CONTROL", None)
    # the trainer's logged loss stands in as the reference's own
    got = reference_check(tiny_cell, 1, 0.0)
    return got["reference_loss"]


def test_the_program_as_it_is_comes_out_correct(tiny_cell, honest):
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env.pop("EDL_BENCH_CONTROL", None)
    got = reference_check(tiny_cell, 1, honest)
    assert got["ok"] and got["refused"] == []


# the fault, and the reading that has to refuse it
@pytest.mark.parametrize("fault, reading", [
    ("reference_float8_e4m3", "token_loss_rms_diff"),
    ("no_residual_multiplier", "token_loss_rms_diff"),
    ("chunk_states_dropped", "token_loss_rms_diff"),
    ("scan_backward_no_step_size_gradient", "grad_rel_err"),
    ("scan_backward_no_decay_gradient", "step_size_grad_rel_err"),
    ("scan_backward_dB_dC_swapped", "grad_rel_err"),
    ("state_left_unchanged", "update_rel_err"),
])
def test_a_fault_comes_out_not_correct(tiny_cell, honest, fault, reading):
    """Each through the accepted driver's own comparison. The faults of
    the backward pass and of the optimizer leave every forward reading
    where it was: only the timed program's readings see them."""
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env["EDL_BENCH_CONTROL"] = fault
    got = reference_check(tiny_cell, 1, honest)
    limits = tiny_cell.config["reference"]
    assert not got["ok"]
    if reading == "token_loss_rms_diff":
        assert got[reading] > limits["token_loss_rms_tolerance"]
    else:
        assert any(r.startswith(reading) for r in got["refused"])
        assert got["token_loss_rms_diff"] \
            <= limits["token_loss_rms_tolerance"]
    if reading == "update_rel_err":
        assert got[reading] == pytest.approx(1.0, abs=1e-6)


def test_the_file_is_the_source_cut_as_it_says():
    """Every key of the source's config.json at the top level, equal to
    it but for the three the file lists; the pattern cut from its
    start; the keys the harness reads equal to the source's."""
    source = {k: v for k, v in CFG["source_config"].items() if k != "what"}
    changed = {k for k, v in source.items() if CFG[k] != v}
    assert changed == set(CFG["reduced_from_source"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert CFG["layer_types"] == source["layer_types"][:10]
    assert CFG["layer_types"].count("mamba") == 9
    assert CFG["vocab_size"] * 4 == source["vocab_size"]
    assert (CFG["n_embd"], CFG["n_head"], CFG["n_layer"], CFG["n_inner"]) \
        == (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_hidden_layers"], CFG["shared_intermediate_size"])
    # the trainer takes the pattern and the mixers' sizes from
    # `granite_hybrid_config`, not from flags: they are the file's
    from benchmark.reference.check_granite_hybrid import program_config
    built = program_config(CFG)
    assert list(built.layer_types) == CFG["layer_types"]
    assert (built.kv_heads, built.ssm_heads, built.ssm_head_dim,
            built.ssm_state, built.ssm_conv, built.ssm_chunk) == tuple(
        CFG[k] for k in ("num_key_value_heads", "mamba_n_heads",
                         "mamba_d_head", "mamba_d_state", "mamba_d_conv",
                         "mamba_chunk_size"))
    assert (built.attn_scale, built.embed_scale, built.residual_scale,
            built.logits_scale, built.norm_eps) == (
        CFG["attention_multiplier"], CFG["embedding_multiplier"],
        CFG["residual_multiplier"], CFG["logits_scaling"],
        CFG["rms_norm_eps"])


def test_flops_a_token_by_hand():
    # a mamba layer: in_proj 2048 x (4096 + 4352 + 64), out_proj 4096 x
    # 2048; the attention layer: q, o 2048^2 each, k, v 2048 x 512 each;
    # the MLP 3 x 2048 x 8192; the tied head 2048 x 25088, once
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp, head = 3 * 2048 * 8192, 2048 * 25088
    assert (mamba, attn, mlp, head) == (25_821_184, 10_485_760, 50_331_648,
                                        51_380_224)
    params = 9 * (mamba + mlp) + attn + mlp + head
    assert flops_hybrid.matmul_params(CFG) == params == 797_573_120
    # the scan, one token of one layer, forward, the masked products at
    # their causal half (a position reads 257 / 2 positions of its chunk
    # on average): the shared scores 2 x 128.5 x 128, and a head the
    # masked product 2 x 128.5 x 64, the chunk's own state and the read
    # of the entering one 2 x 128 x 64 each
    scan = 257 * 128 + 64 * (257 * 64 + 2 * 2 * 128 * 64)
    assert flops_hybrid.scan_flops_per_token(CFG) == scan == 3_182_720
    scores = 0.5 * 12 * 1 * 8192 * 2048
    assert flops_hybrid.train_flops_per_token(CFG, 8192) == 6.0 * params \
        + 3.0 * 9 * scan + scores
    # 4.97 GFLOP a trained token: 96.2 % matrix products, 1.7 % the scan
    assert round(flops_hybrid.train_flops_per_token(CFG, 8192) / 1e9, 2) \
        == 4.97
    one_more = flops_hybrid.train_flops_per_token(
        {**CFG, "layer_types": [*CFG["layer_types"], "mamba"]}, 8192)
    assert one_more - flops_hybrid.train_flops_per_token(CFG, 8192) \
        == 6.0 * (mamba + mlp) + 3.0 * scan


def test_scan_operations_and_bytes_by_hand():
    flops, nbytes = flops_hybrid.scan_train(16384, CFG)
    assert flops == 3 * 16384 * 3_182_720 == 156_437_053_440
    # a token, bf16: x and y 4096 x 2 B each, B and C 128 x 2 B each,
    # dt 64 x 4 B: 8,960 B in, 8,192 B out forward; backward reads the
    # inputs and dy and writes a gradient for every input
    inputs = 4096 * 2 + 2 * 128 * 2 + 64 * 4
    assert inputs == 8960
    assert nbytes == 16384 * ((inputs + 8192) + (inputs + 8192 + inputs))
    # on a v5e: 0.79 ms of products against 0.87 ms of traffic a layer,
    # so memory-bound, narrowly (the whole (Q, Q) products would be 1.06
    # ms and compute-bound)
    least, bound = roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and least == pytest.approx(0.8653e-3, rel=1e-3)
    assert flops / PEAK["bf16_flops_per_s"] == pytest.approx(0.7941e-3,
                                                             rel=1e-3)


def test_attention_operations_and_bytes_by_hand():
    flops, nbytes = flops_hybrid.attention_train(2, CFG, 8192)
    # one attention layer; a product over the causal half of 8192 x 8192
    # at head size 64 for 2 x 32 query heads, seven of them
    product = 8192 * 8192 * 64 * 2 * 32
    assert flops == 7 * product == 1_924_145_348_608
    # six passes over (2, 8192, 32, 64) and six over (2, 8192, 8, 64), bf16
    assert nbytes == 6 * 2 * 8192 * (32 + 8) * 64 * 2 == 503_316_480
    least, bound = roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and least == pytest.approx(9.767e-3, rel=1e-3)
    two = flops_hybrid.attention_train(
        2, {**CFG, "layer_types": [*CFG["layer_types"], "attention"]}, 8192)
    assert two == (2 * flops, 2 * nbytes)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(train_step)/jvp(Transformer)/block0/ssm/ssm_in_proj/in_proj/"
     "dot_general:", "ssm_in_proj"),
    ("jit(train_step)/jvp(Transformer)/block0/ssm/ssm_conv/mul:",
     "ssm_conv"),
    ("jit(train_step)/jvp(Transformer)/checkpoint/block3/ssm/ssm_scan/"
     "dot_general:", "ssm_scan"),
    ("jit(train_step)/transpose(jvp(Transformer))/block3/ssm/ssm_scan/"
     "exp:", "ssm_scan"),
    ("jit(train_step)/jvp(Transformer)/block3/ssm/ssm_gate_norm/"
     "softplus:", "ssm_gate_norm"),
    ("jit(train_step)/jvp(Transformer)/block0/ssm/ssm_gate_norm/norm/"
     "rsqrt:", "ssm_gate_norm"),
    ("jit(train_step)/jvp(Transformer)/block0/ssm/ssm_out_proj/out_proj/"
     "dot_general:", "ssm_out_proj"),
    ("jit(train_step)/jvp(Transformer)/block5/attn/flash_fwd/pallas_call",
     None),
    ("jit(train_step)/jvp(Transformer)/block0/mlp/mlp_gate/dot_general:",
     None),
    (None, None),
])
def test_scope_of_an_operation(tf_op, scope):
    assert ssm_scopes.scope_of(tf_op) == scope


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def evidence(by_scope, busy=1.0):
    trace = {"busy_s": busy, "devices": {0: {"whole_steps": 4}}}
    return {"trace": trace, "peak": PEAK,
            "ssm_scopes": {"by_scope": by_scope, "busy_s": busy}}


def test_the_trace_readers_on_a_made_up_table():
    cell = NS(config=CFG, tokens_per_step=16384)
    ev = evidence({"ssm_in_proj": 0.10, "ssm_conv": 0.02, "ssm_scan": 0.16,
                   "ssm_gate_norm": 0.01, "ssm_out_proj": 0.05})
    assert reader("ssm_time_share").read(cell, ev) == pytest.approx(34.0)
    assert reader("ssm_scan_time_share").read(cell, ev) \
        == pytest.approx(16.0)
    # 4 steps in 0.16 s: 40 ms a step against nine layers' traffic
    least = 9 * 16384 * (17152 + 26112) / 819e9
    assert reader("ssm_scan_roofline").read(cell, ev) == pytest.approx(
        100 * least / 0.040)
    assert ev["ssm_roofline_bound"] == "memory"


def test_hybrid_flash_roofline_on_a_made_up_table():
    cell = NS(config=CFG, tokens_per_step=16384)
    ev = evidence({})
    # four steps; the forward ran twice a step (remat's replay)
    ev["scopes"] = {"by_kernel": {"flash_fwd": 0.0624, "flash_bwd_dkdv":
                                  0.0540, "flash_bwd_dq": 0.0410},
                    "by_scope": {}, "busy_s": 1.0}
    assert reader("hybrid_flash_roofline").read(cell, ev) == pytest.approx(
        100 * (1_924_145_348_608 / 197e12) / (0.1574 / 4))
    assert ev["hybrid_flash_roofline_bound"] == "compute"
    ev["scopes"]["by_kernel"] = {}
    assert reader("hybrid_flash_roofline").read(cell, ev) is None


@pytest.mark.parametrize("name", ["ssm_time_share", "ssm_scan_time_share",
                                  "ssm_scan_roofline", "hybrid_mfu",
                                  "hybrid_flash_roofline"])
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent commit, or a model without state-space layers: no
    such scope or key; the reader returns nothing and does not raise."""
    dense = NS(config={"n_embd": 2048, "n_head": 16, "n_layer": 8,
                       "n_inner": 8192, "vocab_size": 50257,
                       "run": {"seq_len": 2048}}, tokens_per_step=12288)
    assert reader(name).read(dense, evidence({})) is None
    assert reader(name).read(dense, {}) is None
    # the scopes are there and the configuration is not a hybrid's
    table = evidence({"ssm_scan": 0.1})
    table["quiet_windows"] = [((0.0, 10, 0.0), (2.0, 20, 0.0))]
    table["device"] = {"count": 1}
    table["scopes"] = {"by_kernel": {"flash_fwd": 0.1}, "by_scope": {},
                       "busy_s": 1.0}
    if name in ("ssm_scan_roofline", "hybrid_mfu", "hybrid_flash_roofline"):
        assert reader(name).read(dense, table) is None


def test_hybrid_mfu_by_hand():
    cell = NS(config=CFG, tokens_per_step=16384)
    # two log windows of 10 steps in 8 s each: 20,480 tokens/s
    quiet = [((0.0, 10, 0.0), (8.0, 20, 0.0)), ((8.0, 20, 0.0),
                                                (16.0, 30, 0.0))]
    ev = {"quiet_windows": quiet, "peak": PEAK, "device": {"count": 1}}
    per_token = flops_hybrid.train_flops_per_token(CFG, 8192)
    assert reader("hybrid_mfu").read(cell, ev) == pytest.approx(
        100 * 20480 * per_token / 197e12)
    # the chip's peak is 39,620 tokens/s of this model: no reading of a
    # run that took its time can pass 100 %
    assert 197e12 / per_token == pytest.approx(39_620, rel=1e-3)


def test_the_cell_is_in_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads") for m in
             BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("train_tokens_per_s", "step_ms", "loop_stall_share",
                 "loader_wait_share", "device_idle_share",
                 "pallas_time_share", "fused_xent_time_share",
                 "optimizer_time_share", "flash_bwd_time_share",
                 "ssm_time_share", "ssm_scan_time_share",
                 "ssm_scan_roofline", "hybrid_mfu",
                 "hybrid_flash_roofline"):
        assert CELL in lists[name], name
    # their counts are the GPT-2 block's
    for name in ("mfu", "flash_attention_roofline"):
        assert CELL not in lists[name], name
