"""What PR 51 added to the benchmark: the JoyAI-LLM-Flash share's cell
walked through the driver on the CPU at a tiny size, faults sent through
the driver's `reference_check`, the configuration file against its
source, and the five new readers on made-up evidence, on a program
without their scopes and on a recorded trace of another model."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import flops_joyai

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "joyai_d5e16v8.steady"
ENTRY = next(c for c in BENCH["configs"]
             if c["name"] == "joyai-llm-flash-d5-e16v8")
with open(os.path.join(ROOT, ENTRY["file"])) as f:
    CFG = json.load(f)
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_joyai.json")
ONE_DEVICE = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
NEW = ("mla_flash_roofline", "joyai_mfu", "mla_proj_time_share",
       "mtp_time_share", "mtp_loss_gap")


READINGS = ("token_loss_rms_diff", "mtp_token_loss_rms_diff",
            "routing_diff_share", "grad_rel_err", "update_rel_err",
            "bias_update_err", "timed_loss_diff", "timed_mtp_loss_diff")
_rehearsed: dict = {}  # the first rehearsal's readings, for the second


@pytest.mark.parametrize("run", ["first", "again"])
def test_cpu_rehearsal_of_the_cell_is_refused(run):
    """Twice in this checkout, on the same seed: the second run's
    reference child compiles nothing (the first, or any earlier run
    here, left its programs in `cell.REF_CACHE`) and reads what the
    first read, to the digit."""
    import re
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483655", "--seconds", "12", "--trace", "1", "--rehearse",
         "benchmark/tests/tiny_joyai.json"], cwd=ROOT,
        env={**os.environ, **ONE_DEVICE}, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]
    said = re.search(r"the reference child: \[check \+ *[\d.]+s\] done: "
                     r"compiled (\d+) programs and read (\d+) from (\S+)",
                     out.stderr)
    assert said and said.group(3) == os.path.join(ROOT, ".jax_cache_ref")
    readings = {name: re.search(rf"'{name}': ([^,}}]+)", out.stderr).group(1)
                for name in READINGS + ("reference_loss",)}
    if run == "first":
        _rehearsed.update(readings)
        return
    assert int(said.group(1)) == 0 and int(said.group(2)) > 0
    if _rehearsed:  # the first ran in this process
        assert readings == _rehearsed


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, stand_in_cell):
    """What `train_steady_ref.reference_check` reads of a cell, on
    shards of the tiny configuration, with the checker replaced by
    `tools/joyai_controls.py` (the checker itself unless
    EDL_BENCH_CONTROL names a fault)."""
    from benchmark.harness.shards import make_shards
    work = tmp_path_factory.mktemp("joyai_controls")
    with open(TINY) as f:
        config = json.load(f)
    config["reference"]["checker"] = "benchmark.tools.joyai_controls"
    path = work / "config.json"
    path.write_text(json.dumps(config))
    data = work / "data"
    make_shards(str(data), 1, 8, config["run"]["seq_len"],
                config["vocab_size"], 2290051100)
    env = {**os.environ, **ONE_DEVICE, "PYTHONPATH": ROOT}
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
    assert got["routing_diff_share"] == 0.0  # float32 on both sides here
    assert got["reference_loss"] == pytest.approx(
        got["main_loss"] + 0.3 * got["mtp_loss"])


# the fault, and the reading that has to refuse it
@pytest.mark.parametrize("fault, reading", [
    ("reference_float8_e4m3", "token_loss_rms_diff"),
    ("rope_on_the_whole_head", "token_loss_rms_diff"),
    ("pairs_on_q_halves_on_k", "token_loss_rms_diff"),
    ("k_pe_normed", "token_loss_rms_diff"),
    ("scale_from_the_value_size", "token_loss_rms_diff"),
    ("latent_norms_left_out", "token_loss_rms_diff"),
    ("no_shared_expert", "token_loss_rms_diff"),
    ("bias_added_to_the_gates", "token_loss_rms_diff"),
    ("mtp_fed_t_i", "mtp_token_loss_rms_diff"),
    ("mtp_target_off_by_one", "mtp_token_loss_rms_diff"),
    ("lambda_0", "loss"),
    ("mtp_gradient_into_h_cut", "grad_rel_err"),
    ("bias_left_unchanged", "bias_update_err"),
    ("state_left_unchanged", "update_rel_err"),
])
def test_a_fault_comes_out_not_correct(tiny_cell, honest, fault, reading):
    """Each through the accepted driver's own comparison. A fault of the
    module leaves the main head's readings where they were, and the
    faults of the bias's rule and of the optimizer every forward
    reading: only the child's own limits see them."""
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env["EDL_BENCH_CONTROL"] = fault
    got = reference_check(tiny_cell, 1, honest)
    limits = tiny_cell.config["reference"]
    assert not got["ok"]
    if reading == "token_loss_rms_diff":
        assert got[reading] > limits["token_loss_rms_tolerance"]
        return
    assert got["token_loss_rms_diff"] <= limits["token_loss_rms_tolerance"]
    refused = [r.split()[0] for r in got["refused"]]
    if reading == "loss":
        # the timed step's sum lacks 0.3 x the module's loss
        assert "timed_loss_diff" in refused
        assert got["timed_loss_diff"] == pytest.approx(
            0.3 * got["drawn_bias_mtp_loss"], rel=1e-3)
    elif reading == "mtp_token_loss_rms_diff":
        assert reading in refused
        assert got[reading] > 100 * limits["mtp_token_loss_rms_tolerance"]
    elif reading == "grad_rel_err":
        # the main blocks' leaves lack the module's part, the routed
        # ones among them
        assert refused == [reading, "routed_grad_rel_err"]
        assert got[reading] > 0.2
    else:
        assert refused == [reading]
        assert got[reading] == pytest.approx(1.0, abs=1e-6)


def test_the_file_is_the_source_cut_as_it_says():
    """Every key of the source's config.json at the top level, equal to
    it but for the three the file lists; no width among them; the keys
    the harness reads equal to the source's; the trainer's flags build
    the file's model."""
    source = {k: v for k, v in CFG["source_config"].items() if k != "what"}
    changed = {k for k, v in source.items() if CFG[k] != v}
    assert changed == set(CFG["reduced_from_source"]) \
        == set(ENTRY["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert ENTRY["source"] in CFG["source"]
    assert CFG["vocab_size"] * 8 == source["vocab_size"]
    assert CFG["n_routed_experts"] * 16 == source["n_routed_experts"] \
        == CFG["router_experts"]
    assert (CFG["n_embd"], CFG["n_head"], CFG["n_layer"], CFG["n_inner"]) \
        == (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_hidden_layers"], CFG["intermediate_size"])
    from benchmark.reference.check_joyai import (expert_layers,
                                                 program_config,
                                                 reference_hp)
    built = program_config(CFG)
    assert (built.q_lora_rank, built.kv_lora_rank, built.qk_nope_head_dim,
            built.qk_rope_head_dim, built.v_head_dim, built.head_dim,
            built.n_experts, built.held_experts, built.experts_offset,
            built.moe_top_k, built.moe_d_ff, built.n_dense_layers,
            built.moe_shared, built.moe_route_scale, built.moe_bias_rate,
            built.norm_eps, built.rope_theta, built.moe_score,
            built.mtp_layers, built.mtp_weight) == (
        CFG["q_lora_rank"], CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
        CFG["qk_rope_head_dim"], CFG["v_head_dim"], CFG["qk_head_dim"],
        CFG["router_experts"], CFG["n_routed_experts"], 0,
        CFG["num_experts_per_tok"], CFG["moe_intermediate_size"],
        CFG["first_k_dense_replace"], CFG["n_shared_experts"],
        CFG["routed_scaling_factor"], CFG["bias_update_rate"],
        CFG["rms_norm_eps"], CFG["rope_theta"], CFG["scoring_func"],
        CFG["num_nextn_predict_layers"], CFG["mtp_loss_weight"])
    assert built.remat and not built.tie_embeddings
    assert built.embed_scale == 1.0 and not built.n_kv_heads
    hp = reference_hp(CFG, built)
    assert (hp["nope"], hp["rope"], hp["kv_rank"], hp["top_k"],
            hp["route_scale"], hp["theta"], hp["mtp_weight"]) == (
        128, 64, 512, 8, 2.5, 32e6, 0.3)
    assert expert_layers(built) == [("block1",), ("block2",), ("block3",),
                                    ("block4",), ("mtp", "block")]
    assert flops_joyai.parameters(CFG) == 680_439_808
    assert "680,439,808" in CFG["parameters"]
    # every limit of `correct` is in the file with its reason
    for key, value in CFG["reference"].items():
        if key.endswith("_tolerance"):
            assert isinstance(value, float) and key + "_why" \
                in CFG["reference"], key


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def test_joyai_mfu_and_the_loss_gap_by_hand():
    cell = NS(config=CFG, tokens_per_step=16384)
    lines = [{"step": 10 * i, "moe_held": h, "moe_dropped": 0.0,
              "loss": 9.69 + 0.3 * (9.69 + gap), "mtp_loss": 9.69 + gap}
             for i, (h, gap) in enumerate([(0.06, 0.01), (0.065, 0.03),
                                           (0.0625, 0.02)])]
    trace = {"devices": {0: {"periods_ns": [1.0e9, 1.0e9, 1.1e9]}}}
    ev = {"peak": PEAK, "device": {"count": 1}, "step_counters": lines,
          "trace": trace}
    # a step of 16,384 tokens a second: 16,384 x 3.398 GFLOP of 197 TFLOP
    assert reader("joyai_mfu").read(cell, ev) == pytest.approx(
        100 * 16384 * flops_joyai.train_flops_per_token(CFG, 8192, 1 / 16)
        / 197e12)
    assert reader("joyai_mfu").read(cell, ev) == pytest.approx(28.26,
                                                               abs=0.01)
    assert reader("mtp_loss_gap").read(cell, ev) == pytest.approx(0.02)


def test_the_roofline_and_the_two_shares_on_a_made_up_table():
    cell = NS(config=CFG, tokens_per_step=16384)
    trace = {"busy_s": 2.0, "devices": {0: {"whole_steps": 4}}}
    ev = {"trace": trace, "peak": PEAK,
          "scopes": {"by_kernel": {"flash_fwd": 0.5, "flash_bwd_dkdv": 0.4,
                                   "flash_bwd_dq": 0.3, "ragged": 0.3},
                     "by_scope": {"attn": 1.1, "mlp": 0.5}, "busy_s": 2.0},
          "mla_scopes": {"by_scope": {"mla_q": 0.2, "mla_kv": 0.1,
                                      "mtp": 0.4}, "busy_s": 2.0}}
    # 1.2 s of flash calls in 4 steps: 300 ms a step against 150.7 ms
    assert reader("mla_flash_roofline").read(cell, ev) == pytest.approx(
        100 * (29_686_813_949_952 / 197e12) / 0.3)
    assert ev["mla_flash_roofline_bound"] == "compute"
    assert reader("mla_proj_time_share").read(cell, ev) == pytest.approx(15)
    assert reader("mtp_time_share").read(cell, ev) == pytest.approx(20)
    ev["scopes"]["by_kernel"] = {}
    assert reader("mla_flash_roofline").read(cell, ev) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent commit, or another model: no such scope, counter or
    key; the reader returns nothing and does not raise."""
    dense = NS(config={"n_embd": 2048, "n_head": 16, "n_layer": 8,
                       "n_inner": 8192, "vocab_size": 50257,
                       "run": {"seq_len": 2048, "global_batch": 6}},
               tokens_per_step=12288)
    assert reader(name).read(dense, {}) is None
    table = {"quiet_windows": [((0.0, 10, 0.0), (2.0, 20, 0.0))],
             "device": {"count": 1}, "peak": PEAK,
             "step_counters": [{"step": 10, "loss": 10.0}],
             "trace": {"busy_s": 1.0, "devices": {0: {
                 "whole_steps": 4, "periods_ns": [1e9]}}},
             "scopes": {"by_kernel": {"flash_fwd": 0.1},
                        "by_scope": {"mlp": 0.5}, "busy_s": 1.0},
             "mla_scopes": {"by_scope": {}, "busy_s": 1.0},
             "moe_scopes": {"by_scope": {}, "busy_s": 1.0}}
    assert reader(name).read(dense, table) is None
    # this cell's file on a program that logs neither counter
    if name in ("joyai_mfu", "mtp_loss_gap"):
        assert reader(name).read(NS(config=CFG, tokens_per_step=16384),
                                 table) is None


def test_the_scope_readers_on_a_recorded_trace_of_another_model():
    """`olmoe_d1_traced.xplane.pb.gz` (PR 26's, recorded on the chip)
    names neither latent nor module: the two shares read nothing from
    it, by the reduction itself; the roofline finds that trace's flash
    calls and reads a number against this cell's counts (the
    arithmetic, not a measurement)."""
    import gzip
    import shutil
    import tempfile

    from benchmark.reduce import mla_scopes, xplane
    packed = os.path.join(ROOT, "benchmark", "tests", "data",
                          "olmoe_d1_traced.xplane.pb.gz")
    with tempfile.TemporaryDirectory() as tmp:
        inner = os.path.join(tmp, "plugins", "profile", "t")
        os.makedirs(inner)
        with gzip.open(packed) as src, open(
                os.path.join(inner, "host.xplane.pb"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        ev = {"trace": xplane.reduce_dir(tmp, 1), "peak": PEAK}
        cell = NS(config=CFG, tokens_per_step=16384)
        assert reader("mla_proj_time_share").read(cell, ev) is None
        assert reader("mtp_time_share").read(cell, ev) is None
        assert mla_scopes.of(ev)["by_scope"] == {}
        roofline = reader("mla_flash_roofline").read(cell, ev)
        assert roofline is not None and roofline > 0.0


def test_the_reduction_counts_an_operation_under_every_scope_on_its_stack(
        monkeypatch):
    from benchmark.reduce import mla_scopes, scopes
    ops = {"%a": "jit(train_step)/jvp(Transformer)/block1/attn/mla_q/q_a/"
                 "dot_general:",
           "%b": "jit(train_step)/jvp(Transformer)/mtp/block/attn/mla_kv/"
                 "kv_b/dot_general:",
           "%c": "jit(train_step)/jvp(Transformer)/mtp/xent/while/body/"
                 "dot_general:",
           "%d": "jit(train_step)/jvp(Transformer)/block1/mlp/moe_experts/"
                 "mul:"}
    monkeypatch.setattr(scopes, "tf_ops", lambda path: {0: ops})
    plane = {"window_ns": (0, 10_000_000_000), "ops": [
        (0, 1_000_000_000, "%a = fusion", "fusion"),
        (1_000_000_000, 3_000_000_000, "%b = fusion", "fusion"),
        (3_000_000_000, 6_000_000_000, "%c = fusion", "fusion"),
        (6_000_000_000, 10_000_000_000, "%d = fusion", "fusion")]}
    ev = {"trace": {"path": "x", "busy_s": 10.0, "devices": {0: plane}}}
    assert mla_scopes.of(ev)["by_scope"] == pytest.approx(
        {"mla_q": 1.0, "mla_kv": 2.0, "mtp": 5.0})
    assert mla_scopes.share(ev, "mla_q", "mla_kv") == pytest.approx(30.0)
    assert mla_scopes.share(ev, "mtp") == pytest.approx(50.0)


def test_the_cell_is_in_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads") for m in
             BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("train_tokens_per_s", "loop_stall_share", "step_ms",
                 "pallas_time_share", "device_idle_share",
                 "loader_wait_share", "host_clock_gap_share",
                 "fused_xent_time_share", "optimizer_time_share",
                 "flash_bwd_time_share", "attn_time_share",
                 "rope_time_share", "remat_replay_time_share",
                 "moe_time_share", "moe_dispatch_time_share",
                 "moe_max_load", "moe_held_share"):
        assert lists[name][-1] == CELL, name
    for name in NEW:
        assert lists[name] == [CELL], name
    # their counts are other models'
    for name in ("mfu", "flash_attention_roofline", "active_mfu",
                 "moe_ffn_roofline", "hybrid_mfu", "hybrid_flash_roofline",
                 "afmoe_mfu", "window_flash_roofline", "sdar_mfu",
                 "blockdiff_flash_roofline", "masked_share"):
        assert CELL not in lists[name], name
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "steady_ref"
    assert BENCH["workloads"][-1] is entry and BENCH["configs"][-1] is ENTRY
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(NEW)
