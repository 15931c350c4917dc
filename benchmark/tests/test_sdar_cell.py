"""What PR 44 added to the benchmark: the SDAR share's cell walked
through the driver on the CPU at a tiny size, faults sent through the
driver's `reference_check`, the configuration file against its source,
`flops_sdar.py` against a count by enumeration of the mask and by hand,
and the four new readers on a made-up outline of scopes."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark.harness import flops_sdar
from benchmark.harness.flops import roofline_seconds
from benchmark.reduce import blockdiff_scopes, scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "sdar_p1e16v8.steady"
ENTRY = next(c for c in BENCH["configs"]
             if c["name"] == "sdar-30b-a3b-chat-p1-e16v8")
with open(os.path.join(ROOT, ENTRY["file"])) as f:
    CFG = json.load(f)
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_sdar.json")
ONE_DEVICE = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def test_cpu_rehearsal_of_the_cell_is_refused():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483655", "--seconds", "12", "--trace", "1", "--rehearse",
         "benchmark/tests/tiny_sdar.json"], cwd=ROOT,
        env={**os.environ, **ONE_DEVICE}, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]
    for reading in ("token_loss_rms_diff", "routing_diff_share",
                    "grad_rel_err", "update_rel_err", "timed_loss_diff",
                    "masked_share"):
        assert reading in out.stderr


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, stand_in_cell):
    """What `train_steady_ref.reference_check` reads of a cell, on
    shards of the tiny configuration, with the checker replaced by
    `tools/sdar_controls.py` (the checker itself unless
    EDL_BENCH_CONTROL names a fault)."""
    from benchmark.harness.shards import make_shards
    work = tmp_path_factory.mktemp("sdar_controls")
    with open(TINY) as f:
        config = json.load(f)
    config["reference"]["checker"] = "benchmark.tools.sdar_controls"
    path = work / "config.json"
    path.write_text(json.dumps(config))
    data = work / "data"
    make_shards(str(data), 1, 8, config["run"]["seq_len"],
                config["vocab_size"], 2290033100)
    env = {**os.environ, **ONE_DEVICE, "PYTHONPATH": ROOT}
    return stand_in_cell(work, config, path, data, env)


@pytest.fixture(scope="module")
def honest(tiny_cell):
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env.pop("EDL_BENCH_CONTROL", None)
    # the trainer's logged loss stands in as the reference's own
    return reference_check(tiny_cell, 1, 0.0)["reference_loss"]


def test_the_program_as_it_is_comes_out_correct(tiny_cell, honest):
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env.pop("EDL_BENCH_CONTROL", None)
    got = reference_check(tiny_cell, 1, honest)
    assert got["ok"] and got["refused"] == []
    assert got["routing_diff_share"] == 0.0  # float32 on both sides here
    assert 0.3 < got["masked_share"] < 0.7


# the fault, and the reading that has to refuse it
@pytest.mark.parametrize("fault, reading", [
    ("reference_float8_e4m3", "token_loss_rms_diff"),
    ("causal_over_2L", "token_loss_rms_diff"),
    ("noised_sees_its_own_clean_block", "token_loss_rms_diff"),
    ("clean_sees_a_noised_key", "token_loss_rms_diff"),
    ("at_or_before_for_strictly_before", "token_loss_rms_diff"),
    ("rope_by_place_in_2L", "token_loss_rms_diff"),
    ("gates_not_renormalised", "token_loss_rms_diff"),
    ("weight_left_out", "timed_loss_diff"),
    ("state_left_unchanged", "update_rel_err"),
])
def test_a_fault_comes_out_not_correct(tiny_cell, honest, fault, reading):
    """Each through the accepted driver's own comparison. The weight
    and the optimizer leave every forward reading where it was (a
    token's cross-entropy does not know its weight): only the timed
    program's readings see them."""
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env["EDL_BENCH_CONTROL"] = fault
    got = reference_check(tiny_cell, 1, honest)
    limits = tiny_cell.config["reference"]
    assert not got["ok"]
    if reading == "token_loss_rms_diff":
        assert got[reading] > limits["token_loss_rms_tolerance"]
    else:
        assert reading in [r.split()[0] for r in got["refused"]]
        assert got["token_loss_rms_diff"] \
            <= limits["token_loss_rms_tolerance"]


def test_the_file_is_the_source_cut_as_it_says():
    """Every number of the source's config.json at the top level, equal
    to it but for the three the file lists; no width among them; the
    keys the harness reads equal to the source's; the trainer's flags
    build the file's model."""
    source = {k: v for k, v in CFG["source_config"].items() if k != "what"}
    assert source.pop("sliding_window") is None  # no number; see harness_keys
    changed = {k for k, v in source.items() if CFG[k] != v}
    assert changed == set(CFG["reduced_from_source"]) \
        == set(ENTRY["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size"}
    assert CFG["vocab_size"] * 8 == source["vocab_size"]
    assert CFG["num_experts"] * 8 == source["num_experts"] \
        == CFG["router_experts"]
    assert (CFG["n_embd"], CFG["n_head"], CFG["n_layer"], CFG["n_inner"]) \
        == (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_hidden_layers"], CFG["moe_intermediate_size"])
    assert ENTRY["source"] in CFG["source"]
    from benchmark.reference.check_sdar import program_config, reference_hp
    built = program_config(CFG)
    assert (built.head_dim, built.kv_heads, built.n_experts,
            built.held_experts, built.experts_offset, built.moe_top_k,
            built.d_ff, built.norm_eps, built.rope_theta, built.moe_renorm,
            built.moe_score, built.block_length, built.mask_id) == (
        CFG["head_dim"], CFG["num_key_value_heads"], CFG["router_experts"],
        CFG["num_experts"], 0, CFG["num_experts_per_tok"],
        CFG["moe_intermediate_size"], CFG["rms_norm_eps"],
        CFG["rope_theta"], CFG["norm_topk_prob"], "softmax",
        CFG["block_length"], CFG["vocab_size"] - 1)
    assert built.remat and not built.tie_embeddings
    assert (built.moe_aux_weight, built.moe_z_weight, built.moe_shared) \
        == (0.0, 0.0, 0)
    hp = reference_hp(CFG, built)
    assert (hp["block_length"], hp["top_k"], hp["n_kv_head"], hp["eps"],
            hp["theta"], hp["mask_id"]) == (4, 8, 4, 1e-6, 1e6, 18991)
    # what the config.json does not hold is listed with its source
    for key in ("block_length", "schedule", "no_shift", "mask",
                "router_loss", "attention", "experts", "mask_token"):
        assert key in CFG["assumed"], key
    # every limit of `correct` is in the file with its reason
    for key, value in CFG["reference"].items():
        if key.endswith("_tolerance"):
            assert isinstance(value, float) and key + "_why" \
                in CFG["reference"], key


@pytest.mark.parametrize("length, block", [(8, 1), (16, 4), (24, 8), (12, 12),
                                           (64, 4)])
def test_visible_pairs_by_enumeration_of_the_mask(length, block):
    """The count from shapes against the reference's mask, built outright
    on the 2L x 2L grid, place by place."""
    from benchmark.reference import sdar_plain as plain
    seen = np.asarray(plain.visible(length, block))
    assert flops_sdar.visible_pairs(length, block) == int(seen.sum())
    # the noised queries' own block: the top-left quarter
    assert flops_sdar.own_block_pairs(length, block) \
        == int(seen[:length, :length].sum())
    # no noised key for a clean query; the clean copy's triangle of blocks
    assert not seen[length:, :length].any()


def test_flops_a_token_by_hand():
    # attention: q, out 2048 x 4096 each, k, v 2048 x 512 each
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    expert, head = 3 * 2048 * 768, 2048 * 18992
    assert (attn, expert, head) == (18_874_368, 4_718_592, 38_895_616)
    # a layer at balance: the router over 128, and of a position's 8
    # experts the eighth that is held: one
    layer = attn + 2048 * 128 + 0.125 * 8 * expert
    blocks, top = flops_sdar.matmul_params(CFG, 0.125)
    assert blocks == 6 * layer == 143_130_624 and top == head
    # pairs of one row of 8,192 in blocks of 4: 16 x 2048 x 2049
    assert flops_sdar.visible_pairs(8192, 4) == 67_141_632
    products = 6 * 67_141_632 * 32 * 128
    assert flops_sdar.score_products(CFG, 8192) == products
    # a layer's forward products: 1,100 GFLOP (ISSUE 44's count)
    assert round(2 * 2 * products / 6 / 1e9) == 1100
    per_token = flops_sdar.train_flops_per_token(CFG, 8192, 0.125)
    # both copies through the blocks, the noised one through the head
    assert per_token == pytest.approx(
        6.0 * (2 * blocks + head) + 3 * 2 * 2.0 * products / 8192)
    assert round(per_token / 1e9, 3) == 4.368
    # the chip's peak is 45,100 tokens/s of this cut
    assert 197e12 / per_token == pytest.approx(45_100, rel=1e-4)
    # no held assignment at all takes the held experts' part away
    assert flops_sdar.train_flops_per_token(CFG, 8192, 0.0) \
        == pytest.approx(per_token - 6.0 * 2 * 6 * expert)


def test_the_kernels_operations_and_bytes_by_hand():
    flops, nbytes = flops_sdar.kernels_train(1, CFG, 8192)
    # seven products over the pairs but the own blocks', 32 heads of 128
    assert flops == 7 * 2 * 6 * (67_141_632 - 8192 * 4) * 4096 \
        == 23_089_744_183_296
    # two calls a layer: six passes over (1, 8192, 32, 128) and six over
    # (1, 8192, 4, 128) each, bf16, six layers
    assert nbytes == 6 * 2 * 6 * 8192 * (32 + 4) * 128 * 2 == 5_435_817_984
    least, bound = roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and least == pytest.approx(117.2e-3, rel=1e-3)
    # causal attention over both copies would be 1.97x the required work
    causal = 16384 * 16385 // 2
    assert causal / 67_141_632 == pytest.approx(2.0, abs=0.01)


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


STACK = "jit(train_step)/transpose(jvp(Transformer))/jvp(Transformer)/" \
        "checkpoint/block3/attn/"
# (start ns, end ns, the event's name, its kind) as `xplane` keeps them
OPS = [(0, 100, "%flash_fwd.1 tpu_custom_call bf16[8]", "pallas"),
       (100, 300, "%flash_bwd_dq.2 tpu_custom_call bf16[8]", "pallas"),
       (300, 350, "%fusion.3 fusion bf16[8]", "other"),
       (350, 400, "%fusion.4 fusion f32[8]", "other"),
       (400, 450, "%fusion.5 fusion f32[8]", "other"),
       (450, 500, "%fusion.6 fusion bf16[8]", "other"),
       (500, 600, "%fusion.7 fusion bf16[8]", "other"),
       (600, 1000, "%ragged-dot-none.8 tpu_custom_call bf16[8]", "pallas")]
NAMES = {"%flash_fwd.1": "jit(train_step)/jvp(Transformer)/block3/attn/"
                         "attn_clean/jit(_fwd)/flash_fwd/pallas_call:",
         "%flash_bwd_dq.2": STACK + "attn_noised/jit(_bwd_pallas)/"
                                    "flash_bwd_dq/pallas_call:",
         "%fusion.3": STACK + "attn_noised/jit(_bwd_pallas)/reduce_sum:",
         "%fusion.4": STACK + "attn_merge/mul:",
         "%fusion.5": STACK + "attn_own_block/dot_general:",
         "%fusion.6": "jit(train_step)/jvp(Transformer)/blockdiff_assemble/"
                      "concatenate:",
         "%fusion.7": STACK + "out/dot_general:"}


def made_up(monkeypatch, names=NAMES):
    monkeypatch.setattr(scopes, "tf_ops", lambda path: {0: names})
    return {"trace": {"path": "", "busy_s": 1000 / 1e9, "devices": {
        0: {"window_ns": (0, 1000), "ops": OPS, "whole_steps": 2}}},
        "peak": PEAK, "device": {"count": 1},
        "step_counters": [{"step": 10 * i, "moe_held": h, "masked": m}
                          for i, (h, m) in enumerate(
                              [(0.12, 0.49), (0.13, 0.52), (0.125, 0.5)])]}


def test_the_scopes_of_a_made_up_outline(monkeypatch):
    got = blockdiff_scopes.of(made_up(monkeypatch))["by_scope"]
    assert {k: round(v * 1e9) for k, v in got.items()} == {
        "attn_clean_pallas": 100, "attn_noised_pallas": 200,
        "attn_noised": 50, "attn_merge": 50, "attn_own_block": 50,
        "blockdiff_assemble": 50}


def test_the_new_readers_on_a_made_up_outline(monkeypatch):
    cell = NS(config=CFG, tokens_per_step=8192)
    ev = made_up(monkeypatch)
    # 150 ns of merge, own block and assembly in 1000 ns busy
    assert reader("blockdiff_overhead_time_share").read(cell, ev) \
        == pytest.approx(15.0)
    # 300 ns of Mosaic calls under the two scopes in 2 steps
    assert reader("blockdiff_flash_roofline").read(cell, ev) \
        == pytest.approx(100 * (23_089_744_183_296 / 197e12) / 150e-9)
    assert ev["blockdiff_flash_roofline_bound"] == "compute"
    assert reader("masked_share").read(cell, ev) == pytest.approx(50.0)


def test_sdar_mfu_by_hand(monkeypatch):
    cell = NS(config=CFG, tokens_per_step=8192)
    ev = made_up(monkeypatch)
    from benchmark.layer_metrics import step_ms
    monkeypatch.setattr(step_ms, "read", lambda cell, ev: 500.0)
    # a step of 500 ms: 16,384 tokens/s of 45,100 at the peak
    assert reader("sdar_mfu").read(cell, ev) == pytest.approx(
        100 * 16384 / 45_100.32, rel=1e-5)


@pytest.mark.parametrize("name", ["sdar_mfu", "blockdiff_flash_roofline",
                                  "blockdiff_overhead_time_share",
                                  "masked_share"])
def test_a_program_without_the_scopes_reads_nothing(name, monkeypatch):
    """The parent commit, or another model: no such counter, scope or
    key; the reader returns nothing and does not raise."""
    dense = NS(config={"n_embd": 2048, "n_head": 16, "n_layer": 8,
                       "n_inner": 8192, "vocab_size": 50257,
                       "run": {"seq_len": 2048, "global_batch": 6}},
               tokens_per_step=12288)
    assert reader(name).read(dense, {}) is None
    plainly = {k: v for k, v in NAMES.items() if "flash" in k}
    plainly = {k: v.replace("attn_clean/", "").replace("attn_noised/", "")
               for k, v in plainly.items()}
    ev = made_up(monkeypatch, plainly)
    ev["step_counters"] = [{"step": 10, "loss": 10.0}]
    assert reader(name).read(dense, ev) is None
    # this cell's file on a program that names no such scope
    assert reader(name).read(NS(config=CFG, tokens_per_step=8192), ev) is None


def test_the_cell_is_in_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads") for m in
             BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("sdar_mfu", "blockdiff_flash_roofline",
                 "blockdiff_overhead_time_share", "masked_share"):
        assert lists[name] == [CELL]
    assert CELL in lists["train_tokens_per_s"]
    # their counts are other models'
    for name in ("mfu", "flash_attention_roofline", "active_mfu",
                 "moe_ffn_roofline", "hybrid_mfu", "hybrid_flash_roofline",
                 "afmoe_mfu", "window_flash_roofline"):
        assert CELL not in lists[name], name
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "steady_ref"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
