"""What PR 36 added to the benchmark: the Trinity-Mini share's cell
walked through the driver on the CPU at a tiny size, faults sent through
the driver's `reference_check`, the configuration file against its
source, `flops_afmoe.py` against a hand count, and the four new readers
on made-up evidence and on a recorded trace."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import flops_afmoe
from benchmark.harness.flops import roofline_seconds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def afmoe_configs() -> list[tuple[str, dict]]:
    """(cell, configuration file) of the cells with a sliding window."""
    out = []
    for w in BENCH["workloads"]:
        path = next(c["file"] for c in BENCH["configs"]
                    if c["name"] == w["config"])
        with open(os.path.join(ROOT, path)) as f:
            cfg = json.load(f)
        if "sliding_window" in cfg:
            out.append((w["name"], cfg))
    return out


((CELL, CFG),) = afmoe_configs()
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_trinity.json")
ONE_DEVICE = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def test_cpu_rehearsal_of_the_cell_is_refused():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483655", "--seconds", "12", "--trace", "1", "--rehearse",
         "benchmark/tests/tiny_trinity.json"], cwd=ROOT,
        env={**os.environ, **ONE_DEVICE}, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]
    for reading in ("token_loss_rms_diff", "routing_diff_share",
                    "grad_rel_err", "update_rel_err", "bias_update_err",
                    "timed_loss_diff"):
        assert reading in out.stderr


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, stand_in_cell):
    """What `train_steady_ref.reference_check` reads of a cell, on
    shards of the tiny configuration, with the checker replaced by
    `tools/afmoe_controls.py` (the checker itself unless
    EDL_BENCH_CONTROL names a fault)."""
    from benchmark.harness.shards import make_shards
    work = tmp_path_factory.mktemp("afmoe_controls")
    with open(TINY) as f:
        config = json.load(f)
    config["reference"]["checker"] = "benchmark.tools.afmoe_controls"
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
    got = reference_check(tiny_cell, 1, 0.0)
    return got["reference_loss"]


def test_the_program_as_it_is_comes_out_correct(tiny_cell, honest):
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env.pop("EDL_BENCH_CONTROL", None)
    got = reference_check(tiny_cell, 1, honest)
    assert got["ok"] and got["refused"] == []
    assert got["routing_diff_share"] == 0.0  # float32 on both sides here


# the fault, and the reading that has to refuse it
@pytest.mark.parametrize("fault, reading", [
    ("reference_float8_e4m3", "token_loss_rms_diff"),
    ("no_gate", "token_loss_rms_diff"),
    ("rope_on_the_global_layer", "token_loss_rms_diff"),
    ("window_halved", "token_loss_rms_diff"),
    ("bias_added_to_the_gates", "token_loss_rms_diff"),
    ("no_shared_expert", "token_loss_rms_diff"),
    ("bias_left_unchanged", "bias_update_err"),
    ("state_left_unchanged", "update_rel_err"),
])
def test_a_fault_comes_out_not_correct(tiny_cell, honest, fault, reading):
    """Each through the accepted driver's own comparison. The faults of
    the bias's rule and of the optimizer leave every forward reading
    where it was: only the timed program's readings see them."""
    from benchmark.drivers.train_steady_ref import reference_check
    tiny_cell.env["EDL_BENCH_CONTROL"] = fault
    got = reference_check(tiny_cell, 1, honest)
    limits = tiny_cell.config["reference"]
    assert not got["ok"]
    if reading == "token_loss_rms_diff":
        assert got[reading] > limits["token_loss_rms_tolerance"]
    else:
        assert [r.split()[0] for r in got["refused"]] == [reading]
        assert got["token_loss_rms_diff"] \
            <= limits["token_loss_rms_tolerance"]
        assert got[reading] == pytest.approx(1.0, abs=1e-6)


def test_the_file_is_the_source_cut_as_it_says():
    """Every key of the source's config.json at the top level, equal to
    it but for the five the file lists; no width among them; the keys
    the harness reads equal to the source's; the trainer's flags build
    the file's model."""
    source = {k: v for k, v in CFG["source_config"].items() if k != "what"}
    changed = {k for k, v in source.items() if CFG[k] != v}
    entry = next(c for c in BENCH["configs"]
                 if c["file"].endswith("trinity-mini-p1-e16v8.json"))
    assert changed == set(CFG["reduced_from_source"]) \
        == set(entry["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert CFG["layer_types"] == [source["layer_types"][0]] \
        + source["layer_types"][4:8]
    assert CFG["layer_types"].count("full_attention") == 1
    assert CFG["vocab_size"] * 8 == source["vocab_size"]
    assert CFG["num_experts"] * 8 == source["num_experts"] \
        == CFG["router_experts"]
    assert (CFG["n_embd"], CFG["n_head"], CFG["n_layer"], CFG["n_inner"]) \
        == (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_hidden_layers"], CFG["intermediate_size"])
    from benchmark.reference.check_trinity_mini import (program_config,
                                                        reference_hp)
    built = program_config(CFG)
    assert [k + "_attention" for k in built.layer_types] \
        == CFG["layer_types"]
    assert (built.head_dim, built.kv_heads, built.window, built.n_experts,
            built.held_experts, built.experts_offset, built.moe_top_k,
            built.moe_d_ff, built.n_dense_layers, built.moe_shared,
            built.moe_route_scale, built.moe_bias_rate, built.norm_eps,
            built.rope_theta, built.moe_score) == (
        CFG["head_dim"], CFG["num_key_value_heads"], CFG["sliding_window"],
        CFG["router_experts"], CFG["num_experts"], 0,
        CFG["num_experts_per_tok"], CFG["moe_intermediate_size"],
        CFG["num_dense_layers"], CFG["num_shared_experts"],
        CFG["route_scale"], CFG["load_balance_coeff"], CFG["rms_norm_eps"],
        CFG["rope_theta"], CFG["score_func"])
    assert built.embed_scale == CFG["hidden_size"] ** 0.5
    assert built.remat and not built.tie_embeddings
    hp = reference_hp(CFG, built)
    assert hp["layer_types"] == ["sliding"] * 4 + ["full"]
    assert (hp["window"], hp["top_k"], hp["n_kv_head"]) == (2048, 8, 4)
    # every limit of `correct` is in the file with its reason
    for key, value in CFG["reference"].items():
        if key.endswith("_tolerance"):
            assert isinstance(value, float) and key + "_why" \
                in CFG["reference"], key


def test_flops_a_token_by_hand():
    # attention: q, gate, out 2048 x 4096 each, k, v 2048 x 512 each
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    dense = 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    head = 2048 * 25024
    assert (attn, dense, expert, head) == (27_262_976, 37_748_736,
                                           6_291_456, 51_249_152)
    # an expert layer at balance: router 2048 x 128, the shared expert,
    # and of a token's 8 experts the eighth that is held: one
    layer = 2048 * 128 + expert * (1 + 0.125 * 8)
    params = 5 * attn + dense + 4 * layer + head
    assert flops_afmoe.matmul_params(CFG, 0.125) == params == 276_692_992
    # keys a query sees, summed over a sequence of 8192: all earlier
    # ones, or at most 2048
    assert flops_afmoe.visible_keys(8192) == 8192 * 8193 // 2
    assert flops_afmoe.visible_keys(8192, 2048) \
        == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert flops_afmoe.visible_keys(1024, 2048) == 1024 * 1025 // 2
    products = (4 * 14_681_088 + 33_558_528) * 32 * 128
    assert flops_afmoe.score_products(CFG, 8192) == products
    # forward a token: 4 x 29.4 + 67.1 MFLOP of scores in 738
    forward = 2.0 * params + 2 * 2.0 * products / 8192
    assert round(4 * 14_681_088 * 4096 * 4 / 8192 / 1e6, 1) == 117.4
    assert round(forward / 1e6) == 738
    per_token = flops_afmoe.train_flops_per_token(CFG, 8192, 0.125)
    assert per_token == pytest.approx(3 * forward)
    assert round(per_token / 1e9, 3) == 2.214
    # the chip's peak is 88,985 tokens/s of this cut
    assert 197e12 / per_token == pytest.approx(88_985, rel=1e-4)
    # no held assignment at all takes the four held experts' part away
    assert flops_afmoe.train_flops_per_token(CFG, 8192, 0.0) \
        == pytest.approx(per_token - 6.0 * 4 * expert)


def test_attention_operations_and_bytes_by_hand():
    flops, nbytes = flops_afmoe.attention_train(2, CFG, 8192)
    # seven products over the visible keys of 2 sequences, 32 heads of 128
    assert flops == 7 * 2 * 2 * (4 * 14_681_088 + 33_558_528) * 4096 \
        == 10_583_738_941_440
    # six passes over (2, 8192, 32, 128) and six over (2, 8192, 4, 128),
    # bf16, five layers
    assert nbytes == 5 * 6 * 2 * 8192 * (32 + 4) * 128 * 2 == 4_529_848_320
    least, bound = roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and least == pytest.approx(53.72e-3, rel=1e-3)
    # the causal triangle on every layer would be 1.82x the required work
    full = flops_afmoe.attention_train(
        2, {**CFG, "layer_types": ["full_attention"] * 5}, 8192)[0]
    assert full / flops == pytest.approx(1.82, abs=0.01)


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def counters(held):
    return [{"step": 10 * i, "moe_held": h, "moe_dropped": 0.0}
            for i, h in enumerate(held)]


def test_afmoe_mfu_and_held_share_by_hand():
    cell = NS(config=CFG, tokens_per_step=16384)
    # two log windows of 10 steps in 4 s each: 40,960 tokens/s
    quiet = [((0.0, 10, 0.0), (4.0, 20, 0.0)), ((4.0, 20, 0.0),
                                                (8.0, 30, 0.0))]
    ev = {"quiet_windows": quiet, "peak": PEAK, "device": {"count": 1},
          "step_counters": counters([0.12, 0.13, 0.125])}
    assert reader("moe_held_share").read(cell, ev) == pytest.approx(12.5)
    assert reader("afmoe_mfu").read(cell, ev) == pytest.approx(
        100 * 40960 * flops_afmoe.train_flops_per_token(CFG, 8192, 0.125)
        / 197e12)
    assert reader("afmoe_mfu").read(cell, ev) == pytest.approx(46.03,
                                                               abs=0.01)


def test_window_flash_roofline_and_attn_share_on_a_made_up_table():
    cell = NS(config=CFG, tokens_per_step=16384)
    trace = {"busy_s": 2.0, "devices": {0: {"whole_steps": 4}}}
    ev = {"trace": trace, "peak": PEAK,
          "scopes": {"by_kernel": {"flash_fwd": 0.20, "flash_bwd_dkdv": 0.16,
                                   "flash_bwd_dq": 0.14, "ragged": 0.3},
                     "by_scope": {"attn": 1.1, "mlp": 0.5}, "busy_s": 2.0}}
    # 0.5 s of flash calls in 4 steps: 125 ms a step against 53.72 ms
    assert reader("window_flash_roofline").read(cell, ev) == pytest.approx(
        100 * (10_583_738_941_440 / 197e12) / 0.125)
    assert ev["window_flash_roofline_bound"] == "compute"
    assert reader("attn_time_share").read(cell, ev) == pytest.approx(55.0)
    ev["scopes"]["by_kernel"] = {}
    assert reader("window_flash_roofline").read(cell, ev) is None


@pytest.mark.parametrize("name", ["afmoe_mfu", "window_flash_roofline",
                                  "attn_time_share", "moe_held_share"])
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent commit, or another model: no such counter, scope or
    key; the reader returns nothing and does not raise."""
    dense = NS(config={"n_embd": 2048, "n_head": 16, "n_layer": 8,
                       "n_inner": 8192, "vocab_size": 50257,
                       "run": {"seq_len": 2048, "global_batch": 6}},
               tokens_per_step=12288)
    assert reader(name).read(dense, {}) is None
    table = {"quiet_windows": [((0.0, 10, 0.0), (2.0, 20, 0.0))],
             "device": {"count": 1}, "peak": PEAK,
             "step_counters": [{"step": 10, "loss": 10.0}],
             "trace": {"busy_s": 1.0, "devices": {0: {"whole_steps": 4}}},
             "scopes": {"by_kernel": {"flash_fwd": 0.1},
                        "by_scope": {"mlp": 0.5}, "busy_s": 1.0}}
    assert reader(name).read(dense, table) is None
    # the afmoe cell's file on a program that logs no `moe_held`
    if name in ("afmoe_mfu", "moe_held_share"):
        assert reader(name).read(NS(config=CFG, tokens_per_step=16384),
                                 table) is None


def test_the_new_readers_on_a_recorded_trace():
    """`olmoe_d1_traced.xplane.pb.gz` (PR 26's, recorded on the chip):
    `attn_time_share` reads the `attn` scope the program has named since
    PR 24; the window's roofline finds that trace's flash calls and
    reads a number against this cell's counts (the arithmetic, not a
    measurement: the trace is another model's)."""
    import gzip
    import shutil
    import tempfile

    from benchmark.reduce import xplane
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
        share = reader("attn_time_share").read(cell, ev)
        assert share is not None and 0.0 < share < 100.0
        assert share == pytest.approx(
            100 * ev["scopes"]["by_scope"]["attn"] / ev["trace"]["busy_s"])
        roofline = reader("window_flash_roofline").read(cell, ev)
        assert roofline is not None and roofline > 0.0


def test_the_cell_is_in_the_lists_the_issue_names():
    lists = {m["name"]: m.get("workloads") for m in
             BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("train_tokens_per_s", "loop_stall_share", "step_ms",
                 "pallas_time_share", "device_idle_share",
                 "loader_wait_share", "fused_xent_time_share",
                 "optimizer_time_share", "flash_bwd_time_share",
                 "moe_time_share", "moe_dispatch_time_share",
                 "moe_max_load", "afmoe_mfu", "window_flash_roofline",
                 "attn_time_share", "moe_held_share"):
        assert CELL in lists[name], name
    # their counts are other models'
    for name in ("mfu", "flash_attention_roofline", "active_mfu",
                 "moe_ffn_roofline", "hybrid_mfu", "hybrid_flash_roofline"):
        assert CELL not in lists[name], name
    for name in ("afmoe_mfu", "window_flash_roofline"):
        assert lists[name] == [CELL]
    # later share cells joined these two lists
    for name in ("attn_time_share", "moe_held_share"):
        assert lists[name][0] == CELL
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "steady_ref"
