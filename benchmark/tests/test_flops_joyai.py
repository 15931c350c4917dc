"""`harness/flops_joyai.py` against numbers worked by hand: the
680,439,808 parameters the trainer logs, a layer's and row's 2.474 TFLOP
of causal latent attention, and the 3.40 GFLOP a trained token costs at
balance. (In a file of its own: a PR that adds a cell adds files to the
benchmark and edits none, so `test_flops.py` stays as it was.)"""

import json
import os

import pytest

from benchmark.harness import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_joyai_parameters_are_the_trainers_count():
    from benchmark.harness import flops_joyai
    cfg = config("joyai-llm-flash-d5-e16v8")
    # the mixer: q_a 2048 x 1536, q_b 1536 x 6144, kv_a 2048 x 576,
    # kv_b 512 x 8192, out 4096 x 2048, latent norms 1536 + 512
    mla = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 \
        + 4096 * 2048 + 1536 + 512
    assert mla == 26_347_520
    dense = mla + 3 * 2048 * 7168 + 2 * 2048
    expert_layer = mla + 2048 * 256 + 17 * 3 * 2048 * 768 + 2 * 2048
    module = expert_layer + 4096 * 2048 + 3 * 2048
    table = 16160 * 2048
    assert (dense, expert_layer, module, table) == (
        70_391_808, 107_091_968, 115_486_720, 33_095_680)
    assert flops_joyai.parameters(cfg) == dense + 4 * expert_layer \
        + module + 2 * table + 2048 == 680_439_808
    # a sixteenth of the published experts, an eighth of the vocabulary
    assert cfg["n_routed_experts"] * 16 == cfg["router_experts"] == 256
    assert cfg["vocab_size"] * 8 == 129_280


def test_joyai_attention_a_layer_and_row_is_2_474_tflop():
    from benchmark.harness import flops_joyai
    cfg = config("joyai-llm-flash-d5-e16v8")
    # seven products over the S^2 / 2 causal pairs of 32 heads: four at
    # the key size 192, three at the value size 128, 2 FLOPs a
    # multiply-add
    layer, nbytes = flops_joyai.attention_train_layer(1, cfg, 8192)
    assert layer == 8192 * 8192 // 2 * 32 * 2 * (4 * 192 + 3 * 128) \
        == 2_473_901_162_496
    # q thrice at 192, k thrice at 128 + 64 / 32, six passes at 128
    assert nbytes == 8192 * 32 * 2 * (3 * 192 + 3 * 130 + 6 * 128)
    step, _ = flops_joyai.attention_train(2, cfg, 8192)
    assert step == 6 * 2 * layer  # six layers, the module's among them
    least, bound = flops.roofline_seconds(
        *flops_joyai.attention_train(2, cfg, 8192),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and least == pytest.approx(150.7e-3, rel=1e-3)


def test_joyai_flops_a_token_are_3_40_gflop_at_balance():
    from benchmark.harness import flops_joyai
    cfg = config("joyai-llm-flash-d5-e16v8")
    mla = 26_347_520 - 2048          # without the two latent norms
    expert = 3 * 2048 * 768
    # an expert layer at balance: the router over 256, the shared
    # expert, and of a token's 8 experts the sixteenth that is held
    layer = 2048 * 256 + expert * (1 + 8 / 16)
    params = 6 * mla + 3 * 2048 * 7168 + 5 * layer + 4096 * 2048 \
        + 2 * 16160 * 2048
    assert flops_joyai.matmul_params(cfg, 1 / 16) == params
    # forward a token: a layer's scores and values over 4,096 keys
    scores = 2 * 4096 * 32 * (192 + 128)
    assert scores == 83_886_080
    forward = 2 * params + 6 * scores
    assert round(forward / 1e6) == 1133
    per_token = flops_joyai.train_flops_per_token(cfg, 8192, 1 / 16)
    assert per_token == 3 * forward
    assert round(per_token / 1e9, 2) == 3.40
    # the chip's peak is 57,972 tokens/s of this cut
    assert 197e12 / per_token == pytest.approx(57_972, rel=1e-4)
    # the flash calls are 44 % of it, the mixers 72 %
    assert 6 * scores / forward == pytest.approx(0.444, abs=0.001)
    assert (6 * scores + 2 * 6 * mla) / forward == pytest.approx(0.723,
                                                                 abs=0.001)
    # no held assignment at all takes the five layers' held part away
    assert flops_joyai.train_flops_per_token(cfg, 8192, 0.0) \
        == per_token - 6 * 5 * 8 / 16 * expert
