"""The FLOP and byte functions against numbers worked by hand."""

import json
import os

import pytest

from benchmark.harness import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


# per block 4*2048^2 + 2*2048*8192 = 50,331,648; head 2048*50257 =
# 102,926,336
@pytest.mark.parametrize("name, matmul_params, per_token", [
    # 8*50,331,648 + 102,926,336; 6x that + 6*8*2048*2048
    ("cerebras-gpt-1.3b-d8", 505_579_520, 3_033_477_120 + 201_326_592),
    # 24*50,331,648 + 102,926,336; 6x that + 6*24*2048*2048
    ("cerebras-gpt-1.3b", 1_310_885_888, 7_865_315_328 + 603_979_776),
])
def test_lm_flops_per_token(name, matmul_params, per_token):
    cfg = config(name)
    assert flops.lm_matmul_params(cfg) == matmul_params
    assert flops.lm_train_flops_per_token(
        cfg, cfg["run"]["seq_len"]) == per_token


def test_causal_attention_flops_and_bytes():
    # one head, one sequence of 4 tokens, head size 2: one causal product
    # costs 2 * (4*4/2) * 2 = 32 FLOPs, seven of them 224; twelve passes
    # over 4*2 bf16 values are 192 bytes
    assert flops.causal_attention_train(1, 1, 4, 2) == (224.0, 192.0)
    # the d8 configuration's layer at 6 sequences: 7 * 2*0.5*2048^2*128
    # * 6*16 = 360,777,252,864
    f, b = flops.causal_attention_train(6, 16, 2048, 128)
    assert f == 360_777_252_864 and b == 12 * 6 * 2048 * 16 * 128 * 2


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert flops.roofline_seconds(10.0, 1000.0, peak) == (100.0, "memory")


def test_peaks_table_has_a_source_for_every_row():
    with open(os.path.join(HERE, "..", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in peaks.values())
