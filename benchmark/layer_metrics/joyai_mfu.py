"""Step: model FLOP/s utilization of a JoyAI-LLM-Flash share. The FLOPs
one step's tokens require (benchmark/harness/flops_joyai.py: the
mixers' five matrices, scores at the key size and values at the value
size over the keys a query sees, the dense layer, the held experts at
the share of assignments the trainer logged, `moe_held=`, the
prediction module's block and merge, the head twice; forward x 3,
remat's replay not counted) over the step's period on the device
(`step_ms`) and chips times the chip's bf16 peak: the cell's share of
the whole step's peak."""

from benchmark.harness.flops_joyai import train_flops_per_token
from benchmark.layer_metrics import moe_held_share, step_ms


def read(cell, ev):
    if "kv_lora_rank" not in cell.config:
        return None
    held = moe_held_share.read(cell, ev)  # per cent
    if held is None:
        return None
    period = step_ms.read(cell, ev)
    if not period:
        return None
    flops = cell.tokens_per_step * train_flops_per_token(
        cell.config, cell.config["run"]["seq_len"], held / 100.0)
    peak = ev["device"]["count"] * ev["peak"]["bf16_flops_per_s"]
    return 100.0 * flops / (period / 1000.0) / peak
