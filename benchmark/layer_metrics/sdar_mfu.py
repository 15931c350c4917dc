"""Step: model FLOP/s utilization of a share trained by diffusion over
blocks. The FLOPs one step's tokens require
(benchmark/harness/flops_sdar.py: matrix products on both copies'
positions, the head on the noised copy's alone, the scores at the pairs
the mask lets through, the held experts at the share of assignments the
trainer logged, `moe_held=`; forward x 3, remat's replay not counted)
over the step's period on the device (`step_ms`) and chips times the
chip's bf16 peak."""

from benchmark.harness.flops_sdar import train_flops_per_token
from benchmark.layer_metrics import moe_held_share, step_ms


def read(cell, ev):
    if "block_length" not in cell.config:
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
