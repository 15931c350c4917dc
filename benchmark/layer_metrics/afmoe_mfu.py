"""Step: model FLOP/s utilization of an afmoe share. This run's tokens a
second (of the log windows the profiler did not touch, as `mfu`) times
the FLOPs a trained token requires (benchmark/harness/flops_afmoe.py:
matrix products, the windowed and the global scores at the keys a query
sees, the held experts at the share of assignments the trainer logged,
`moe_held=`; remat's replay not counted) over chips times the chip's
bf16 peak. A constant times the end-to-end metric but for the logged
share, kept for reading across configurations."""

from benchmark.harness.cell import rate_over
from benchmark.harness.flops_afmoe import train_flops_per_token
from benchmark.layer_metrics import moe_held_share


def read(cell, ev):
    held = moe_held_share.read(cell, ev)  # per cent
    if not ev.get("quiet_windows") or held is None \
            or "sliding_window" not in cell.config:
        return None
    rate = rate_over(ev["quiet_windows"], cell.tokens_per_step)
    per_token = train_flops_per_token(
        cell.config, cell.config["run"]["seq_len"], held / 100.0)
    peak = ev["device"]["count"] * ev["peak"]["bf16_flops_per_s"]
    return 100.0 * rate * per_token / peak
