"""Step: model FLOP/s utilization. This run's tokens a second times the
FLOPs a trained token requires (benchmark/harness/flops.py, no recompute
counted) over chips times the chip's bf16 peak. The rate is that of the
log windows the profiler did not touch (per-layer metrics come from
traced runs); in an untraced run that is `train_tokens_per_s` itself, so
this is a constant times the end-to-end metric, kept for reading across
configurations."""

from benchmark.harness.cell import rate_over
from benchmark.harness.flops import lm_train_flops_per_token


def read(cell, ev):
    if not ev.get("quiet_windows"):
        return None
    rate = rate_over(ev["quiet_windows"], cell.tokens_per_step)
    per_token = lm_train_flops_per_token(cell.config,
                                         cell.config["run"]["seq_len"])
    peak = ev["device"]["count"] * ev["peak"]["bf16_flops_per_s"]
    return 100.0 * rate * per_token / peak
