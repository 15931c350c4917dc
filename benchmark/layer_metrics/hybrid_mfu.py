"""Step: model FLOP/s utilization of a hybrid state-space model. This
run's tokens a second (of the log windows the profiler did not touch, as
`mfu`) times the FLOPs a trained token requires
(benchmark/harness/flops_hybrid.py: matrix products, the chunked scan,
causal attention in its attention layers; remat's replay not counted)
over chips times the chip's bf16 peak. A constant times the end-to-end
metric, kept for reading across configurations."""

from benchmark.harness.cell import rate_over
from benchmark.harness.flops_hybrid import train_flops_per_token


def read(cell, ev):
    if not ev.get("quiet_windows") or "mamba_n_heads" not in cell.config:
        return None
    rate = rate_over(ev["quiet_windows"], cell.tokens_per_step)
    per_token = train_flops_per_token(cell.config,
                                      cell.config["run"]["seq_len"])
    peak = ev["device"]["count"] * ev["peak"]["bf16_flops_per_s"]
    return 100.0 * rate * per_token / peak
