"""Kernels: the grouped expert matmuls' share of their roofline. The
least time the chip could take for one step's three grouped matmuls a
layer, forward and backward (nine products; operations and bytes from
shapes, benchmark/harness/flops_moe.py, and the chip's peaks), over the
device time of the grouped-matmul kernels in one step (XLA's own Mosaic
kernel for `ragged_dot` today; `reduce/moe_scopes.py` says how they are
recognised). The activation between the products and the casts of the
tables are outside it (`moe_time_share` holds them). At these shapes
the bound is compute (evidence["moe_roofline_bound"])."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_moe import grouped_matmuls_train
from benchmark.reduce import moe_scopes


def read(cell, ev):
    spent = moe_scopes.seconds(ev, moe_scopes.GROUPED)
    if spent is None or "num_experts" not in cell.config:
        return None
    trace = ev["trace"]
    steps = min(d["whole_steps"] for d in trace["devices"].values())
    if not steps:
        return None
    tokens = cell.tokens_per_step // len(trace["devices"])
    flops, nbytes = grouped_matmuls_train(tokens, cell.config)
    layers = cell.config["n_layer"]
    least, bound = roofline_seconds(flops * layers, nbytes * layers,
                                    ev["peak"])
    ev["moe_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
