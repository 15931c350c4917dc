"""Kernels: the state-space scan's share of its roofline. The least time
the chip could take for one step's scans in their chunked form at the
configuration's chunk, forward and backward, every mamba layer
(operations and bytes from shapes, benchmark/harness/flops_hybrid.py,
and the chip's peaks; nothing recomputed is counted), over the device
time under the `ssm_scan` scope in one step. By scope, not by kernel
name: whatever implements the scan later is read as the same work. Which
bound applies is in evidence["ssm_roofline_bound"]."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_hybrid import scan_train
from benchmark.reduce import ssm_scopes


def read(cell, ev):
    spent = ssm_scopes.seconds(ev, ssm_scopes.SCAN)
    if spent is None or "mamba_n_heads" not in cell.config:
        return None
    trace = ev["trace"]
    steps = min(d["whole_steps"] for d in trace["devices"].values())
    if not steps:
        return None
    tokens = cell.tokens_per_step // len(trace["devices"])
    flops, nbytes = scan_train(tokens, cell.config)
    layers = cell.config["layer_types"].count("mamba")
    least, bound = roofline_seconds(flops * layers, nbytes * layers,
                                    ev["peak"])
    ev["ssm_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
