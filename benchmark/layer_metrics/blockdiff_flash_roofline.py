"""Kernels: the flash kernels' share of their roofline under the two
block masks of training by diffusion over blocks. The least time the
chip could take for one step's masked attention, forward and backward
(benchmark/harness/flops_sdar.kernels_train: seven products over the
pairs the mask lets through, the noised queries' own block left out, it
is not the kernels'; remat's second forward is not required work), over
the device time of the Mosaic calls under the program's `attn_clean` and
`attn_noised` scopes in one step, every run of them. Which bound applies
is in evidence["blockdiff_flash_roofline_bound"]."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_sdar import kernels_train
from benchmark.reduce import blockdiff_scopes as bd


def read(cell, ev):
    if "block_length" not in cell.config:
        return None
    spent = bd.seconds(ev, *(k + bd.PALLAS for k in bd.KERNELS))
    if spent is None:
        return None
    trace = ev["trace"]
    steps = min(d["whole_steps"] for d in trace["devices"].values())
    if not steps:
        return None
    run = cell.config["run"]
    flops, nbytes = kernels_train(
        run["global_batch"] // len(trace["devices"]), cell.config,
        run["seq_len"])
    least, bound = roofline_seconds(flops, nbytes, ev["peak"])
    ev["blockdiff_flash_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
