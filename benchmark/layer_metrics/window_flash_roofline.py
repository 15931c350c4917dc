"""Kernels: the flash-attention kernels' share of their roofline in a
model whose layers mix a sliding window with global attention and whose
key/value heads are fewer than its query heads. The least time the chip
could take for one step's attention, forward and backward
(benchmark/harness/flops_afmoe.attention_train: the products over the
keys a query sees, `min(i + 1, window)` on a sliding layer, the
key/value heads read once a group; remat's second forward is not
required work), over the device time of the Mosaic calls `flash_fwd`,
`flash_bwd_dkdv` and `flash_bwd_dq` in one step, every run of them,
remat's replay of the forward included. Which bound applies is in
evidence["window_flash_roofline_bound"]."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_afmoe import attention_train
from benchmark.reduce import scopes

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


def read(cell, ev):
    if "sliding_window" not in cell.config:
        return None
    got = scopes.of(ev)
    if got is None:
        return None
    spent = sum(got["by_kernel"].get(k, 0.0) for k in KERNELS)
    trace = ev["trace"]
    steps = min(d["whole_steps"] for d in trace["devices"].values())
    if not spent or not steps:
        return None
    run = cell.config["run"]
    flops, nbytes = attention_train(
        run["global_batch"] // len(trace["devices"]), cell.config,
        run["seq_len"])
    least, bound = roofline_seconds(flops, nbytes, ev["peak"])
    ev["window_flash_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
