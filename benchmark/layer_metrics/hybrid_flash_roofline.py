"""Kernels: the flash-attention kernels' share of their roofline in a
hybrid model, whose attention layers are some of its layers and whose
key/value heads are fewer than its query heads. The least time the chip
could take for one step's grouped-query causal attention, forward and
backward (benchmark/harness/flops_hybrid.attention_train: the attention
layers of `layer_types`, the key/value heads read once a group; remat's
second forward is not required work), over the device time of the
Mosaic calls `flash_fwd`, `flash_bwd_dkdv` and `flash_bwd_dq` in one
step, every run of them, remat's replay of the forward included. Which
bound applies is in evidence["hybrid_flash_roofline_bound"]."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_hybrid import attention_train
from benchmark.reduce import scopes

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


def read(cell, ev):
    if "num_key_value_heads" not in cell.config \
            or "layer_types" not in cell.config:
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
    ev["hybrid_flash_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
