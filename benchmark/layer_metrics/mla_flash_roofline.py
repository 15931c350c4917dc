"""Kernels: the flash-attention kernels' share of their roofline where
keys and values have head sizes of their own (latent attention: keys of
192, values of 128). The least time the chip could take for one step's
causal attention in every layer, the prediction module's too, forward
and backward (benchmark/harness/flops_joyai.attention_train: seven
products over the causal pairs, four at the key size and three at the
value size, counted at the published sizes however the kernel contracts
them; remat's replay and the backward's second pass over QK^T beyond the
one the recipe needs are not required work), over the device time of
the Mosaic calls `flash_fwd`, `flash_bwd_dkdv` and `flash_bwd_dq` in one
step, every run of them. Which bound applies is in
evidence["mla_flash_roofline_bound"]."""

from benchmark.harness.flops import roofline_seconds
from benchmark.harness.flops_joyai import attention_train
from benchmark.reduce import scopes

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


def read(cell, ev):
    if "kv_lora_rank" not in cell.config:
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
    ev["mla_flash_roofline_bound"] = bound
    return 100.0 * least / (spent / steps)
