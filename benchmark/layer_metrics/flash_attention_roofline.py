"""Kernels: the flash-attention kernels' share of their roofline. The
least time the chip could take for one step's causal attention, forward
and backward, from shapes (benchmark/harness/flops.py) and the chip's
peaks, over the device time of the Mosaic calls the attention module
issued in one step. Today the program names no kernel: the compiler
calls these `%attn.<n>` after the flax module that holds them, and
`%shard_map.<n>` under a mesh, which cannot tell forward from backward
or flash from any other kernel. So the reader takes every Mosaic call
of the step and reports only where there are exactly three a layer
(forward, dK/dV, dQ); a step with other kernels in it waits for names
(PERF.md §7). At these shapes the bound is compute
(evidence["roofline_bound"])."""

from benchmark.harness.flops import causal_attention_train, roofline_seconds
from benchmark.reduce import xplane


def read(cell, ev):
    trace = ev.get("trace")
    if not trace:
        return None
    steps = min(d["whole_steps"] for d in trace["devices"].values())
    cfg, run = cell.config, cell.config["run"]
    spent = xplane.seconds_by(trace, lambda op: op[3] == "pallas")
    calls = min(sum(op[3] == "pallas" for op in d["ops"])
                for d in trace["devices"].values())
    if not steps or calls // steps != 3 * cfg["n_layer"]:
        return None
    local_batch = run["global_batch"] // len(trace["devices"])
    flops, nbytes = causal_attention_train(
        local_batch, cfg["n_head"], run["seq_len"],
        cfg["n_embd"] // cfg["n_head"])
    least, bound = roofline_seconds(flops * cfg["n_layer"],
                                    nbytes * cfg["n_layer"], ev["peak"])
    ev["roofline_bound"] = bound
    return 100.0 * least / (spent[True] / steps)
