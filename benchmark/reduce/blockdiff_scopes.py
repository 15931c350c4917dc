"""Device seconds under the scopes of training by diffusion over blocks.

The program names what the objective adds to attention
(`edl_tpu/models/blockdiff.py`): `attn_clean` and `attn_noised` around
the two masked flash calls, `attn_own_block` around the noised queries'
product with their own block, `attn_merge` around the join of the two
parts by their log-sum-exps, `blockdiff_assemble` around building
[noised ; clean], the split and the join. A Mosaic call made under a
scope carries it in its name stack, forward and backward
(`.../block3/attn/attn_clean/jit(_bwd_pallas)/flash_bwd_dq/pallas_call`;
my compile for a described v5e, PR 44), so the kernels' time is told
apart from the fusions around them. A program that names no such scope
(another model, an older program) gives an empty table, and the readers
report nothing.
"""

from __future__ import annotations

import collections

from benchmark.harness.procs import say
from benchmark.reduce import scopes, xplane

KERNELS = ("attn_clean", "attn_noised")
OVERHEAD = ("attn_own_block", "attn_merge", "blockdiff_assemble")
SCOPES = (*KERNELS, *OVERHEAD)
PALLAS = "_pallas"  # a scope's Mosaic calls: "attn_clean_pallas"


def scope_of(op, tf_op: str | None) -> str | None:
    parts = set((tf_op or "").rstrip(":").split("/"))
    scope = next((s for s in SCOPES if s in parts), None)
    if scope in KERNELS and op[3] == "pallas":
        return scope + PALLAS
    return scope


def of(ev: dict) -> dict | None:
    """{"by_scope": device seconds by scope, averaged over the chips,
    inside the window of whole steps, "busy_s"}; made once a run."""
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if "blockdiff_scopes" not in ev:
        names = scopes.tf_ops(trace["path"])
        n = len(trace["devices"])
        by_scope: collections.Counter = collections.Counter()
        for dev, plane in trace["devices"].items():
            ops = names.get(dev, {})
            for scope, seconds in xplane.seconds_by(
                    {"devices": {dev: plane}}, lambda op: scope_of(
                        op, ops.get(op[2].split(" ", 1)[0]))).items():
                if scope is not None:
                    by_scope[scope] += seconds / n
        busy = trace["busy_s"]
        if by_scope:
            say("device seconds under the block-diffusion scopes (share of "
                "busy time): " + ", ".join(
                    f"{k} {v:.4f} ({100 * v / busy:.1f} %)" for k, v in
                    sorted(by_scope.items(), key=lambda kv: -kv[1])))
        ev["blockdiff_scopes"] = {"by_scope": dict(by_scope), "busy_s": busy}
    return ev["blockdiff_scopes"]


def seconds(ev: dict, *keys: str) -> float | None:
    got = of(ev)
    if got is None:
        return None
    return sum(got["by_scope"].get(k, 0.0) for k in keys) or None
