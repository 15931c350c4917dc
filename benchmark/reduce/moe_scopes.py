"""Device seconds under the mixture-of-experts layer's own scopes.

`reduce/scopes.py` sorts every operation under the first of its fixed
scopes (`mlp` for the whole expert layer). The layer names its parts
inside that: `moe_router`, `moe_dispatch` (sort, gather), `moe_experts`
(the grouped matmuls and the activation between them), `moe_combine`
(un-permute, weight), and `rope` inside attention. This file sorts the
same operations by those, innermost first, with `scopes.tf_ops`.

XLA lowers `jax.lax.ragged_dot` on a TPU to a Mosaic kernel of its own,
`%ragged-dot-none.<n>`, whose metadata has lost the name stack (my
compile for a described v5e, PR 26): those calls are recognised by
their instruction's name and counted as `grouped_matmul`. A program
that names no such scope and issues no such call (a dense model, an
older program) gives an empty table, and the readers report nothing.
"""

from __future__ import annotations

import collections

from benchmark.harness.procs import say
from benchmark.reduce import scopes, xplane

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine", "rope")
GROUPED = "grouped_matmul"
# the parts of the layer that are not matmul
DISPATCH = ("moe_router", "moe_dispatch", "moe_combine")
LAYER = (*DISPATCH, "moe_experts", GROUPED)


def scope_of(instruction: str, tf_op: str | None) -> str | None:
    if instruction.lstrip("%").startswith("ragged-dot"):
        return GROUPED
    parts = set((tf_op or "").rstrip(":").split("/"))
    return next((s for s in SCOPES if s in parts), None)


def of(ev: dict) -> dict | None:
    """{"by_scope": device seconds by scope, averaged over the chips,
    inside the window of whole steps, "busy_s"}; made once a run."""
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if "moe_scopes" not in ev:
        names = scopes.tf_ops(trace["path"])
        n = len(trace["devices"])
        by_scope: collections.Counter = collections.Counter()
        for dev, plane in trace["devices"].items():
            ops = names.get(dev, {})

            def key(op):
                instruction = op[2].split(" ", 1)[0]
                return scope_of(instruction, ops.get(instruction))
            for scope, seconds in xplane.seconds_by(
                    {"devices": {dev: plane}}, key).items():
                if scope is not None:
                    by_scope[scope] += seconds / n
        busy = trace["busy_s"]
        if by_scope:
            say("device seconds under the expert layer's scopes (share of "
                "busy time): " + ", ".join(
                    f"{k} {v:.4f} ({100 * v / busy:.1f} %)" for k, v in
                    sorted(by_scope.items(), key=lambda kv: -kv[1])))
        ev["moe_scopes"] = {"by_scope": dict(by_scope), "busy_s": busy}
    return ev["moe_scopes"]


def seconds(ev: dict, *keys: str) -> float | None:
    got = of(ev)
    if got is None:
        return None
    return sum(got["by_scope"].get(k, 0.0) for k in keys) or None


def share(ev: dict, *keys: str) -> float | None:
    """Per cent of busy device time under ``keys``; nothing where the
    program wrote none of them."""
    spent = seconds(ev, *keys)
    return None if spent is None else 100.0 * spent / of(ev)["busy_s"]
