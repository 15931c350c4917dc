"""Device seconds under the state-space mixer's own scopes.

`reduce/scopes.py` sorts every operation under the first of its fixed
scopes, none of which a Mamba-2 mixer carries. The mixer names its
parts: `ssm_in_proj`, `ssm_conv`, `ssm_scan` (the chunked scan: forward,
backward and the backward's second pass over the chunk-local products,
with the softplus and the skip term beside it), `ssm_gate_norm`,
`ssm_out_proj`. This file sorts the operations by those with
`scopes.tf_ops`, by scope and not by kernel name, so that a later
implementation of the scan is read as the same work. The optimizer's
update of the mixer's parameters is not in them
(`optimizer_time_share`).

A program that names no such scope (a model without state-space layers,
an older program) gives an empty table, and the readers report nothing.
"""

from __future__ import annotations

import collections

from benchmark.harness.procs import say
from benchmark.reduce import scopes, xplane

SCAN = "ssm_scan"
SCOPES = ("ssm_in_proj", "ssm_conv", SCAN, "ssm_gate_norm", "ssm_out_proj")


def scope_of(tf_op: str | None) -> str | None:
    parts = set((tf_op or "").rstrip(":").split("/"))
    return next((s for s in SCOPES if s in parts), None)


def of(ev: dict) -> dict | None:
    """{"by_scope": device seconds by scope, averaged over the chips,
    inside the window of whole steps, "busy_s"}; made once a run."""
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if "ssm_scopes" not in ev:
        names = scopes.tf_ops(trace["path"])
        n = len(trace["devices"])
        by_scope: collections.Counter = collections.Counter()
        for dev, plane in trace["devices"].items():
            ops = names.get(dev, {})
            for scope, seconds in xplane.seconds_by(
                    {"devices": {dev: plane}}, lambda op: scope_of(
                        ops.get(op[2].split(" ", 1)[0]))).items():
                if scope is not None:
                    by_scope[scope] += seconds / n
        busy = trace["busy_s"]
        if by_scope:
            say("device seconds under the state-space mixer's scopes "
                "(share of busy time): " + ", ".join(
                    f"{k} {v:.4f} ({100 * v / busy:.1f} %)" for k, v in
                    sorted(by_scope.items(), key=lambda kv: -kv[1])))
        ev["ssm_scopes"] = {"by_scope": dict(by_scope), "busy_s": busy}
    return ev["ssm_scopes"]


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
