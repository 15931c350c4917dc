"""Device seconds under the scopes that latent attention and the
multi-token-prediction module add to a step: `mla_q` and `mla_kv` (the
mixer's two latents: down-projection, norm, up-projection, the heads
put together, the shared rotary key repeated) and `mtp` (the module:
its embedding lookup, merge, block and norm, and its sweep of the
streamed CE). An operation counts under every one of these that is on
its name stack (`block2/attn/mla_q/...` under `mla_q`;
`mtp/block/attn/mla_q/...` under `mla_q` and under `mtp`), found with
`scopes.tf_ops`. A program that names none of them (another
architecture, an older program) gives an empty table, and the readers
report nothing.
"""

from __future__ import annotations

import collections

from benchmark.harness.procs import say
from benchmark.reduce import scopes, xplane

SCOPES = ("mla_q", "mla_kv", "mtp")


def of(ev: dict) -> dict | None:
    """{"by_scope": device seconds by scope, averaged over the chips,
    inside the window of whole steps, "busy_s"}; made once a run."""
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if "mla_scopes" not in ev:
        names = scopes.tf_ops(trace["path"])
        n = len(trace["devices"])
        by_scope: collections.Counter = collections.Counter()
        for dev, plane in trace["devices"].items():
            ops = names.get(dev, {})

            def under(op):
                """Every one of SCOPES on the operation's name stack."""
                path = (ops.get(op[2].split(" ", 1)[0]) or "").rstrip(":")
                return tuple(s for s in SCOPES if s in path.split("/"))
            for scopes_, spent in xplane.seconds_by(
                    {"devices": {dev: plane}}, under).items():
                for scope in scopes_:
                    by_scope[scope] += spent / n
        busy = trace["busy_s"]
        if by_scope:
            say("device seconds under the latent attention's and the "
                "prediction module's scopes (share of busy time): "
                + ", ".join(f"{k} {v:.4f} ({100 * v / busy:.1f} %)"
                            for k, v in by_scope.items()))
        ev["mla_scopes"] = {"by_scope": dict(by_scope), "busy_s": busy}
    return ev["mla_scopes"]


def share(ev: dict, *keys: str) -> float | None:
    """Per cent of busy device time under ``keys`` (each counted by
    itself: give keys that do not nest); nothing where the program
    wrote none of them."""
    got = of(ev)
    if got is None:
        return None
    spent = sum(got["by_scope"].get(k, 0.0) for k in keys)
    return 100.0 * spent / got["busy_s"] if spent else None
