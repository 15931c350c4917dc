"""Step: device time of a rematerialised block's replay (`--remat on`:
the part of a block's forward that its backward runs again, because the
first run kept only the block's input and the values `models/
transformer.KEPT` names) over the time in which any operation ran,
inside the traced window. An operation counts where its name stack
holds `rematted_computation`, the scope JAX opens around the replay;
a fusion counts under the operation it is rooted in, and XLA's own
Mosaic kernel for a grouped matmul carries no name stack, so a replayed
one is not in it. None of this time is work the result needs: it is
what the memory it saves costs. Nothing for a program that replays
nothing (no `--remat`, or a trace without the scope)."""

from benchmark.reduce import scopes, xplane

SCOPE = "rematted_computation"


def replayed(tf_op: str | None) -> bool:
    return SCOPE in (tf_op or "").split("/")


def read(cell, ev):
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    names = scopes.tf_ops(trace["path"])
    spent = sum(
        xplane.seconds_by({"devices": {dev: plane}}, lambda op: replayed(
            names.get(dev, {}).get(op[2].split(" ", 1)[0]))).get(True, 0.0)
        for dev, plane in trace["devices"].items()) / len(trace["devices"])
    return 100.0 * spent / trace["busy_s"] if spent else None
