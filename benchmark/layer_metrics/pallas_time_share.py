"""Kernels: device time in Mosaic (Pallas) custom calls over the time in
which any operation ran, inside the traced window."""

from benchmark.reduce import xplane


def read(cell, ev):
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    by_kind = xplane.seconds_by(trace, lambda op: op[3])
    return 100.0 * by_kind.get("pallas", 0.0) / trace["busy_s"]
