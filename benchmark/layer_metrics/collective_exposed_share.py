"""Mesh and sharding: the share of the traced window in which a
collective operation ran on a chip and no other operation did."""

from benchmark.reduce import xplane


def read(cell, ev):
    trace = ev.get("trace")
    if not trace or len(trace["devices"]) < 2:
        return None
    return 100.0 * xplane.exposed_collective_s(trace) / trace["window_s"]
