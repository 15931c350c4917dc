"""Step: median period of the jitted step on the device's `XLA Modules`
line (start of one step's event to the start of the next), over the
chips. From the device trace, never from a host clock."""

from benchmark.reduce import xplane


def read(cell, ev):
    return xplane.step_ms(ev["trace"]) if "trace" in ev else None
