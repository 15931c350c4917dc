"""Step: device time under the blocks' `attn` module (the projections,
the norms a head, rope, the flash kernels forward, replayed and
backward, the gate, the output projection) over the time in which any
operation ran, inside the traced window. The optimizer's update of the
attention's parameters is not in it (`optimizer_time_share`)."""

from benchmark.reduce import scopes


def read(cell, ev):
    return scopes.share(ev, "by_scope", "attn")
