"""Kernels: device time under the scope `xent` (the streamed-vocabulary
cross-entropy's two `while` loops, forward and backward) over the time
in which any operation ran, inside the traced window."""

from benchmark.reduce import scopes


def read(cell, ev):
    return scopes.share(ev, "by_scope", "xent")
