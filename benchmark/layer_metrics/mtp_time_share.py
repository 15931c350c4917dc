"""Step: device time under the multi-token-prediction module's `mtp`
scope (the next tokens' embedding lookup, the two norms and the merge
projection, its whole expert block with its flash calls, its norm and
its own sweep of the streamed CE; forward, replayed and backward) over
the time in which any operation ran, inside the traced window. The
optimizer's update of the module's parameters is not in it
(`optimizer_time_share`)."""

from benchmark.reduce import mla_scopes


def read(cell, ev):
    return mla_scopes.share(ev, "mtp")
