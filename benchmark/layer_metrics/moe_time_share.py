"""Step: device time of the whole mixture-of-experts layer (router,
dispatch, the grouped expert matmuls with the activation between them,
combine; forward and backward) over the time in which any operation ran,
inside the traced window. The optimizer's update of the expert tables is
not in it (`optimizer_time_share`)."""

from benchmark.reduce import moe_scopes


def read(cell, ev):
    return moe_scopes.share(ev, *moe_scopes.LAYER)
