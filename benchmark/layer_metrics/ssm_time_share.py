"""Step: device time of the whole state-space mixer (input projection,
causal conv, the chunked scan, the gated norm, output projection;
forward, remat's replay and backward) over the time in which any
operation ran, inside the traced window. The MLP beside it and the
optimizer's update of its parameters are not in it."""

from benchmark.reduce import ssm_scopes


def read(cell, ev):
    return ssm_scopes.share(ev, *ssm_scopes.SCOPES)
