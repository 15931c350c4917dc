"""Kernels: device time of the chunked state-space scan alone (`ssm_scan`:
forward, remat's replay, backward with its second pass over the
chunk-local products) over busy time. With `ssm_time_share` it splits
the mixer into the scan and the projections around it."""

from benchmark.reduce import ssm_scopes


def read(cell, ev):
    return ssm_scopes.share(ev, ssm_scopes.SCAN)
