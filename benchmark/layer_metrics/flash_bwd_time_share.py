"""Kernels: device time in the flash-attention backward's two Mosaic
calls, `flash_bwd_dkdv` and `flash_bwd_dq`, over busy time. With
`pallas_time_share` (all Mosaic calls) it splits the attention kernels'
time into forward and backward."""

from benchmark.reduce import scopes


def read(cell, ev):
    return scopes.share(ev, "by_kernel", "flash_bwd_dkdv", "flash_bwd_dq")
