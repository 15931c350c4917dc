"""Step: what the block-diffusion objective costs outside the kernels
and the matrix products: device time under the program's `attn_merge`
(the join of a noised query's two parts by their log-sum-exps),
`blockdiff_assemble` (building [noised ; clean], the split and the join)
and `attn_own_block` (the noised queries against their own block),
forward, replayed and backward, over the time in which any operation
ran, inside the traced window."""

from benchmark.reduce import blockdiff_scopes as bd


def read(cell, ev):
    spent = bd.seconds(ev, *bd.OVERHEAD)
    return None if spent is None else 100.0 * spent / bd.of(ev)["busy_s"]
