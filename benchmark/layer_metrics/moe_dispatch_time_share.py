"""Step: device time of the part of the mixture-of-experts layer that
is not matmul — the router (logits, softmax, top-k, its statistics), the
dispatch (sort by expert, gather of the rows) and the combine
(un-permute, weight and sum) — over busy time. With `moe_time_share` it
splits the layer into moving rows and multiplying them."""

from benchmark.reduce import moe_scopes


def read(cell, ev):
    return moe_scopes.share(ev, *moe_scopes.DISPATCH)
