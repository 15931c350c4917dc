"""Step: device time under the scope `opt_update` over busy time. A
fusion counts under the scope of its root: where the compiler fuses the
AdamW update of a weight into the matmul that makes its gradient, that
time is the matmul's scope's, not this one's (PERF.md §5)."""

from benchmark.reduce import scopes


def read(cell, ev):
    return scopes.share(ev, "by_scope", "opt_update")
