"""Step: device time under the latent attention's `mla_q` and `mla_kv`
scopes (both latents' down-projections, norms and up-projections, q and
k put together from their parts, the shared rotary key repeated to every
head; forward, replayed and backward) over the time in which any
operation ran, inside the traced window: what the latent costs outside
the flash kernels. Rope and the output projection are not in it."""

from benchmark.reduce import mla_scopes


def read(cell, ev):
    return mla_scopes.share(ev, "mla_q", "mla_kv")
