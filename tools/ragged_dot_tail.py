"""Does XLA's grouped matmul on the chip pay for rows that are in no
group? `jax.lax.ragged_dot` at the afmoe cell's shapes (T*k = 131,072
rows of 2,048 against 16 tables of 2,048 x 1,024, bfloat16), with every
row in a group, with an eighth of them in groups and the rest a tail
(a chip's share of 128 experts at balance), and with the buffer cut to
that eighth; forward, and forward + backward. Also what the tail rows
of the output hold. Prints one JSON line; times are device times of
this chip and go under no metric's name.

    chiprun -- python tools/ragged_dot_tail.py
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS, D, FF, HELD = 131072, 2048, 1024, 16


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main() -> int:
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (HELD, D, FF), jnp.bfloat16) * 0.02
    out = {"platform": jax.devices()[0].platform, "rows": ROWS}
    fwd = jax.jit(lambda x, w, g: jax.lax.ragged_dot(x, w, g))
    both = jax.jit(jax.grad(lambda x, w, g: jnp.sum(
        jax.lax.ragged_dot(x, w, g).astype(jnp.float32) ** 2),
        argnums=(0, 1)))
    for name, rows, each in (("all_rows_in_groups", ROWS, ROWS // HELD),
                             ("an_eighth_in_groups", ROWS, ROWS // HELD // 8),
                             ("buffer_cut_to_the_eighth", ROWS // 8,
                              ROWS // HELD // 8)):
        x = jax.random.normal(jax.random.fold_in(key, rows), (rows, D),
                              jnp.bfloat16)
        g = jnp.full((HELD,), each, jnp.int32)
        y = np.asarray(fwd(x, w, g).astype(jnp.float32))
        dx = np.asarray(both(x, w, g)[0].astype(jnp.float32))
        tail, dtail = y[HELD * each:], dx[HELD * each:]
        out[name] = {
            "forward_ms": timed(fwd, x, w, g),
            "forward_backward_ms": timed(both, x, w, g),
            "tail_rows": int(tail.shape[0]),
            "tail_all_zero": bool(tail.size == 0 or not tail.any()),
            "tail_finite": bool(np.isfinite(tail).all()),
            "dx_tail_all_zero": bool(dtail.size == 0 or not dtail.any()),
            "dx_tail_finite": bool(np.isfinite(dtail).all())}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
