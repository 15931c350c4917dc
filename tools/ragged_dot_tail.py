"""Does XLA's grouped matmul on the chip pay for rows that are in no
group, and what does it leave there? `jax.lax.ragged_dot` at the afmoe
cell's shapes (T*k = 131,072 rows of 2,048 against 16 tables of
2,048 x 1,024, bfloat16), with every row in a group, with an eighth of
them in groups and the rest a tail (a chip's share of 128 experts at
balance), and with the buffer cut to that eighth; forward, and forward +
backward.

Beside the times, the fact `MoEMLP`'s masks rest on: each result is
written into a donated buffer that was filled with NaN beforehand, and
the tail of the operand and of the incoming cotangent is NaN too. A
kernel that neither reads nor writes a row in no group leaves the tail
of its output and of d(lhs) NaN (zero: it wrote them; the CPU does),
keeps the groups' rows finite, and makes the same d(rhs), bit for bit,
as from tails of zeros. Prints one JSON line; times are device times of
this chip and go under no metric's name.

    chiprun -- python tools/ragged_dot_tail.py
    JAX_PLATFORMS=cpu python tools/ragged_dot_tail.py 1024   # rehearsal
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


def nans(shape):
    return jnp.full(shape, jnp.nan, jnp.bfloat16)


def holds(rows) -> dict:
    """What a block of rows holds, as shares of its elements."""
    rows = np.asarray(rows.astype(jnp.float32))
    if rows.size == 0:
        return {"rows": 0}
    return {"rows": int(rows.shape[0]),
            "nan_share": float(np.isnan(rows).mean()),
            "zero_share": float((rows == 0).mean())}


def into_nan(fn, *shapes):
    """`fn`'s results written over donated buffers of NaN, one a result,
    and whether each result lies where its buffer lay."""
    n = len(shapes)
    jitted = jax.jit(lambda *a: fn(*a[n:]), donate_argnums=tuple(range(n)),
                     keep_unused=True)

    def call(*args):
        bufs = [nans(s) for s in shapes]
        jax.block_until_ready(bufs)
        at = [b.unsafe_buffer_pointer() for b in bufs]
        out = jitted(*bufs, *args)
        out = out if isinstance(out, tuple) else (out,)
        jax.block_until_ready(out)
        return out, [o.unsafe_buffer_pointer() == p for o, p in zip(out, at)]
    return call


def pull(x, w, g, dy):
    return jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, g), x, w)[1](dy)


def main(argv: list[str]) -> int:
    rows_all = int(argv[0]) if argv else ROWS
    d, ff = (D, FF) if not argv else (128, 64)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (HELD, d, ff), jnp.bfloat16) * 0.02
    out = {"platform": jax.devices()[0].platform, "rows": rows_all}
    fwd = jax.jit(lambda x, w, g: jax.lax.ragged_dot(x, w, g))
    both = jax.jit(jax.grad(lambda x, w, g: jnp.sum(
        jax.lax.ragged_dot(x, w, g).astype(jnp.float32) ** 2),
        argnums=(0, 1)))
    for name, rows, each in (
            ("all_rows_in_groups", rows_all, rows_all // HELD),
            ("an_eighth_in_groups", rows_all, rows_all // HELD // 8),
            ("buffer_cut_to_the_eighth", rows_all // 8,
             rows_all // HELD // 8)):
        kx, ky = jax.random.split(jax.random.fold_in(key, rows))
        x = jax.random.normal(kx, (rows, d), jnp.bfloat16)
        dy = jax.random.normal(ky, (rows, ff), jnp.bfloat16)
        g = jnp.full((HELD, ), each, jnp.int32)
        n = HELD * each
        tail = (jnp.arange(rows) >= n)[:, None]
        x_nan, dy_nan = (jnp.where(tail, jnp.nan, a) for a in (x, dy))
        x_zero, dy_zero = (jnp.where(tail, 0, a) for a in (x, dy))
        (y,), y_there = into_nan(jax.lax.ragged_dot, (rows, ff))(
            x_nan, w, g)
        cot = into_nan(pull, (rows, d), (HELD, d, ff))
        (dx, dw), d_there = cot(x_nan, w, g, dy_nan)
        (_, dw_zero), _ = cot(x_zero, w, g, dy_zero)
        out[name] = {
            "forward_ms": timed(fwd, x, w, g),
            "forward_backward_ms": timed(both, x, w, g),
            "results_lie_in_the_nan_buffers": y_there + d_there,
            "y_groups": holds(y[:n]), "y_tail": holds(y[n:]),
            "dx_groups": holds(dx[:n]), "dx_tail": holds(dx[n:]),
            "dw": holds(dw.reshape(-1, ff)),
            "dw_same_bits_as_from_zero_tails": bool(np.array_equal(
                np.asarray(dw.astype(jnp.float32)),
                np.asarray(dw_zero.astype(jnp.float32))))}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
