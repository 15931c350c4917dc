"""Rotary positions as one pass: a Pallas kernel that reads q (or k) once
in its own type and writes it once.

`models/transformer.rope` states the formula, x*cos + cat(-x[D/2:],
x[:D/2])*sin. XLA does not fuse a slice and a concatenate that cut a
head's 128 lanes in the middle: it compiled the formula to three passes
with float32 tensors of the whole (B, S, H, D) written to HBM between
them, 1.6 GB a call on q where 0.27 GB are needed (PERF.md §6, PR 45).
Here the halves change places by a roll of the lanes by D/2, and the
minus sign rides in the table: sin_signed = cat(-sin[:D/2], sin[D/2:]),

    out = x*cos + roll(x, D/2)*sin_signed        float32 inside, cast back.

A rotation's transpose is the rotation by the negative angle, and the
angles' two halves are equal, so the backward is the same kernel with
that table negated: dx = g*cos - roll(g, D/2)*sin_signed. Its residuals
are the tables alone.

The kernel takes the head-major view the flash kernels take, (B*H, S,
D): a block of (1, rows, D) of x against (rows, D) of each float32
table, the heads innermost in the grid so that a table block is fetched
once for all of them. The transposes in and out are the flash
wrappers' own, inverted, and XLA cancels each pair.

`rows_for` is the rule that picks the path, logged once a trace as the
flash kernels log theirs; the formula in `models/transformer.py` is the
path everywhere else and the reference of the tests.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.ops.rope")

LANES = 128
# rows of the sequence a block at 128 lanes (fewer for a wider head: the
# same bytes), halved until they divide the sequence. On a v5e a q call
# of (32, 16384, 128) ran 0.94 / 0.67 / 0.52 / 0.50 / 0.50 ms at 256 /
# 512 / 1024 / 2048 / 4096 rows (tools/rope_chip_check.py; PERF.md §6,
# PR 45): 2048 is where the curve is flat, 0.5 MB of x a block.
ROWS = 2048
MIN_ROWS = 128
_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret_kernel():
    """Test hook: take the kernel off a TPU too, in interpret mode."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = False


def rows_for(x, mesh=None) -> int | None:
    """Rows of the sequence a block where the kernel takes x (B, S, H,
    D), else None: it wants a TPU (or the tests' interpret mode), heads
    of whole 128-lane rows, a sequence of whole blocks and no mesh that
    shards anything (a pallas_call is opaque to the partitioner)."""
    b, s, h, d = x.shape
    rows = max(MIN_ROWS, ROWS * LANES // d)
    while rows > MIN_ROWS and s % rows:
        rows //= 2
    if jax.default_backend() != "tpu" and not _FORCE_INTERPRET:
        why = f"backend {jax.default_backend()}"
    elif d % LANES:
        why = f"a head of {d} is no multiple of {LANES} lanes"
    elif s % rows:
        why = f"a sequence of {s} is no multiple of {rows}"
    elif mesh is not None and any(n > 1 for n in mesh.shape.values()):
        why = f"sharded by mesh {dict(mesh.shape)}"
    else:
        log.info("rope %s: pallas kernel, rows a block %d", (b, s, h, d),
                 rows)
        return rows
    log.info("rope %s: plain formula: %s", (b, s, h, d), why)
    return None


def _kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)
    half = pltpu.roll(x, x.shape[-1] // 2, 1)
    o_ref[0] = (x * cos_ref[...] + half * sin_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "name"))
def _call(x, cos, sin_signed, *, rows: int, interpret: bool, name: str):
    b, s, h, d = x.shape
    xt = x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = pl.pallas_call(
        _kernel,
        grid=(s // rows, b * h),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda si, bh: (bh, si, 0)),
            pl.BlockSpec((rows, d), lambda si, bh: (si, 0)),
            pl.BlockSpec((rows, d), lambda si, bh: (si, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda si, bh: (bh, si, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), x.dtype),
        interpret=interpret,
        name=name,
    )(xt, cos, sin_signed)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x, cos, sin_signed, rows):
    return _call(x, cos, sin_signed, rows=rows,
                 interpret=_FORCE_INTERPRET, name="rope_fwd")


def _rotate_fwd(x, cos, sin_signed, rows):
    return _rotate(x, cos, sin_signed, rows), (cos, sin_signed)


def _rotate_bwd(rows, tables, g):
    cos, sin_signed = tables
    dx = _call(g, cos, -sin_signed, rows=rows, interpret=_FORCE_INTERPRET,
               name="rope_bwd")
    # the tables come from positions, which take no gradient
    return dx, None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate(x: jax.Array, theta: float, positions, rows: int) -> jax.Array:
    """`models/transformer.rope(x, theta, positions)` on (B, S, H, D), in
    blocks of ``rows`` (`rows_for`) of the sequence."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return _rotate(x, jnp.concatenate([cos, cos], -1),
                   jnp.concatenate([-sin, sin], -1), rows)
