"""The two elementwise stages of a Mamba-2 mixer around its scan, each
one pass over HBM in each direction.

Between `in_proj` and the scan, per channel of the middle part of the
projection (x | B | C, `start` columns in):

    acc_t = bias + sum_k taps[k] * xBC_{t - (K - 1) + k}     K taps, causal,
    x | B | C = silu(acc)                                    zero before t = 0

and between the scan and `out_proj`, per row over all H*P features, with
z the first H*P columns of the same projection:

    g   = (y + D x) * silu(z)                  D per head
    out = g * rsqrt(mean(g^2) + eps) * scale

Both are memory-bound: a few multiply-adds an element. As XLA
expressions (`_conv_expressions`, `_gate_norm_expressions`: what the
mixer held until PR 37, and what runs off a TPU) the conv is a padded
copy and four shifted reads of packed bfloat16 rows, its backward four
pads and a column reduction, and the gate and the norm a chain of
float32 passes that the row reduction splits; on the chip they took 6.8
and 3.2 times their traffic's time (PERF.md §6, PR 37).

On a TPU, where the shapes meet the tiles (`_conv_plan`, `_norm_plan`),
each stage is a Pallas kernel with a written-out backward
(`custom_vjp`), chosen as `ops/ssd.py` chooses: by the backend and the
shapes a trace can see, no flag. Each reads its operands once and
writes its results once:

* the conv reads its columns straight out of the projection by block
  index (a slice handed to the call would be a copy), the 16 rows above
  a block as a second small block of the same array, and writes x, B and
  C as arrays of their own, which is how the scan's kernels take them.
  One kernel body, called on the x columns and on the B|C columns. The
  backward rebuilds the pre-activation from the input, reads the 16
  rows below a block too, writes d(input) and adds the taps' and the
  bias's gradients up in a float32 block that stays in VMEM over rows
  and batch. x has two users (the scan, the skip term): `conv` hands
  it out twice, so that the two cotangents reach this kernel apart and
  are summed in it.
* the gate and the norm read y, x and z (out of the projection, by
  block index) a block of whole rows at a time; the backward rebuilds a
  row's statistics and writes dy, dz and the skip term's dx, and adds
  d(D) and d(scale) up the same way.

Inside, a block is walked 16 rows at a time (one packed bfloat16 tile),
so that a step's chain lives in vector registers; shifted rows are
sublane rotations of float32 values in 8-row pieces (a vector register
each), never of packed ones. The conv's kernels are bound by the vector
ALU about as much as by HBM (the compiler's bundle counts, PERF.md §6),
the gate and norm's by HBM.

Numbers: bfloat16 (the model's dtype) at HBM, float32 inside, as the
expressions: the conv's sum, both SiLUs, the skip term, the gate, the
mean of squares and the norm are float32, and what leaves for HBM is
rounded where the expressions round it. The backward kernels sum a
gradient's terms in float32 and round once, where autodiff of the
expressions rounds each of the conv's four terms to the model's dtype
first.

Scopes: every call runs under the scope the expressions ran under
(`ssm_conv`, `ssm_gate_norm`), opened here around forward and backward.

No reference counterpart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import ssd
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.ops.ssm_stages")

_F32 = jnp.float32
_LANES = 128
_SUB = 16            # rows a step of the kernels' loops: a packed bf16 tile
_CONV_ROWS = 1024    # rows a grid step, the conv's kernels
_NORM_ROWS = 128     # rows a grid step, the gate and norm's (whole rows)
_VMEM = 64 << 20     # a call's double-buffered blocks pass the default 16 MiB


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _rows_at(i):
    return pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)


def _halves(t):
    return t[:_SUB // 2], t[_SUB // 2:]


def _fold(t):
    """(16, C) float32 as the (8, C) sum of its halves: a column sum as
    far as vector adds take it (the last 8 rows are XLA's)."""
    top, bottom = _halves(t)
    return top + bottom


def _silu_and_slope(t):
    sg = jax.nn.sigmoid(t)
    return t * sg, sg * (1.0 + t * (1.0 - sg))


# -- the conv -----------------------------------------------------------------

def _conv_expressions(xbc, taps, bias, sizes):
    """silu(causal depthwise conv + bias) of xbc (B, S, C), split into
    pieces of `sizes` columns; taps (K, C): output t sums tap k of input
    t - (K - 1) + k."""
    width, s = taps.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    acc = bias.astype(_F32)
    for k in range(width):
        acc = acc + padded[:, k:k + s].astype(_F32) * taps[k]
    out = jax.nn.silu(acc).astype(xbc.dtype)
    cuts = [sum(sizes[:i + 1]) for i in range(len(sizes) - 1)]
    return tuple(jnp.split(out, cuts, -1))


def _shifted(above, cur, width, row):
    """[x_{t-s} for s < width] for the 16 rows of `cur`, `above` the 8
    rows before them (float32 both, `row` the sublane of an 8-row
    piece): each 8-row piece rotated down its sublanes, its first s rows
    taken from the rotated piece before it."""
    top, bottom = _halves(cur)
    out = [cur]
    for s in range(1, width):
        before, first, second = (pltpu.roll(t, s, 0)
                                 for t in (above, top, bottom))
        out.append(jnp.concatenate([jnp.where(row < s, before, first),
                                    jnp.where(row < s, first, second)]))
    return out


def _ahead(cur, below, s, row):
    """x_{t+s} for the 16 rows of `cur`, `below` the 8 rows after them:
    `_shifted`'s mirror, a rotation up the sublanes."""
    top, bottom = _halves(cur)
    first, second, after = (pltpu.roll(t, _SUB // 2 - s, 0)
                            for t in (top, bottom, below))
    last = row >= _SUB // 2 - s
    return jnp.concatenate([jnp.where(last, second, first),
                            jnp.where(last, after, second)])


def _pre_activation(shifted, taps_ref, bias_ref):
    width = taps_ref.shape[0]
    acc = bias_ref[...] + taps_ref[width - 1:width, :] * shifted[0]
    for s in range(1, width):
        acc = acc + taps_ref[width - 1 - s:width - s, :] * shifted[s]
    return acc


def _last8(ref, at=slice(None)):
    """The last 8 of 16 rows of a block, float32."""
    return _halves(ref[0, at, :].astype(_F32))[1]


def _conv_fwd_kernel(x_ref, above_ref, taps_ref, bias_ref, *out_refs):
    """One (batch, row block, column block) step. x_ref (1, R, C): the
    block; above_ref (1, 16, C): the 16 rows before it (any, where the
    block is a sequence's first); taps_ref (K, C), bias_ref (1, C),
    float32; out_refs: (1, R, C / n) each, the block's columns dealt
    out in order."""
    rows, cols = x_ref.shape[1:]
    each = cols // len(out_refs)
    row = _iota((_SUB // 2, cols), 0)
    above = jnp.where(pl.program_id(1) > 0, _last8(above_ref), 0.0)

    def step(i, above):
        at = _rows_at(i)
        cur = x_ref[0, at, :].astype(_F32)
        out, _ = _silu_and_slope(_pre_activation(
            _shifted(above, cur, taps_ref.shape[0], row), taps_ref, bias_ref))
        for k, ref in enumerate(out_refs):
            ref[0, at, :] = out[:, k * each:(k + 1) * each].astype(ref.dtype)
        return _halves(cur)[1]

    lax.fori_loop(0, rows // _SUB, step, above)


def _conv_bwd_kernel(x_ref, above_ref, below_ref, taps_ref, bias_ref, *refs,
                     terms):
    """One (column block, batch, row block) step of the backward.

    As the forward's, and: below_ref (1, 16, C), the 16 rows after the
    block (any, where it is a sequence's last); refs: the cotangents'
    blocks (1, R, C / n), terms[k] of them for the forward's k-th of n
    outputs (an output used twice hands two, summed here in float32),
    then their 16 rows after the block, then the outputs: dx_ref
    (1, R, C) and sums_ref (K + 1, 8, C) float32, the taps' and the
    bias's gradients of every row so far as 8 partial sums, resident
    while batch and rows go by."""
    n = sum(terms)
    g_refs, g_below_refs, (dx_ref, sums_ref) = (
        refs[:n], refs[n:2 * n], refs[2 * n:])
    rows, cols = x_ref.shape[1:]
    width = taps_ref.shape[0]
    r, last = pl.program_id(2), pl.num_programs(2) - 1
    row = _iota((_SUB // 2, cols), 0)

    @pl.when((pl.program_id(1) == 0) & (r == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def wide(parts, at):
        parts = iter(parts)
        return jnp.concatenate([sum(next(parts)[0, at, :].astype(_F32)
                                    for _ in range(k)) for k in terms],
                               axis=1)

    def d_pre(above, cur, g):
        """The shifted inputs and d(pre-activation) of cur's rows."""
        shifted = _shifted(above, cur, width, row)
        _, slope = _silu_and_slope(
            _pre_activation(shifted, taps_ref, bias_ref))
        return shifted, g * slope

    # what the rows below the block hand up: their d(pre-activation)
    _, below = d_pre(_last8(x_ref, slice(rows - _SUB, rows)),
                     below_ref[0].astype(_F32),
                     wide(g_below_refs, slice(None)))
    below = jnp.where(r < last, _halves(below)[0], 0.0)

    def step(i, above, below):
        at = _rows_at(i)
        shifted, d_acc = d_pre(above, x_ref[0, at, :].astype(_F32),
                               wide(g_refs, at))
        for s in range(width):
            sums_ref[width - 1 - s] += _fold(d_acc * shifted[s])
        sums_ref[width] += _fold(d_acc)
        # dx_t = sum_s taps[K-1-s] d_acc_{t+s}
        dx = taps_ref[width - 1:width, :] * d_acc
        for s in range(1, width):
            dx = dx + taps_ref[width - 1 - s:width - s, :] * _ahead(
                d_acc, below, s, row)
        dx_ref[0, at, :] = dx.astype(dx_ref.dtype)
        return _halves(d_acc)[0]

    def walk(k, below):
        i = rows // _SUB - 1 - k
        return step(i, _last8(x_ref, _rows_at(i - 1)), below)

    below = lax.fori_loop(0, rows // _SUB - 1, walk, below)
    step(0, jnp.where(r > 0, _last8(above_ref), 0.0), below)


def _conv_plan(s: int, start: int, sizes) -> dict | None:
    """The blocks of the conv's kernels for a sequence of s rows whose
    channels start `start` columns into the projection and leave as
    pieces of `sizes` columns (x, then B and C), or None where the
    shapes do not meet the tiles: whole 128-lane slabs in every piece,
    column blocks that the pieces' starts are multiples of, whole row
    blocks."""
    inner, *rest = sizes
    rows = min(_CONV_ROWS, s)
    cols = next((c for c in (1024, 512, 256, 128)
                 if inner % c == 0 and start % c == 0), None)
    tail = sum(rest)
    if cols is None or s % rows or rows % _SUB or len(set(rest)) != 1 \
            or rest[0] % _LANES or (start + inner) % tail or inner % tail:
        return None
    return {"rows": rows, "cols": cols, "tail": tail}


def _conv_specs(rows, cols, first, per_out, order, s):
    """Block specs by name for a grid whose indices `order` turns into
    (batch, row block, column block); the projection's column blocks
    start at block `first`; `above` and `below` make the 16-row blocks
    before and after a row block (the nearest inside the sequence, at
    its ends)."""
    def spec(block, where):
        return pl.BlockSpec(block, lambda *g: where(*order(*g)))
    per, last = rows // _SUB, s // _SUB - 1

    def near(block, first, at):
        return spec(block, lambda b, r, j: (
            b, jnp.clip(at(r), 0, last), first + j))
    return dict(
        spec=spec,
        x=spec((1, rows, cols), lambda b, r, j: (b, r, first + j)),
        above=near((1, _SUB, cols), first, lambda r: r * per - 1),
        below=near((1, _SUB, cols), first, lambda r: (r + 1) * per),
        g_below=near((1, _SUB, cols // per_out), 0, lambda r: (r + 1) * per),
        out=spec((1, rows, cols // per_out), lambda b, r, j: (b, r, j)),
        dx=spec((1, rows, cols), lambda b, r, j: (b, r, j)))


# jitted so that a model's layers share one trace and one lowering of
# each kernel (ops/ssd.py and ops/flash_attention.py do the same)
@functools.partial(jax.jit, static_argnames=("at", "cols", "n_out", "rows",
                                             "interpret"))
def _conv_fwd_call(proj, taps, bias, *, at, cols, n_out, rows, interpret):
    """The forward over the projection's columns [at[0], at[0] + at[2]),
    which are the conv's channels from at[1] on, in column blocks of
    `cols`, each dealt out to n_out outputs."""
    bsz, s, _ = proj.shape
    start, chan, size = at
    sp = _conv_specs(rows, cols, start // cols, n_out,
                     lambda b, r, j: (b, r, j), s)
    spec = sp["spec"]
    return pl.pallas_call(
        _conv_fwd_kernel,
        grid=(bsz, s // rows, size // cols),
        in_specs=[sp["x"], sp["above"],
                  spec((taps.shape[0], cols),
                       lambda b, r, j: (0, chan // cols + j)),
                  spec((1, cols), lambda b, r, j: (0, chan // cols + j))],
        out_specs=[sp["out"]] * n_out,
        out_shape=[jax.ShapeDtypeStruct((bsz, s, size // n_out), proj.dtype)
                   ] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_conv_fwd",
    )(proj, proj, taps, bias)


@functools.partial(jax.jit, static_argnames=("at", "cols", "rows", "terms",
                                             "interpret"))
def _conv_bwd_call(proj, taps, bias, gs, *, at, cols, rows, terms,
                   interpret):
    """The backward over the same columns: d(those columns) (B, S, size)
    and the partial sums (K + 1, 8, size) of the taps' and the bias's
    gradients; gs the cotangents, terms[k] of them for the forward's
    k-th output."""
    bsz, s, _ = proj.shape
    start, chan, size = at
    n, width = len(terms), taps.shape[0]
    sp = _conv_specs(rows, cols, start // cols, n,
                     lambda j, b, r: (b, r, j), s)
    spec = sp["spec"]
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, terms=terms),
        grid=(size // cols, bsz, s // rows),
        in_specs=[sp["x"], sp["above"], sp["below"],
                  spec((width, cols), lambda b, r, j: (0, chan // cols + j)),
                  spec((1, cols), lambda b, r, j: (0, chan // cols + j))]
        + [sp["out"]] * len(gs) + [sp["g_below"]] * len(gs),
        out_specs=[sp["dx"],
                   spec((width + 1, _SUB // 2, cols),
                        lambda b, r, j: (0, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, size), proj.dtype),
                   jax.ShapeDtypeStruct((width + 1, _SUB // 2, size), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_conv_bwd",
    )(proj, proj, proj, taps, bias, *gs, *gs)


def _conv_parts(start, sizes, plan):
    """(columns of the projection, of the conv's channels, how many),
    column block and outputs of the two calls: x, then B|C."""
    inner = sizes[0]
    return (((start, 0, inner), plan["cols"], 1),
            ((start + inner, inner, plan["tail"]), plan["tail"],
             len(sizes) - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernels(proj, taps, bias, start, sizes, interpret):
    return _conv_kernels_fwd(proj, taps, bias, start, sizes, interpret)[0]


def _conv_kernels_fwd(proj, taps, bias, start, sizes, interpret):
    plan = _conv_plan(proj.shape[1], start, sizes)
    with jax.named_scope("ssm_conv"):
        taps32, bias32 = taps.astype(_F32), bias.astype(_F32)[None]
        outs = []
        for at, cols, n_out in _conv_parts(start, sizes, plan):
            outs += _conv_fwd_call(proj, taps32, bias32, at=at, cols=cols,
                                   n_out=n_out, rows=plan["rows"],
                                   interpret=interpret)
    # x twice: see `conv`
    return (*outs, outs[0]), (proj, taps, bias)


def _conv_kernels_bwd(start, sizes, interpret, res, gs):
    proj, taps, bias = res
    plan = _conv_plan(proj.shape[1], start, sizes)
    with jax.named_scope("ssm_conv"):
        taps32, bias32 = taps.astype(_F32), bias.astype(_F32)[None]
        dxs, sums = [], []
        for (at, cols, n_out), g in zip(_conv_parts(start, sizes, plan),
                                        ((gs[0], gs[-1]), gs[1:-1])):
            dx, part = _conv_bwd_call(
                proj, taps32, bias32, tuple(g), at=at, cols=cols,
                rows=plan["rows"], terms=(len(g) // n_out,) * n_out,
                interpret=interpret)
            dxs.append(dx)
            sums.append(part)
        sums = jnp.sum(jnp.concatenate(sums, axis=-1), axis=1)
        # the projection's other columns are other cotangents' (XLA adds
        # the padded pieces up in the one pass that d(projection) is)
        after = proj.shape[-1] - start - sum(sizes)
        d_proj = jnp.pad(jnp.concatenate(dxs, axis=-1),
                         ((0, 0), (0, 0), (start, after)))
    return (d_proj, sums[:-1].astype(taps.dtype),
            sums[-1].astype(bias.dtype))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


# -- the gate and the norm ----------------------------------------------------

def _gate_norm_expressions(y, x, z, skip, scale, eps):
    """RMSNorm((y + D x) * silu(z)) * scale over the last dimension of z
    (B, S, H P); y, x (B, S, H, P), skip (H,), scale (H P,)."""
    g = y.astype(_F32) + skip[:, None] * x.astype(_F32)
    g = g.reshape(z.shape) * jax.nn.silu(z.astype(_F32))
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g * scale).astype(y.dtype)


def _gated(y_ref, x_ref, z_ref, skip_ref, at):
    """Float32, for 16 rows: x, z, y + D x, silu(z) and its slope."""
    x, z = x_ref[0, at, :].astype(_F32), z_ref[0, at, :].astype(_F32)
    return (x, z, y_ref[0, at, :].astype(_F32) + skip_ref[...] * x,
            *_silu_and_slope(z))


def _norm_fwd_kernel(y_ref, x_ref, z_ref, skip_ref, scale_ref, out_ref, *,
                     eps):
    """One (batch, row block) step: y_ref, x_ref, z_ref, out_ref
    (1, R, H P); skip_ref (D a lane), scale_ref: (1, H P) float32."""
    def step(i, _):
        at = _rows_at(i)
        _, _, skipped, gate, _ = _gated(y_ref, x_ref, z_ref, skip_ref, at)
        g = skipped * gate
        g = g * lax.rsqrt(jnp.mean(g * g, axis=1, keepdims=True) + eps)
        out_ref[0, at, :] = (g * scale_ref[...]).astype(out_ref.dtype)
        return 0

    lax.fori_loop(0, y_ref.shape[1] // _SUB, step, 0)


def _norm_bwd_kernel(y_ref, x_ref, z_ref, skip_ref, scale_ref, do_ref,
                     dy_ref, dx_ref, dz_ref, sums_ref, *, eps):
    """As the forward's, and: do_ref the output's cotangent; dy_ref,
    dx_ref (the skip term's), dz_ref like y_ref; sums_ref (2, 8, H P)
    float32: d(scale) and d(D) a lane of every row so far as 8 partial
    sums, resident over the whole grid."""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def step(i, _):
        at = _rows_at(i)
        x, z, skipped, gate, slope = _gated(y_ref, x_ref, z_ref, skip_ref, at)
        g = skipped * gate
        rstd = lax.rsqrt(jnp.mean(g * g, axis=1, keepdims=True) + eps)
        normed = g * rstd
        do = do_ref[0, at, :].astype(_F32)
        sums_ref[0] += _fold(do * normed)
        dn = do * scale_ref[...]
        dg = rstd * (dn - normed * jnp.mean(dn * normed, axis=1,
                                            keepdims=True))
        dy = dg * gate
        sums_ref[1] += _fold(dy * x)
        dy_ref[0, at, :] = dy.astype(dy_ref.dtype)
        dx_ref[0, at, :] = (dy * skip_ref[...]).astype(dx_ref.dtype)
        dz_ref[0, at, :] = (dg * skipped * slope).astype(dz_ref.dtype)
        return 0

    lax.fori_loop(0, y_ref.shape[1] // _SUB, step, 0)


def _norm_plan(s: int, inner: int) -> dict | None:
    """The gate and norm's row block, or None where the shapes do not
    meet the tiles: whole 128-lane slabs a row, whole row blocks."""
    rows = min(_NORM_ROWS, s)
    if inner % _LANES or s % rows or rows % _SUB:
        return None
    return {"rows": rows}


def _norm_specs(rows, inner):
    block = pl.BlockSpec((1, rows, inner), lambda b, r: (b, r, 0))
    lane = pl.BlockSpec((1, inner), lambda b, r: (0, 0))
    return block, lane


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def _norm_fwd_call(y, x, proj, skip, scale, *, eps, rows, interpret):
    bsz, s, inner = y.shape
    block, lane = _norm_specs(rows, inner)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        grid=(bsz, s // rows),
        in_specs=[block, block, block, lane, lane],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_gate_norm_fwd",
    )(y, x, proj, skip, scale)


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def _norm_bwd_call(y, x, proj, skip, scale, do, *, eps, rows, interpret):
    bsz, s, inner = y.shape
    block, lane = _norm_specs(rows, inner)
    like = jax.ShapeDtypeStruct(y.shape, y.dtype)
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        grid=(bsz, s // rows),
        in_specs=[block, block, block, lane, lane, block],
        out_specs=[block, block, block,
                   pl.BlockSpec((2, _SUB // 2, inner),
                                lambda b, r: (0, 0, 0))],
        out_shape=[like, like, like,
                   jax.ShapeDtypeStruct((2, _SUB // 2, inner), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_gate_norm_bwd",
    )(y, x, proj, skip, scale, do)


def _lanes_of(skip, scale, p):
    """D a lane (each head's over its P lanes) and the scale, (1, H P)
    float32."""
    return (jnp.repeat(skip.astype(_F32), p)[None],
            scale.astype(_F32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _norm_kernels(y, x, proj, skip, scale, eps, interpret):
    return _norm_kernels_fwd(y, x, proj, skip, scale, eps, interpret)[0]


def _norm_kernels_fwd(y, x, proj, skip, scale, eps, interpret):
    bsz, s, h, p = y.shape
    with jax.named_scope("ssm_gate_norm"):
        out = _norm_fwd_call(
            y.reshape(bsz, s, h * p), x.reshape(bsz, s, h * p), proj,
            *_lanes_of(skip, scale, p), eps=eps,
            rows=_norm_plan(s, h * p)["rows"], interpret=interpret)
    return out, (y, x, proj, skip, scale)


def _norm_kernels_bwd(eps, interpret, res, do):
    y, x, proj, skip, scale = res
    bsz, s, h, p = y.shape
    with jax.named_scope("ssm_gate_norm"):
        dy, dx, dz, sums = _norm_bwd_call(
            y.reshape(bsz, s, h * p), x.reshape(bsz, s, h * p), proj,
            *_lanes_of(skip, scale, p), do, eps=eps,
            rows=_norm_plan(s, h * p)["rows"], interpret=interpret)
        sums = jnp.sum(sums, axis=1)
        d_proj = jnp.pad(dz, ((0, 0), (0, 0),
                              (0, proj.shape[-1] - h * p)))
    return (dy.reshape(y.shape), dx.reshape(x.shape), d_proj,
            jnp.sum(sums[1].reshape(h, p), axis=1).astype(skip.dtype),
            sums[0].astype(scale.dtype))


_norm_kernels.defvjp(_norm_kernels_fwd, _norm_kernels_bwd)


# -- which form, and the seam -------------------------------------------------

def _path(plan) -> tuple[str, bool | None]:
    """Which form a stage with this plan takes, from what can be seen:
    its name for the log, and the kernels' `interpret` flag (None: the
    expressions). `ssd.force_interpret_kernels()` is the tests' hook for
    these kernels too."""
    if plan is not None and jax.default_backend() == "tpu":
        return "pallas kernel, compiled", False
    if plan is not None and ssd._FORCE_INTERPRET:
        return "pallas kernel, interpret mode", True
    return "xla expressions", None


def conv(proj: jax.Array, taps: jax.Array, bias: jax.Array, *, start: int,
         sizes: tuple[int, int, int]) -> tuple[jax.Array, ...]:
    """x, B, C (B, S, sizes[i]) in proj's dtype, and x once more: silu
    of the causal depthwise conv, plus bias, of proj's columns [start,
    start + sum(sizes)); proj (B, S, W), taps (K, sum(sizes)), bias
    (sum(sizes),). x has two users, the scan and the skip term: given
    one of the two x each, their cotangents reach the backward's kernel
    apart and are summed there in float32, not in a pass of their own
    before it."""
    sizes = tuple(sizes)
    mode, interpret = _path(_conv_plan(proj.shape[1], start, sizes))
    log.info("ssm conv %s at %d, %s: %s", tuple(proj.shape), start, sizes,
             mode)
    if interpret is None:
        with jax.named_scope("ssm_conv"):
            outs = _conv_expressions(
                proj[..., start:start + sum(sizes)], taps, bias, sizes)
            return (*outs, outs[0])
    return _conv_kernels(proj, taps, bias, start, sizes, interpret)


def gate_norm(y: jax.Array, x: jax.Array, proj: jax.Array, skip: jax.Array,
              scale: jax.Array, *, eps: float) -> jax.Array:
    """(B, S, H P) in y's dtype: RMSNorm((y + skip x) * silu(z)) * scale
    with z proj's first H P columns; y, x (B, S, H, P), skip (H,),
    scale (H P,)."""
    _, s, h, p = y.shape
    mode, interpret = _path(_norm_plan(s, h * p))
    log.info("ssm gate and norm %s: %s", tuple(y.shape), mode)
    if interpret is None:
        with jax.named_scope("ssm_gate_norm"):
            return _gate_norm_expressions(y, x, proj[..., :h * p], skip,
                                          scale, eps)
    return _norm_kernels(y, x, proj, skip, scale, eps, interpret)


def describe(seq: int, heads: int, head_dim: int, state: int) -> str:
    """What a run logs of the two stages it built (`lm_train`'s start
    line): which form each takes here, and at which blocks."""
    inner = heads * head_dim
    conv_plan = _conv_plan(seq, inner, (inner, state, state))
    norm_plan = _norm_plan(seq, inner)

    def said(stage, plan, blocks):
        mode, interpret = _path(plan)
        at = "" if interpret is None else f", blocks {blocks(plan)}"
        return f"{stage} ({mode}{at})"
    return ", ".join((
        said("conv", conv_plan, lambda p:
             f"{p['rows']} x {p['cols']} and x {p['tail']}"),
        said("gate and norm", norm_plan, lambda p: f"{p['rows']} x {inner}")))
