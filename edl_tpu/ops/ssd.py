"""The state-space scan of a Mamba-2 layer in its chunked (SSD) form,
forward and backward.

Per head, with a scalar decay a_t = exp(dt_t * A) (A < 0), a state S in
R^{P x N} and one B/C group shared by the heads:

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t        S_0 = 0
    y_t = S_t C_t

Walking t one step at a time is 8,192 dependent steps of rank-1
updates; the chunked form (Dao & Gu 2024, "state space duality") cuts
the sequence into chunks of Q positions and turns the work into matrix
products. With cs_i the running sum of dt*A inside a chunk (inclusive),
u_j = dt_j x_j, and `prev` the state that enters the chunk:

    y_i  = sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) u_j    inside the chunk
         + exp(cs_i) prev C_i                             what came before
    S    = sum_j exp(cs_last - cs_j) u_j (x) B_j          the chunk's own state
    prev_z = sum_{c<z} exp(sum_{c<k<z} cs_last_k) S_c     across chunks

The first line is a masked (Q, Q) product per chunk and head, `(C B^T o
L) u`; the others are (Q, N) x (N, P) products and one small product
over the chunks. Nothing here is sequential.

Numbers: the decay logs, dt, the running sums, every exponent and the
carried states are float32; exponents are always differences that are
<= 0 where they are used (never exp(cs_i) * exp(-cs_j), which overflows
inside one chunk at the published sizes). The matrix products take
their operands in x's dtype (bf16 under `--bf16`) and accumulate in
float32.

The backward is written out (`custom_vjp`), not left to autodiff: its
residuals are the inputs and the chunks' entering states, and it builds
the chunk-local products again from them, so no (Q, Q)-per-head tensor
outlives the call in either direction. The gradient of the running sums
uses the row/column identity of the masked product (sum_j dM_ij M_ij =
dy_i . y_i, sum_i dM_ij M_ij = u_j . du_j), both sides from the same
float32 accumulators, so the (Q, Q) product of dM and M is never formed.

Two forms of the same sums, chosen by what a trace can see
(`_kernel_interpret`): on a TPU, where the shapes meet the tiles
(`_fits`), two Pallas kernels, `ssd_fwd` and `ssd_bwd`; everywhere
else (the CPU, a chunk or a state off the 128 lanes) XLA einsums, which
are also the kernels' second opinion in the tests. Both run under the
`ssm_scan` scope, which this file alone opens.

The kernels walk a grid of (batch, chunk, block of heads), the chunk
axis in order (the backward from the last chunk to the first). What is
(Q, Q) - C B^T, the decay, the masked product, the backward's dy u^T -
is made in VMEM in (128, 128) pieces, used and dropped, and the pieces
above the diagonal are never made; the state that one chunk hands the
next (the backward: its gradient) stays in a float32 VMEM scratch of
(H, P, N), updated term by term, `prev <- exp(cs_last) prev + own`.
The forward reads x, B, C and the per-position rows once and writes y
and the entering states; the backward reads those and dy and writes dx,
dB, dC and two per-position sums. Per position and head and small, and
left to XLA around the calls: the running sums and their exponentials
going in (`_per_position`), the reverse running sum of d(cs), d(dt) and dA
coming out. The rounding points are the einsums': operands of every
product in x's dtype, float32 sums, float32 exponents of differences.

No reference counterpart.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.ops.ssd")

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_TILE = 128          # side of the pieces a (Q, Q) product is made in


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _local(x, dt, a, b, c):
    """What both directions build from the chunked inputs
    (x (B, C, Q, H, P), dt (B, C, Q, H), b and c (B, C, Q, N)): the
    running sums, the decay L head-major (0 above the diagonal), the
    masked product M = (c b^T) o L in x's dtype, u = dt x, the running
    sum at the chunk's end, the decay to it, and u under that decay."""
    q = x.shape[2]
    cs = jnp.cumsum(dt * a, axis=2)                         # (B, C, Q, H)
    cs_h = cs.transpose(0, 1, 3, 2)                         # (B, C, H, Q)
    seg = cs_h[..., :, None] - cs_h[..., None, :]           # cs_i - cs_j
    tril = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))         # L, 0 above
    g = _dot("bcin,bcjn->bcij", c, b)
    m = (g[:, :, None] * decay).astype(x.dtype)             # (B, C, H, Q, Q)
    u = (x * dt[..., None]).astype(x.dtype)
    last = cs[:, :, -1]                                     # (B, C, H)
    to_end = jnp.exp(last[:, :, None] - cs)                 # (B, C, Q, H)
    u_end = (x * (dt * to_end)[..., None]).astype(x.dtype)
    return cs, decay, m, u, last, to_end, u_end


def _transfer(last):
    """T[z, c] = exp(sum_{c<k<z} last_k) for c < z, else 0: how much of
    chunk c's own state is left when chunk z starts. The sums are made
    term by term (never as a difference of two long running sums, whose
    float32 error at 8,192 positions would show)."""
    n = last.shape[1]
    k = jnp.arange(n)
    between = (k[None, None, :] > k[None, :, None]) \
        & (k[None, None, :] < k[:, None, None])             # [z, c, k]
    seg = jnp.einsum("bkh,zck->bhzc", last, between.astype(_F32),
                     precision=_HIGHEST)
    return jnp.exp(jnp.where(k[:, None] > k[None, :], seg, -jnp.inf))


def _forward_einsums(x, dt, a, b, c):
    cs, _, m, u, last, _, u_end = _local(x, dt, a, b, c)
    own = _dot("bcjhp,bcjn->bchpn", u_end, b)               # (B, C, H, P, N)
    prev = jnp.einsum("bhzc,bchpn->bzhpn", _transfer(last), own,
                      precision=_HIGHEST)
    y = _dot("bchij,bcjhp->bcihp", m, u) + jnp.exp(cs)[..., None] * _dot(
        "bcin,bchpn->bcihp", c, prev.astype(x.dtype))
    return y, prev


# -- the kernels --------------------------------------------------------------
#
# Layout. A grid step works a block of _HB heads as tiles of 128 lanes:
# a tile is 128 / P heads side by side, (Q, 128) as x holds them
# ("natural": positions down the sublanes) or (128, Q) transposed
# (positions along the lanes, a head's P rows on top of the next's).
# What varies per position and head multiplies a natural tile as a
# (Q, 128) array, a transposed one as (128, Q). The second is a row
# broadcast down the sublanes, which costs next to nothing (`_down`).
# The first is a (Q, 1) column broadcast along the lanes, which the
# lane-crossing unit does a vector register at a time: a first version
# that made every factor so kept that unit busy two thirds of the time
# and ran no faster than the einsums. So the work is shared: the MXU
# makes most of them from the values' three bfloat16 pieces and a 0/1
# matrix (`_expand`: every product is x * 1 and the pieces add up
# exactly), the lane-crossing unit the rest (`_along`, a head's cs in
# `_masked`) and the tiles' transposes. Which unit does which was
# settled by the compiler's bundle counts and then on the chip
# (PERF.md, PR 35). States are (heads P, N): a tile's are (128, N),
# rows as the seam's (H, P, N).

_HB = 8              # heads a grid step
_CS, _DT, _W, _LEAD, _TO_END = range(5)   # per position and head:
_ROWS = 5            # cs, dt, dt * to_end, exp(cs), to_end


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _mm_nt(a, b):
    """a b^T: the last dimensions of both contracted (the MXU's native
    transposed-operand form)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _mm_tn(a, b):
    """a^T b: the first dimensions of both contracted."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _fits(q: int, h: int, p: int, n: int) -> bool:
    """Whether the shapes meet the kernels' tiles: the chunk and the
    state whole slabs of 128 lanes, whole heads side by side in 128
    lanes, blocks of 8 heads."""
    return q % _TILE == 0 and n % _TILE == 0 and p >= 16 \
        and _TILE % p == 0 and h % _HB == 0


def _per_position(dt, a):
    """What the kernels take per position and head, float32: cs, dt,
    dt * to_end, exp(cs), to_end, a head block's quantity-major, the
    positions along the lanes: rows (B, C, H / 8, 5 x 8, Q); and
    left (B, C, H): exp(cs_last), how much of a state is left at its
    chunk's end."""
    bsz, chunks, q, h = dt.shape
    cs = jnp.cumsum(dt * a, axis=2)
    lead, to_end = jnp.exp(cs), jnp.exp(cs[:, :, -1:] - cs)
    rows = jnp.stack([cs, dt, dt * to_end, lead, to_end], axis=2)
    rows = rows.reshape(bsz, chunks, _ROWS, q, h // _HB, _HB).transpose(
        0, 1, 4, 2, 5, 3).reshape(bsz, chunks, h // _HB, _ROWS * _HB, q)
    return rows, lead[:, :, -1]


def _pieces_of(rows_ref):
    """(Q, 128) bfloat16: the block's rows down the sublanes, each value
    as three bfloat16 pieces that add up to it exactly (hi | mid | lo,
    5 hb lanes each). Cut by masking bits, not by rounding, so that each
    piece is a bfloat16 value as it stands."""
    def top(v):
        return pltpu.bitcast(
            pltpu.bitcast(v, jnp.uint32) & jnp.uint32(0xFFFF0000), _F32)
    rows = rows_ref[0, 0, 0]
    hi = top(rows)
    mid = top(rows - hi)
    lo = rows - hi - mid
    pad = jnp.zeros((_TILE - 3 * rows.shape[0], rows.shape[1]), _F32)
    return jnp.concatenate([hi, mid, lo, pad], axis=0).T.astype(jnp.bfloat16)


def _selectors(p: int):
    """The 0/1 matrices (bfloat16, (128, 128) each) that `_expand`
    multiplies the pieces by: for quantity i and tile t at
    [i * tiles + t], lane l takes head t * (128 / p) + l // p; for head
    k at [5 * tiles + k] every lane takes that head's cs."""
    import numpy as np
    hb, per, tiles = _HB, _TILE // p, _HB * p // _TILE
    lane = np.arange(_TILE)
    out = np.zeros((_ROWS * tiles + hb, _TILE, _TILE), np.float32)
    for piece in range(3):
        at = piece * _ROWS * hb
        for i in range(_ROWS):
            for t in range(tiles):
                out[i * tiles + t, at + i * hb + t * per + lane // p,
                    lane] = 1
        for k in range(hb):
            out[_ROWS * tiles + k, at + _CS * hb + k, :] = 1
    return jnp.asarray(out, jnp.bfloat16)


def _expand(pieces, sel_ref, at):
    """(Q, 128) float32: the values selector `at` picks, exactly."""
    return _mm(pieces, sel_ref[at])


def _along(cols, which, heads, p):
    """(Q, 128) float32: quantity `which` of the tile's heads, each
    head's column broadcast along its P lanes: what `_expand` makes on
    the MXU, here on the lane-crossing unit (the two share the work)."""
    lane = _iota((1, _TILE), 1)
    out = cols[:, which * _HB + heads[0]:which * _HB + heads[0] + 1]
    for r, k in enumerate(heads[1:], 1):
        out = jnp.where(lane // p >= r,
                        cols[:, which * _HB + k:which * _HB + k + 1], out)
    return out


def _slab(i):
    return slice(i * _TILE, (i + 1) * _TILE)


def _transposed(tile):
    """A (Q, 128) tile as (128, Q) float32."""
    return tile.astype(_F32).T


def _down(rows_ref, which, heads, p):
    """(128, Q): quantity `which` of the tile's heads, each head's row
    broadcast down its P sublanes."""
    q = rows_ref.shape[-1]
    return jnp.concatenate([jnp.broadcast_to(
        rows_ref[0, 0, 0, which * _HB + k:which * _HB + k + 1, :], (p, q))
        for k in heads], axis=0)


def _pieces(q):
    """The (128, 128) pieces of a (Q, Q) product on or under the
    diagonal, by slab of rows and slab of columns."""
    return [(i, j) for i in range(q // _TILE) for j in range(i + 1)]


def _masked(g_ref, rows_ref, cols, k, i, j, tril, dtype):
    """Piece (i, j) of head k's decay L (float32, 0 above the diagonal)
    and of the masked product M = (C B^T) o L in the operands' dtype;
    `cols` the block's rows down the sublanes, cs first, a head a
    lane."""
    seg = cols[_slab(i), k:k + 1] - rows_ref[0, 0, 0, k:k + 1, _slab(j)]
    if i == j:
        seg = jnp.where(tril, seg, -jnp.inf)
    decay = jnp.exp(seg)
    return decay, (g_ref[_slab(i), _slab(j)] * decay).astype(dtype)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _left(left_ref, chunk, heads, p):
    """(128, 1): exp(cs_last) of the tile's heads, down their rows."""
    row = _iota((_TILE, 1), 0)
    out = jnp.zeros((_TILE, 1), _F32)
    for r, k in enumerate(heads):
        out = jnp.where(row // p == r,
                        left_ref[pl.program_id(0), chunk, k], out)
    return out


def _fwd_kernel(left_ref, x_ref, rows_ref, sel_ref, b_ref, c_ref,
                y_ref, prev_ref, g_ref, state_ref, *, p: int):
    """One (batch, chunk, head block) step of the forward.

    left_ref: (B, C, H) in SMEM; x_ref, y_ref: (1, 1, Q, hb P);
    rows_ref: (1, 1, 1, 5 hb, Q); sel_ref: `_selectors`; b_ref, c_ref:
    (1, 1, Q, N); prev_ref: (1, 1, hb P, N), the states that enter this
    chunk. Scratch,
    float32: g_ref (Q, Q), C B^T of the chunk, made by the chunk's
    first head block; state_ref (H P, N), what the chunks so far hand
    on."""
    ci, hi = pl.program_id(1), pl.program_id(2)
    q, dtype = x_ref.shape[2], x_ref.dtype
    hb, per, tiles = _HB, _TILE // p, _HB * p // _TILE
    bm, cm = b_ref[0, 0], c_ref[0, 0]
    mine = pl.ds(pl.multiple_of(hi * hb * p, _TILE), hb * p)

    @pl.when(hi == 0)
    def _():
        g_ref[...] = _mm_nt(cm, bm)

    @pl.when(ci == 0)
    def _():
        state_ref[mine] = jnp.zeros((hb * p, state_ref.shape[1]), _F32)

    pieces = _pieces_of(rows_ref)
    cols = rows_ref[0, 0, 0].T                                   # (Q, 5 hb)
    tril = _iota((_TILE, _TILE), 0) >= _iota((_TILE, _TILE), 1)
    lane = _iota((1, _TILE), 1)
    states = state_ref[mine]
    prev_ref[0, 0] = states
    handed = []
    for t in range(tiles):
        heads = list(range(t * per, (t + 1) * per))
        x = x_ref[0, 0, :, _slab(t)]
        u = (x.astype(_F32) * _expand(pieces, sel_ref, _DT * tiles + t)
             ).astype(dtype)
        # a head's u alone in its lanes, so that one product over
        # (head, column slab) gives every head of the tile its own
        alone = [jnp.where(lane // p == r, u, jnp.zeros_like(u))
                 for r in range(per)]
        prev = states[_slab(t)]
        y = _along(cols, _LEAD, heads, p) \
            * _mm_nt(cm, prev.astype(dtype))
        masked = {(k, i, j): _masked(g_ref, rows_ref, cols, k, i, j,
                                     tril, dtype)[1]
                  for k in heads for i, j in _pieces(q)}
        y_ref[0, 0, :, _slab(t)] = (y + jnp.concatenate([_mm(
            jnp.concatenate([masked[k, i, j] for k in heads
                             for j in range(i + 1)], axis=1),
            jnp.concatenate([alone[r][_slab(j)] for r in range(per)
                             for j in range(i + 1)], axis=0))
            for i in range(q // _TILE)])).astype(y_ref.dtype)
        u_end = (_transposed(x) * _down(rows_ref, _W, heads, p)
                 ).astype(dtype)
        handed.append(_left(left_ref, ci, [hi * hb + k for k in heads], p)
                      * prev + _mm(u_end, bm))
    state_ref[mine] = jnp.concatenate(handed)


def _bwd_kernel(left_ref, x_ref, dy_ref, rows_ref, sel_ref,
                b_ref, c_ref, prev_ref, dx_ref, sums_ref, db_ref, dc_ref,
                g_ref, dg_ref, db_acc, dc_acc, state_ref, *, p: int):
    """One (batch, chunk, head block) step of the backward; the grid's
    chunk axis walks the sequence from its end.

    As the forward's, and: dy_ref, dx_ref like x_ref; sums_ref
    (1, 1, 1, 2 hb, Q) float32: per head d(cs) before its running sum
    (row term minus column term, and at the last position what the
    chunk's end state carries on), then sum_p du x; db_ref, dc_ref
    (1, 1, Q, N), written by the chunk's last head block. Scratch,
    float32: dg_ref (Q, Q), the heads' dy u^T under their decay, summed;
    db_acc, dc_acc (Q, N); state_ref (H P, N), the gradient of the
    state this chunk hands on."""
    ci, hi = pl.program_id(1), pl.program_id(2)
    chunk = pl.num_programs(1) - 1 - ci
    q, dtype = x_ref.shape[2], x_ref.dtype
    hb, per, tiles = _HB, _TILE // p, _HB * p // _TILE
    bm, cm = b_ref[0, 0], c_ref[0, 0]
    mine = pl.ds(pl.multiple_of(hi * hb * p, _TILE), hb * p)

    @pl.when(hi == 0)
    def _():
        g_ref[...] = _mm_nt(cm, bm)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    @pl.when(ci == 0)
    def _():
        state_ref[mine] = jnp.zeros((hb * p, state_ref.shape[1]), _F32)

    pieces = _pieces_of(rows_ref)
    cols = rows_ref[0, 0, 0].T                                   # (Q, 5 hb)
    tril = _iota((_TILE, _TILE), 0) >= _iota((_TILE, _TILE), 1)
    lane = _iota((1, _TILE), 1)
    last = _iota((1, q), 1) == q - 1
    slabs = range(q // _TILE)
    states = state_ref[mine]
    handed = []
    for t in range(tiles):
        heads = list(range(t * per, (t + 1) * per))
        x, dy = x_ref[0, 0, :, _slab(t)], dy_ref[0, 0, :, _slab(t)]
        x32 = x.astype(_F32)
        u = (x32 * _expand(pieces, sel_ref, _DT * tiles + t)).astype(dtype)
        u_end = (x32 * _expand(pieces, sel_ref, _W * tiles + t)
                 ).astype(dtype)
        dy_lead = (dy.astype(_F32) * _along(cols, _LEAD, heads, p)
                   ).astype(dtype)
        # transposed, float32: positions along the lanes
        x_t, dy_t = _transposed(x), _transposed(dy)
        dt_down = _down(rows_ref, _DT, heads, p)
        lead_down = _down(rows_ref, _LEAD, heads, p)
        u_t = (x_t * dt_down).astype(dtype)
        dy_lo_t = dy_t.astype(dtype)
        dy_alone = [jnp.where(lane // p == r, dy, jnp.zeros_like(dy))
                    for r in range(per)]
        prev, d_own = prev_ref[0, 0, _slab(t)], states[_slab(t)]
        prev_lo, d_own_lo = prev.astype(dtype), d_own.astype(dtype)
        masked = {}
        for r, k in enumerate(heads):
            for i, j in _pieces(q):
                decay, masked[k, i, j] = _masked(
                    g_ref, rows_ref, cols, k, i, j, tril, dtype)
                dg_ref[_slab(i), _slab(j)] += _mm_nt(
                    dy_alone[r][_slab(i)], u[_slab(j)]) * decay
        # the output again, in float32 (the row term of d(cs)), and du:
        # a head's P rows against its own pieces, one product over the
        # slabs the piece's other side spans
        y_t = lead_down * _mm_nt(prev_lo, cm) + jnp.concatenate([
            jnp.concatenate([_mm_nt(
                u_t[r * p:(r + 1) * p, :(i + 1) * _TILE],
                jnp.concatenate([masked[k, i, j] for j in range(i + 1)],
                                axis=1)) for i in slabs], axis=1)
            for r, k in enumerate(heads)], axis=0)
        du_end_t = _down(rows_ref, _TO_END, heads, p) \
            * _mm_nt(d_own_lo, bm)
        du_t = du_end_t + jnp.concatenate([
            jnp.concatenate([_mm(
                dy_lo_t[r * p:(r + 1) * p, j * _TILE:],
                jnp.concatenate([masked[k, i, j] for i in slabs[j:]],
                                axis=0)) for j in slabs], axis=1)
            for r, k in enumerate(heads)], axis=0)
        db_acc[...] += _mm(u_end, d_own_lo)
        dc_acc[...] += _mm(dy_lead, prev_lo)
        # rows minus columns, and at a chunk's last position everything
        # its end state carries on; a head's P rows summed
        u32_t = u_t.astype(_F32)
        d_cs = dy_t * y_t - u32_t * du_t
        d_dt = du_t * x_t
        ends = u32_t * du_end_t
        kept = d_own * prev
        for r, k in enumerate(heads):
            at = slice(r * p, (r + 1) * p)
            d_last = jnp.sum(ends[at]) \
                + left_ref[pl.program_id(0), chunk, hi * hb + k] \
                * jnp.sum(kept[at])
            mine_cs = jnp.sum(d_cs[at], axis=0, keepdims=True)
            sums_ref[0, 0, 0, k:k + 1, :] = jnp.where(
                last, mine_cs + d_last, mine_cs)
            sums_ref[0, 0, 0, hb + k:hb + k + 1, :] = jnp.sum(
                d_dt[at], axis=0, keepdims=True)
        dx_ref[0, 0, :, _slab(t)] = (du_t * dt_down).T.astype(dx_ref.dtype)
        handed.append(
            _mm((dy_t * lead_down).astype(dtype), cm)
            + _left(left_ref, chunk, [hi * hb + k for k in heads], p)
            * d_own)
    state_ref[mine] = jnp.concatenate(handed)

    @pl.when(hi == pl.num_programs(2) - 1)
    def _():
        dg = dg_ref[...].astype(dtype)
        db_ref[0, 0] = (db_acc[...] + _mm_tn(dg, cm)).astype(db_ref.dtype)
        dc_ref[0, 0] = (dc_acc[...] + _mm(dg, bm)).astype(dc_ref.dtype)


def _specs(q, p, n, sel, chunk_at):
    """Block specs by name for a grid of (batch, chunk, head block)
    whose chunk index `chunk_at` turns into the chunk's place."""
    def spec(block, where):
        return pl.BlockSpec(block, lambda bi, ci, hi: where(
            bi, chunk_at(ci), hi))

    def per_block(rows):
        return spec((1, 1, 1, rows, q), lambda bi, ci, hi: (bi, ci, hi, 0, 0))
    return dict(
        left=pl.BlockSpec(memory_space=pltpu.SMEM),     # exp(cs_last), whole
        x=spec((1, 1, q, _HB * p), lambda bi, ci, hi: (bi, ci, 0, hi)),
        rows=per_block(_ROWS * _HB),
        sums=per_block(2 * _HB),
        sel=pl.BlockSpec(sel.shape, lambda bi, ci, hi: (0, 0, 0)),  # once
        b=spec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
        states=spec((1, 1, _HB * p, n), lambda bi, ci, hi: (bi, ci, hi, 0)))


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# jitted so that a model's layers share one trace and one lowering of
# each kernel (ops/flash_attention.py does the same, and says why)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward_pallas(x, dt, a, b, c, *, interpret: bool):
    bsz, n_chunks, q, h, p = x.shape
    n = b.shape[-1]
    rows, left = _per_position(dt, a)
    sel = _selectors(p)
    at = _specs(q, p, n, sel, lambda ci: ci)
    y, prev = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(bsz, n_chunks, h // _HB),
        in_specs=[at["left"], at["x"], at["rows"], at["sel"], at["b"],
                  at["b"]],
        out_specs=[at["x"], at["states"]],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_chunks, q, h * p), x.dtype),
            jax.ShapeDtypeStruct((bsz, n_chunks, h * p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((q, q), _F32),
                        pltpu.VMEM((h * p, n), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="ssd_fwd",
    )(left, x.reshape(bsz, n_chunks, q, h * p), rows, sel, b, c)
    return y.reshape(x.shape), prev.reshape(bsz, n_chunks, h, p, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward_pallas(x, dt, a, b, c, prev, dy, *, interpret: bool):
    bsz, n_chunks, q, h, p = x.shape
    n = b.shape[-1]
    rows, left = _per_position(dt, a)
    sel = _selectors(p)
    at = _specs(q, p, n, sel, lambda ci: n_chunks - 1 - ci)
    flat = (bsz, n_chunks, q, h * p)
    dx, sums, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(bsz, n_chunks, h // _HB),
        in_specs=[at["left"], at["x"], at["x"], at["rows"], at["sel"],
                  at["b"], at["b"], at["states"]],
        out_specs=[at["x"], at["sums"], at["b"], at["b"]],
        out_shape=[
            jax.ShapeDtypeStruct(flat, x.dtype),
            jax.ShapeDtypeStruct((bsz, n_chunks, h // _HB, 2 * _HB, q),
                                 _F32),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((q, q), _F32), pltpu.VMEM((q, q), _F32),
                        pltpu.VMEM((q, n), _F32), pltpu.VMEM((q, n), _F32),
                        pltpu.VMEM((h * p, n), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="ssd_bwd",
    )(left, x.reshape(flat), dy.reshape(flat), rows, sel, b, c,
      prev.reshape(bsz, n_chunks, h * p, n))
    # per position and head, in XLA: the reverse running sum of d(cs),
    # and what it gives the step sizes and the decay rates
    sums = sums.reshape(bsz, n_chunks, h // _HB, 2, _HB, q).transpose(
        3, 0, 1, 5, 2, 4).reshape(2, bsz, n_chunks, q, h)
    d_la = jnp.cumsum(sums[0, :, :, ::-1], axis=2)[:, :, ::-1]
    d_dt = d_la * a + sums[1]
    d_a = jnp.sum(d_la * dt, axis=(0, 1, 2))
    return dx.reshape(x.shape), d_dt, d_a, db, dc


_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret_kernels():
    """Test hook: run the kernels in interpret mode off a TPU, so that
    the tests can hold them to the einsums on the CPU."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = False


def _path(q: int, h: int, p: int, n: int) -> tuple[str, bool | None]:
    """Which form a scan of these sizes takes, from what can be seen:
    its name for the log, and the kernels' `interpret` flag (None: the
    einsums)."""
    if _fits(q, h, p, n) and jax.default_backend() == "tpu":
        return "pallas kernel, compiled", False
    if _fits(q, h, p, n) and _FORCE_INTERPRET:
        return "pallas kernel, interpret mode", True
    return "xla einsums", None


def _kernel_interpret(what: str, x, b) -> bool | None:
    """The `interpret` flag of this trace (None: the einsums), logged."""
    _, _, q, h, p = x.shape
    mode, interpret = _path(q, h, p, b.shape[-1])
    log.info("ssd scan %s %s: %s", what, tuple(x.shape), mode)
    return interpret


def _forward(x, dt, a, b, c):
    """y (B, C, Q, H, P) and the states that enter each chunk
    (B, C, H, P, N) float32, from the chunked inputs."""
    interpret = _kernel_interpret("fwd", x, b)
    if interpret is None:
        return _forward_einsums(x, dt, a, b, c)
    return _forward_pallas(x, dt, a, b, c, interpret=interpret)


def _chunked(t, chunk):
    return t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, a, b, c, chunk):
    return _ssd_fwd(x, dt, a, b, c, chunk)[0]


def _ssd_fwd(x, dt, a, b, c, chunk):
    with jax.named_scope("ssm_scan"):
        y, prev = _forward(_chunked(x, chunk), _chunked(dt, chunk), a,
                           _chunked(b, chunk), _chunked(c, chunk))
        return y.reshape(x.shape).astype(x.dtype), (x, dt, a, b, c, prev)


def _backward_einsums(x, dt, a, b, c, prev, dy):
    cs, decay, m, u, last, to_end, u_end = _local(x, dt, a, b, c)
    lead = jnp.exp(cs)[..., None]                           # (B, C, Q, H, 1)
    prev_lo = prev.astype(x.dtype)
    # the output again, in float32: the row term of d(cs)
    y = _dot("bchij,bcjhp->bcihp", m, u) \
        + lead * _dot("bcin,bchpn->bcihp", c, prev_lo)
    dy_lead = (dy * lead).astype(x.dtype)
    # states: what each chunk's entering state and own state receive
    d_prev = _dot("bcihp,bcin->bchpn", dy_lead, c)
    d_own = jnp.einsum("bhzc,bzhpn->bchpn", _transfer(last), d_prev,
                       precision=_HIGHEST)
    d_own_lo = d_own.astype(x.dtype)
    du_end = to_end[..., None] * _dot("bchpn,bcjn->bcjhp", d_own_lo, b)
    du = _dot("bchij,bcihp->bcjhp", m, dy) + du_end
    # d(c b^T): the heads' dM = dy u^T under their decay, summed
    dg = jnp.sum(_dot("bcihp,bcjhp->bchij", dy, u) * decay, axis=2)
    dg = dg.astype(x.dtype)
    db = _dot("bcij,bcin->bcjn", dg, c) \
        + _dot("bcjhp,bchpn->bcjn", u_end, d_own_lo)
    dc = _dot("bcij,bcjn->bcin", dg, b) \
        + _dot("bcihp,bchpn->bcin", dy_lead, prev_lo)
    # running sums: rows minus columns, and at a chunk's last
    # position everything its end state carries on
    u32 = u.astype(_F32)
    d_cs = jnp.sum(dy.astype(_F32) * y, -1) - jnp.sum(u32 * du, -1)
    d_last = jnp.sum(u32 * du_end, axis=(2, 4)) \
        + jnp.exp(last) * jnp.sum(d_own * prev, axis=(3, 4))
    d_cs = d_cs.at[:, :, -1].add(d_last)
    d_la = jnp.cumsum(d_cs[:, :, ::-1], axis=2)[:, :, ::-1]
    x32 = x.astype(_F32)
    d_dt = d_la * a + jnp.sum(du * x32, -1)
    d_a = jnp.sum(d_la * dt, axis=(0, 1, 2))
    dx = du * dt[..., None]
    return dx, d_dt, d_a, db, dc


def _ssd_bwd(chunk, res, dy):
    shapes = [r.shape for r in res[:5]]
    x, dt, a, b, c, prev = res
    x, dt, b, c, dy = (_chunked(t, chunk) for t in (x, dt, b, c, dy))
    with jax.named_scope("ssm_scan"):
        interpret = _kernel_interpret("bwd", x, b)
        if interpret is None:
            outs = _backward_einsums(x, dt, a, b, c, prev, dy)
        else:
            outs = _backward_pallas(x, dt, a, b, c, prev, dy,
                                    interpret=interpret)
    return tuple(g.reshape(s).astype(r.dtype)
                 for g, s, r in zip(outs, shapes, res))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256) -> jax.Array:
    """y of the recurrence above, for every position.

    x: (B, S, H, P) inputs by head; dt: (B, S, H) float32 step sizes,
    positive (after the softplus); a: (H,) float32, negative; b, c:
    (B, S, N), one group for all heads, in x's dtype. Returns
    (B, S, H, P) in x's dtype. ``chunk`` must divide S (a shorter
    sequence is one chunk). The skip term D x is the caller's.
    """
    s = x.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"the sequence ({s}) is no multiple of the "
                         f"scan's chunk ({chunk})")
    return _ssd(x, dt.astype(_F32), a.astype(_F32), b, c, chunk)


def describe(chunk: int, heads: int, head_dim: int, state: int) -> str:
    """What a run logs of the scan it built (`lm_train`'s start line):
    the sizes, and which form scans of them take here."""
    return (f"ssd chunk {chunk}, {heads} heads x {head_dim} x state {state}"
            f" ({_path(chunk, heads, head_dim, state)[0]})")
