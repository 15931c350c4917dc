"""Fused causal attention: Pallas TPU forward AND backward kernels.

The hot op of the transformer path, written for the hardware instead of
leaving the S^2 score tensor to XLA: the kernel streams K/V blocks
through VMEM against a resident Q block, keeping the online-softmax
running (max, denominator) in registers/VMEM — scores never exist in HBM
at any size, and the two matmuls per block land on the MXU with fp32
accumulation. Causal skip: K/V blocks entirely in a Q block's future are
never read (the standard flash-attention trick, halving the work).

Backward: the flash recipe (Dao et al.) with the saved log-sum-exp and
delta = rowsum(dO * O), as two Pallas kernels — dK/dV (KV block
resident, Q streamed) and dQ (Q block resident, KV streamed) — with the
causal block skip in both directions. `_bwd_blockwise`, the plain-XLA
scan version, is kept as the off-TPU path and the reference oracle for
the kernel parity tests. What the three kernels cost on the chip, cell
by cell, is in PERF.md §5.

What one score block pair costs inside the kernels:

- Operands reach the MXU in the dtype they arrive in (bf16 under
  `--bf16`, float32 in the CPU parity tests): no block of Q, K, V or dO
  is upcast, `p` and `ds` are cast to the value dtype at the product's
  input, every product accumulates in float32, and the softmax's
  statistics, `exp`, `lse`, the row term and the accumulators are
  float32. The scale multiplies the float32 scores, never `q`.
- No transpose: QK^T and dO·V^T contract the last dimensions of both
  operands (the MXU's native A·B^T); the dK/dV kernel computes the
  transposed scores K·Q^T directly, against `lse` and the row term laid
  along the lanes, so p^T·dO and ds^T·Q are plain products.
- The causal mask only where it cuts: pairs wholly below the diagonal
  run a body with no iota, compare or select; non-causal calls run that
  body alone.
- With equal blocks the one pair the diagonal crosses is worked as 2x2
  sub-blocks (`_diag_sub`) and the sub-block in the future is skipped.
- State sits in float32 VMEM scratch, whole vector registers wide, and
  the loops carry nothing: the accumulators, and in the forward the
  running max (the same in every lane) and the sum of exponentials
  (lane-partial sums, reduced across lanes once a program).

Layout contract: q (B, S, H, D), k (B, S, KV, D) and v (B, S, KV, Dv)
in, (B, S, H, Dv) out (the transformer's native layout; the kernel grid
works on (B*H, S, D) views); dV and dO are at the value size too. KV
divides H and query head h reads key/value head h // (H / KV) by its
block index (`_Heads`), so grouped-query callers hand the key/value
heads over as they project them, unrepeated, and get dK and dV back at
KV heads: summed over each group inside the dK/dV kernel where a whole
sequence's accumulators fit its VMEM (`_bwd_dkdv_group_kernel`), by one
XLA sum of the kernel's slabs where not. Head-major views, not column
blocks of (B, S, H*D): XLA's TPU tiles of (B, S, H, D) are over (H, D),
so that view is no bitcast and costs a copy an operand (PERF.md §6,
PR 56). On non-TPU
backends both directions dispatch to compiled XLA blockwise paths
(`_fwd_blockwise` / `_bwd_blockwise`) — interpret-mode Pallas is orders
of magnitude slower and would throttle the CPU elastic/multipod worlds.
The parity tests force the kernels through the same public API via
`force_interpret_kernels()`.

No reference counterpart (its models are CNNs + served ERNIE); this is
the tpu-first half of the long-context story, composing with
parallel/ring_attention.py which shards S over the mesh and calls a
per-shard attention on each block pair.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.ops.flash_attention")

_NEG_INF = -1e30
# what the dK/dV kernel that sums over a group asks for: its blocks are
# whole sequences and pass the default 16 MiB (`ops/ssm_stages._VMEM`)
_GROUP_VMEM = 64 << 20


class _Heads(NamedTuple):
    """Where a program (bh, i) of the three kernels finds its heads in
    the head-major views the kernels take, (B*N, S, D) of a (B, S, N, D)
    operand: query head bh of the H a batch row has, and its key/value
    head bh // group of the H / group, so that no key or value is
    written once a query head."""
    h: int
    group: int

    @classmethod
    def of(cls, q, k) -> "_Heads":
        return cls(q.shape[2], q.shape[2] // k.shape[2])

    @staticmethod
    def view(x):
        b, s, n, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)

    @staticmethod
    def back(x, b: int):
        """A (B*N, S, D) result as (B, S, N, D)."""
        return x.reshape(b, -1, *x.shape[1:]).transpose(0, 2, 1, 3)

    def spec(self, rows: int, d: int, *, kv: bool = False,
             whole: bool = False):
        """Block i of `rows` of the sequence (`whole`: all of it) of the
        program's query head, or (`kv`) of its key/value head."""
        group = self.group if kv else 1
        return pl.BlockSpec((1, rows, d), lambda bh, i: (
            bh // group if group > 1 else bh, 0 if whole else i, 0))

    def sums_in_kernel(self, s: int, d: int, dv: int, itemsize: int) -> bool:
        """Whether the dK/dV kernel sums over a group itself
        (`_bwd_dkdv_group_kernel`): where a head's whole sequence of K,
        V, Q and dO, double-buffered, dK and dV and their two float32
        accumulators fit the VMEM a call may ask for (33.5 MB of
        `_GROUP_VMEM` at 8,192 positions, heads of 128, bfloat16). A
        longer or wider head leaves the kernel a query head, in slabs
        (`slab_spec`), for one XLA sum."""
        lanes = -(-d // 128) * 128 + -(-dv // 128) * 128
        return self.group > 1 and (
            s * lanes * (6 * itemsize + 4) <= _GROUP_VMEM * 5 // 8)

    def group_spec(self, s: int, d: int, *, kv: bool = False):
        """The whole sequence of the query head, or (`kv`) of the
        key/value head, of program (b * KV + kv head, place in group)."""
        group = self.group
        if kv:
            return pl.BlockSpec((1, s, d), lambda bk, place: (bk, 0, 0))
        return pl.BlockSpec(
            (1, s, d), lambda bk, place: (bk * group + place, 0, 0))

    # Where the sum is XLA's, dK and dV come out a query head, each in
    # the slab of its place in its group, (group, B*KV, S, D), so that
    # the sum over a group is a sum of slabs and moves nothing; equal
    # heads have no slabs
    def slab_spec(self, rows: int, d: int):
        """`spec` for dK or dV: the program's block at its key/value
        head, in the slab of its query head's place in the group."""
        group = self.group
        if group == 1:
            return self.spec(rows, d)
        return pl.BlockSpec((None, 1, rows, d),
                            lambda bh, i: (bh % group, bh // group, i, 0))

    def slab_shape(self, b: int, s: int, d: int, dtype):
        heads = (b * self.h // self.group, s, d)
        return jax.ShapeDtypeStruct(
            heads if self.group == 1 else (self.group, *heads), dtype)

    def slab_sum(self, x, b: int):
        """dK or dV laid as `slab_shape` says, summed over each
        key/value head's query heads in float32: (B, S, KV, D)."""
        if self.group > 1:
            x = x.astype(jnp.float32).sum(0).astype(x.dtype)
        return self.back(x, b)


def _group_sum(x, kv: int):
    """The XLA paths' dK or dV a query head, (B, S, H, D) float32,
    summed over each key/value head's group: (B, S, KV, D)."""
    b, s, h, d = x.shape
    return x if h == kv else x.reshape(b, s, kv, h // kv, d).sum(3)


def _repeated(x, h: int):
    """The XLA paths' key/value heads, one a query head."""
    group = h // x.shape[2]
    return x if group == 1 else jnp.repeat(x, group, axis=2)


def _dot(a, b):
    """a·b on the MXU, operands as they are, float32 out."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a·b^T by contracting the last dimension of both: the MXU's
    native transposed-rhs form, no transpose issued."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _visible(shape, ahead, *, q_minor: bool = False):
    """Causal mask of one score piece whose first query sits `ahead`
    positions after its first key: query i sees key j iff
    i + ahead >= j. The piece is (queries, keys), or (keys, queries)
    with the queries along the lanes (`q_minor`: the dK/dV kernel's
    transposed scores)."""
    row = lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    return col + ahead >= row if q_minor else row + ahead >= col


# which of the two edges of a sliding window's band cut a piece
_CAUSAL, _BAND, _BOTH = "causal", "band", "both"


def _in_band(shape, ahead, window: int, cut: str, *, q_minor: bool = False):
    """`_visible` under a window: query i sees key j iff
    0 <= i + ahead - j < window. `cut` says which of the two bounds the
    piece can break, so that the other is not computed: `_CAUSAL` (the
    diagonal's), `_BAND` (the window's far edge) or `_BOTH`."""
    if cut == _CAUSAL:
        return _visible(shape, ahead, q_minor=q_minor)
    row = lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    back = col + ahead - row if q_minor else row + ahead - col
    if cut == _BAND:
        return back < window
    return (back >= 0) & (back < window)


def _sub_cut(i: int, j: int, sub: int, window: int) -> str | None:
    """How the window cuts sub-block (query i, key j <= i) of the
    diagonal pair: queries sit (i - j) * sub - (sub - 1) to
    (i - j) * sub + (sub - 1) positions after keys. "" = not at all
    (unmasked), None = no key of it is visible."""
    near, far = (i - j) * sub - (sub - 1), (i - j) * sub + (sub - 1)
    if max(near, 0) >= window:
        return None
    band = far >= window
    if i == j:
        return _BOTH if band else _CAUSAL
    return _BAND if band else ""


def _rows(i: int, n: int) -> slice:
    return slice(i * n, (i + 1) * n)


# Visibility by block index (`blocks`, static: (size, strict)): with
# b(i) = i // size, query i sees key j iff b(j) <= b(i), or b(j) < b(i)
# where `strict`. The geometry of training by diffusion over blocks: a
# clean row's queries see the blocks up to and including their own, a
# noised row's queries the clean row's blocks before their own.
def _block_of(x, size: int):
    """x // size for int32 x >= 0: a shift where size is a power of two."""
    if size & (size - 1) == 0:
        return x >> (size.bit_length() - 1)
    return lax.div(x, jnp.int32(size))


def _edge(r, blocks):
    """The first key that query r does not see."""
    size, strict = blocks
    return (_block_of(r, size) + (0 if strict else 1)) * size


def _first_query(j, blocks):
    """The first query that sees key j."""
    size, strict = blocks
    return (_block_of(j, size) + (1 if strict else 0)) * size


def _by_block(shape, q0, k0, blocks, *, q_minor: bool = False):
    """The mask of one score piece whose first query is at q0 and whose
    first key is at k0, (queries, keys) or, `q_minor`, (keys, queries)."""
    size, strict = blocks
    n_q, n_k = (shape[1], shape[0]) if q_minor else shape
    qb = _block_of(lax.broadcasted_iota(
        jnp.int32, (1, n_q) if q_minor else (n_q, 1), 1 if q_minor else 0)
        + q0, size)
    kb = _block_of(lax.broadcasted_iota(
        jnp.int32, (n_k, 1) if q_minor else (1, n_k), 0 if q_minor else 1)
        + k0, size)
    return kb < qb if strict else kb <= qb


def _mask(shape, ahead, window, cut, blocks, *, q_minor: bool = False):
    """The mask of a piece that `ahead` says is cut: under `blocks`
    `ahead` is (first query, first key), else the distance between
    them (`_in_band`)."""
    if blocks is not None:
        return _by_block(shape, *ahead, blocks, q_minor=q_minor)
    return _in_band(shape, ahead, window, cut, q_minor=q_minor)


def _lane_fold(x, width: int):
    """Sum of the `width`-lane column slabs of x: a row sum's
    elementwise part, with the reduction across lanes left for later."""
    out = x[:, :width]
    for c in range(1, x.shape[1] // width):
        out = out + x[:, c * width:(c + 1) * width]
    return out


def _lane_tile(x, n: int):
    """x (rows, W), the same in every lane, as (rows, n): for n a
    multiple of W whole registers side by side, so nothing moves."""
    width = x.shape[1]
    if n <= width:
        return x[:, :n]
    if n % width:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.concatenate([x] * (n // width), axis=1)


def _over_keys(pair, qi, *, blk_q: int, blk_k: int, n_k: int, sub: int,
               causal: bool, window: int | None = None,
               blocks: tuple | None = None) -> None:
    """What the forward and the dQ kernel share: `pair(rows, k_at,
    ahead)` for every piece of keys that q block `qi` sees, in order —
    `rows` the block's query rows, `k_at` the keys' place in the
    sequence, `ahead` None where every key is visible (no mask), `cut`
    which edge of the band the mask is for (`_in_band`)."""

    def block(ki, ahead, cut=_CAUSAL):
        pair(slice(None), pl.ds(ki * blk_k, blk_k), ahead, cut)

    if window is not None:
        _over_keys_in_band(pair, block, qi, blk_q=blk_q, blk_k=blk_k,
                           sub=sub, window=window)
        return
    if blocks is not None:
        # kv blocks every query of this q block sees whole, unmasked as
        # below the diagonal; then the pair(s) the blocks' staircase
        # crosses, under its mask
        q0 = qi * blk_q
        n_full = jnp.minimum(lax.div(_edge(q0, blocks), blk_k), n_k)
        lax.fori_loop(0, n_full, lambda ki, _: block(ki, None), None)
        if sub and sub % blocks[0] == 0:
            # the staircase stays inside the diagonal sub-blocks
            for i in range(blk_q // sub):
                for j in range(i + 1):
                    k0 = qi * blk_k + j * sub
                    pair(_rows(i, sub), pl.ds(k0, sub),
                         (q0 + i * sub, k0) if i == j else None)
        else:
            n_seen = jnp.minimum(lax.div(
                _edge(q0 + (blk_q - 1), blocks) + (blk_k - 1), blk_k), n_k)
            lax.fori_loop(n_full, n_seen,
                          lambda ki, _: block(ki, (q0, ki * blk_k)), None)
        return
    if not causal:
        lax.fori_loop(0, n_k, lambda ki, _: block(ki, None), None)
        return
    # kv blocks wholly at or before this q block's first row, then the
    # ones the diagonal crosses; later ones never contribute
    n_full = lax.div(qi * blk_q + 1, blk_k)
    lax.fori_loop(0, n_full, lambda ki, _: block(ki, None), None)
    if sub:
        # blk_q == blk_k: kv block `qi` as sub-blocks; query sub-block i
        # sees key sub-blocks j < i whole, j == i under the mask, j > i
        # not at all
        for i in range(blk_q // sub):
            for j in range(i + 1):
                pair(_rows(i, sub), pl.ds(qi * blk_k + j * sub, sub),
                     0 if i == j else None)
    else:
        lax.fori_loop(
            n_full, lax.div((qi + 1) * blk_q + blk_k - 1, blk_k),
            lambda ki, _: block(ki, qi * blk_q - ki * blk_k), None)


def _over_keys_in_band(pair, block, qi, *, blk_q: int, blk_k: int, sub: int,
                       window: int) -> None:
    """`_over_keys` under a sliding window: of the kv blocks at or
    before q block `qi`, only those that hold a key some query of the
    block sees. In order: the whole pairs inside the band, unmasked;
    the pair(s) the diagonal crosses, as without a window (a window
    narrower than two blocks cuts those too); then the pair(s) the
    band's far edge crosses, under its mask — last, so that a row whose
    keys there are all too old already has its running max."""
    q0 = qi * blk_q
    q1 = q0 + (blk_q - 1)
    # kv blocks whose last key the block's first query still sees; from
    # `near` on, every key is within the window of the last query too
    first = lax.div(jnp.maximum(q0 - (window - 1), 0), blk_k)
    near = lax.div(jnp.maximum(q1 - window + blk_k, 0), blk_k)
    n_full = lax.div(q0 + 1, blk_k)  # wholly at or before the first row
    lax.fori_loop(jnp.minimum(near, n_full), n_full,
                  lambda ki, _: block(ki, None), None)
    if sub:
        for i in range(blk_q // sub):
            for j in range(i + 1):
                cut = _sub_cut(i, j, sub, window)
                if cut is not None:
                    pair(_rows(i, sub), pl.ds(qi * blk_k + j * sub, sub),
                         (i - j) * sub if cut else None, cut)
    else:
        # a diagonal block's first key is less than blk_q + blk_k - 1
        # positions before the block's last query
        cut = _BOTH if window < blk_q + blk_k - 1 else _CAUSAL
        lax.fori_loop(
            n_full, lax.div((qi + 1) * blk_q + blk_k - 1, blk_k),
            lambda ki, _: block(ki, q0 - ki * blk_k, cut), None)
    lax.fori_loop(first, jnp.minimum(near, n_full),
                  lambda ki, _: block(ki, q0 - ki * blk_k, _BAND), None)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, blk_k: int, sub: int, scale: float, causal: bool,
                window: int | None = None, blocks: tuple | None = None):
    """One (batch*head, q-block) program: stream K/V blocks online.

    q_ref: (1, BLK_Q, D); k_ref: (1, S, D); v_ref: (1, S, Dv); o_ref:
    (1, BLK_Q, Dv); lse_ref: (1, BLK_Q, 1) log-sum-exp for the backward
    (trailing 1 dim: TPU block shapes need the last dims tileable-or-
    full). Scratch, all float32: acc_ref (BLK_Q, Dv) the
    output's accumulator; m_ref (BLK_Q, W) the running max, the same in
    every lane (a (BLK_Q, 1) column here cost the kernel a third more
    time on the chip: PERF.md §6, PR 32); l_ref (BLK_Q, W) the sum of
    exponentials as W lane-partial sums, reduced across lanes once, at
    the end — one reduction through the XLU a pair (the row max), not
    two. `sub`: side of the diagonal pair's sub-blocks (`_diag_sub`), 0
    where the blocks differ and the pairs the diagonal crosses are
    masked whole.
    """
    blk_q, d = q_ref.shape[1], acc_ref.shape[1]
    width = m_ref.shape[1]

    def pair(rows, k_at, ahead, cut=_CAUSAL):
        """Online-softmax step of query rows `rows` of this block over
        the keys at `k_at`; `ahead` None: every key visible, no mask."""
        sblk = _dot_nt(q_ref[0, rows, :], k_ref[0, k_at, :]) * scale
        if ahead is not None:
            sblk = jnp.where(_mask(sblk.shape, ahead, window, cut, blocks),
                             sblk, _NEG_INF)
        m = m_ref[rows, :]
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1, keepdims=True))
        p = jnp.exp(sblk - _lane_tile(m_new, sblk.shape[1]))
        corr = jnp.exp(m - m_new)
        m_ref[rows, :] = m_new
        l_ref[rows, :] = l_ref[rows, :] * corr + _lane_fold(p, width)
        v_blk = v_ref[0, k_at, :]
        acc_ref[rows, :] = (acc_ref[rows, :] * _lane_tile(corr, d)
                            + _dot(p.astype(v_blk.dtype), v_blk))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    _over_keys(pair, pl.program_id(1), blk_q=blk_q, blk_k=blk_k,
               n_k=k_ref.shape[1] // blk_k, sub=sub, causal=causal,
               window=window, blocks=blocks)
    l = jnp.maximum(jnp.sum(l_ref[...], axis=-1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_ref[:, :1] + jnp.log(l)


# jitted so that a model's layers, which call this with one set of shapes,
# share one trace and one lowering of the kernel: traced a layer each, the
# three kernels added seconds to a trainer's start (PERF.md §6, PR 32)
@functools.partial(jax.jit, static_argnames=(
    "blk_q", "blk_k", "scale", "causal", "interpret", "window", "blocks"))
def _fwd(q, k, v, *, blk_q: int, blk_k: int, scale: float, causal: bool,
         interpret: bool, window: int | None = None,
         blocks: tuple | None = None):
    b, s, h, d = q.shape
    dv = v.shape[-1]
    at = _Heads.of(q, k)
    grid = (b * h, s // blk_q)
    sub = _diag_sub(blk_q, blk_k)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, blk_k=blk_k, sub=sub, scale=scale,
                          causal=causal, window=window,
                          blocks=blocks),
        grid=grid,
        in_specs=[
            at.spec(blk_q, d),
            at.spec(s, d, kv=True, whole=True),
            at.spec(s, dv, kv=True, whole=True),
        ],
        out_specs=[
            at.spec(blk_q, dv),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, dv), jnp.float32)]
        + [pltpu.VMEM((blk_q, _stat_width(blk_k, sub)), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_fwd",
    )(at.view(q), at.view(k), at.view(v))
    return at.back(o, b), lse[..., 0]


def _seen(q_pos, kv_pos, window: int | None, blocks: tuple | None = None):
    """The XLA paths' mask: causal, and under a window the `window`
    newest keys only; under `blocks` by block index."""
    if blocks is not None:
        size, strict = blocks
        qb, kb = q_pos // size, kv_pos // size
        return kb < qb if strict else kb <= qb
    mask = q_pos >= kv_pos
    return mask if window is None else mask & (q_pos - kv_pos < window)


def _fwd_blockwise(q, k, v, *, blk: int, scale: float, causal: bool,
                   window: int | None = None, blocks: tuple | None = None):
    """Flash forward in plain XLA (KV-block scan with the online
    softmax) — the off-TPU fallback. Returns (o, lse) exactly as `_fwd`
    does: o (B,S,H,Dv) in q.dtype, lse (B*H, S) fp32."""
    b, s, h, _ = q.shape
    q32 = q.astype(jnp.float32)
    k32 = _repeated(k, h).astype(jnp.float32)
    v32 = _repeated(v, h).astype(jnp.float32)
    q_pos = jnp.arange(s)

    def kv_step(carry, ki):
        m, l, acc = carry  # (B,H,S), (B,H,S), (B,S,H,Dv)
        ksl = lax.dynamic_slice_in_dim(k32, ki * blk, blk, axis=1)
        vsl = lax.dynamic_slice_in_dim(v32, ki * blk, blk, axis=1)
        sblk = jnp.einsum("bqhd,bkhd->bhqk", q32, ksl,
                          preferred_element_type=jnp.float32) * scale
        if causal:
            kv_pos = ki * blk + jnp.arange(blk)
            mask = _seen(q_pos[:, None], kv_pos[None, :], window, blocks)
            sblk = jnp.where(mask[None, None], sblk, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1))
        p = jnp.exp(sblk - m_new[..., None])
        corr = jnp.exp(m - m_new)  # (B,H,S)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = (acc * corr.transpose(0, 2, 1)[..., None]
               + jnp.einsum("bhqk,bkhd->bqhd", p, vsl,
                            preferred_element_type=jnp.float32))
        return (m_new, l, acc), None

    init = (jnp.full((b, h, s), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, s), jnp.float32),
            jnp.zeros((b, s, h, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = lax.scan(kv_step, init, jnp.arange(s // blk))
    l = jnp.maximum(l, 1e-30)  # same guard as the kernel
    o = (acc / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    lse = (m + jnp.log(l)).reshape(b * h, s)
    return o, lse


def _dkdv_block(ki, at, q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref,
                dk_acc, dv_acc, *, blk_k: int, sub: int, scale: float,
                causal: bool, window: int | None, blocks: tuple | None):
    """dK and dV of kv block `ki` for one query head, added into the
    float32 accumulators: stream the Q blocks (causal: only blocks that
    can see this KV block). Works on the transposed scores K·Q^T, so no
    piece is transposed for p^T·dO and ds^T·Q. `at(start, size)` says
    where rows start .. start + size of the block lie in `k_ref`,
    `v_ref` and the accumulators.

    q_ref: (1, S, D); do_ref: (1, S, Dv);
    lse_ref/rt_ref: (1, S/BLK_Q, 1, BLK_Q) fp32, a q block's values
    along the lanes (picked by an index of an untiled dimension: a
    dynamic row of a tile does not compile at every width) — lse from
    the forward; rt is the row term delta - dlse (delta =
    rowsum(dO*O)), precomputed in XLA so one kernel serves both the
    plain and the lse-cotangent vjp. `sub` as in `_fwd_kernel`.
    """
    _, n_q, _, blk_q = lse_ref.shape
    to = v_ref.dtype

    def pair(keys, q_at, qi, lanes, ahead, cut=_CAUSAL):
        """Key rows `keys` of this block against the queries at `q_at`:
        lanes `lanes` of q block `qi`'s lse/rt rows."""
        q, do = q_ref[0, q_at, :], do_ref[0, q_at, :]
        pt = jnp.exp(_dot_nt(k_ref[0, keys, :], q) * scale
                     - lse_ref[0, qi, :, lanes])
        if ahead is not None:
            pt = jnp.where(_mask(pt.shape, ahead, window, cut, blocks,
                                 q_minor=True), pt, 0.0)
        dv_acc[keys, :] += _dot(pt.astype(to), do)
        dst = pt * (_dot_nt(v_ref[0, keys, :], do) - rt_ref[0, qi, :, lanes])
        dk_acc[keys, :] += _dot(dst.astype(to), q)  # x scale: at the end

    def block(qi, ahead, cut=_CAUSAL):
        pair(at(0, blk_k), pl.ds(qi * blk_q, blk_q), qi, slice(None), ahead,
             cut)

    first_full = 0
    if window is not None:
        # the forward's walk mirrored over the q blocks: the pair(s) the
        # diagonal crosses, the whole pairs inside the band, the pair(s)
        # its far edge crosses; later q blocks see no key of this block
        k0 = ki * blk_k
        k1 = k0 + (blk_k - 1)
        first_full = lax.div(k1 + blk_q - 1, blk_q)
        # q blocks from `far` on have a query that no longer sees this
        # block's first key; from `end` on, none that sees its last
        far = jnp.minimum(lax.div(k0 + window + blk_q, blk_q) - 1, n_q)
        end = jnp.minimum(lax.div(k1 + window + blk_q - 1, blk_q), n_q)
        if sub:
            for j in range(blk_k // sub):
                for i in range(j, blk_k // sub):
                    cut = _sub_cut(i, j, sub, window)
                    if cut is not None:
                        pair(at(j * sub, sub),
                             pl.ds(ki * blk_q + i * sub, sub),
                             ki, _rows(i, sub),
                             (i - j) * sub if cut else None, cut)
        else:
            cut = _BOTH if window < blk_q + blk_k - 1 else _CAUSAL
            lax.fori_loop(
                lax.div(k0, blk_q), first_full,
                lambda qi, _: block(qi, qi * blk_q - k0, cut), None)
        lax.fori_loop(first_full, jnp.maximum(first_full, far),
                      lambda qi, _: block(qi, None), None)
        lax.fori_loop(jnp.maximum(first_full, far), end,
                      lambda qi, _: block(qi, qi * blk_q - k0, _BAND), None)
        return
    if blocks is not None:
        # the forward's walk mirrored: the q block(s) the staircase
        # crosses (from the first with a query that sees a key of this
        # block), then the ones whose every query sees all of it
        k0 = ki * blk_k
        first_full = jnp.minimum(lax.div(
            _first_query(k0 + (blk_k - 1), blocks) + (blk_q - 1), blk_q), n_q)
        if sub and sub % blocks[0] == 0:
            for j in range(blk_k // sub):
                for i in range(j, blk_k // sub):
                    q0 = ki * blk_q + i * sub
                    pair(at(j * sub, sub), pl.ds(q0, sub), ki, _rows(i, sub),
                         (q0, k0 + j * sub) if i == j else None)
        else:
            first_seen = jnp.minimum(
                lax.div(_first_query(k0, blocks), blk_q), n_q)
            lax.fori_loop(first_seen, first_full,
                          lambda qi, _: block(qi, (qi * blk_q, k0)), None)
    elif causal:
        # q blocks the diagonal crosses (from the first that can see any
        # row of this kv block), then the ones that see all of it
        first_full = lax.div((ki + 1) * blk_k + blk_q - 2, blk_q)
        if sub:
            # blk_q == blk_k: q block `ki` as sub-blocks; key sub-block j
            # is seen by query sub-blocks i > j whole, i == j masked
            for j in range(blk_k // sub):
                for i in range(j, blk_k // sub):
                    pair(at(j * sub, sub), pl.ds(ki * blk_q + i * sub, sub),
                         ki, _rows(i, sub), 0 if i == j else None)
        else:
            lax.fori_loop(
                lax.div(ki * blk_k, blk_q), first_full,
                lambda qi, _: block(qi, qi * blk_q - ki * blk_k), None)
    lax.fori_loop(first_full, n_q, lambda qi, _: block(qi, None), None)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, sub: int,
                     scale: float, **mask):
    """One (batch*head, kv-block) program: K/V block resident,
    accumulate dK/dV in fp32 VMEM scratch (dk_acc: (BLK_K, D), dv_acc:
    (BLK_K, Dv)). k_ref/dk_ref: (1, BLK_K, D); v_ref/dv_ref: (1, BLK_K,
    Dv); the rest as `_dkdv_block` says."""
    ki = pl.program_id(1)
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    _dkdv_block(ki, lambda start, size: slice(start, start + size), q_ref,
                k_ref, v_ref, do_ref, lse_ref, rt_ref, dk_acc, dv_acc,
                blk_k=k_ref.shape[1], sub=sub, scale=scale, **mask)
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dkdv_group_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, blk_k: int,
                           sub: int, scale: float, **mask):
    """`_bwd_dkdv_kernel` where key/value heads serve groups of query
    heads: one (batch*kv head, place in the group) program over the
    whole sequence. K, V, dK, dV (1, S, .) stay resident for the
    group's programs, each of which streams its own query head's Q and
    dO once and adds every kv block's dK/dV into the accumulators,
    (S, D) and (S, Dv) fp32: the sum over the group is made here, and
    written once, by the group's last program."""
    place = pl.program_id(1)

    @pl.when(place == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def kv_block(ki, _):
        base = pl.multiple_of(ki * blk_k, blk_k)
        _dkdv_block(ki, lambda start, size: pl.ds(base + start, size),
                    q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref, dk_acc,
                    dv_acc, blk_k=blk_k, sub=sub, scale=scale, **mask)

    lax.fori_loop(0, k_ref.shape[1] // blk_k, kv_block, None)

    @pl.when(place == pl.num_programs(1) - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref, dq_ref,
                   dq_acc, *, blk_k: int, sub: int, scale: float,
                   causal: bool, window: int | None = None,
                   blocks: tuple | None = None):
    """One (batch*head, q-block) program: Q block resident, stream KV
    blocks (causal skip and diagonal as in the forward), accumulate dQ
    in fp32 VMEM scratch (dq_acc: (BLK_Q, D)); v_ref: (1, S, Dv),
    do_ref: (1, BLK_Q, Dv). lse_ref/rt_ref: (1, BLK_Q, 1) columns."""
    to = v_ref.dtype

    def pair(rows, k_at, ahead, cut=_CAUSAL):
        k_blk = k_ref[0, k_at, :]
        p = jnp.exp(_dot_nt(q_ref[0, rows, :], k_blk) * scale
                    - lse_ref[0, rows, :])
        if ahead is not None:
            p = jnp.where(_mask(p.shape, ahead, window, cut, blocks), p, 0.0)
        ds = p * (_dot_nt(do_ref[0, rows, :], v_ref[0, k_at, :])
                  - rt_ref[0, rows, :])
        dq_acc[rows, :] += _dot(ds.astype(to), k_blk)  # x scale: at the end

    dq_acc[...] = jnp.zeros_like(dq_acc)
    _over_keys(pair, pl.program_id(1), blk_q=q_ref.shape[1], blk_k=blk_k,
               n_k=k_ref.shape[1] // blk_k, sub=sub, causal=causal,
               window=window, blocks=blocks)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "blk_q", "blk_k", "scale", "causal", "interpret", "window", "blocks"))
def _bwd_pallas(q, k, v, o, lse, do, *, blk_q: int, blk_k: int,
                scale: float, causal: bool, dlse, interpret: bool,
                window: int | None = None, blocks: tuple | None = None):
    """Pallas flash backward: same math as `_bwd_blockwise` (the XLA
    reference used by the parity tests) but with scores recomputed in
    VMEM — nothing S^2-shaped touches HBM — and the causal block skip
    in BOTH directions (the XLA scan masks instead of skipping, doing
    2x the needed work)."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    at = _Heads.of(q, k)
    qt, kt, vt, dot = at.view(q), at.view(k), at.view(v), at.view(do)
    # row term = delta - dlse, delta_i = rowsum(dO_i * O_i): cheap
    # elementwise XLA; folding it here keeps the kernels single-purpose
    rt = jnp.sum(dot.astype(jnp.float32)
                 * at.view(o).astype(jnp.float32), axis=-1)
    if dlse is not None:
        rt = rt - dlse.astype(jnp.float32)
    sub = _diag_sub(blk_q, blk_k)

    def along_lanes(x):  # a q block's values in one row of lanes
        return x.reshape(b * h, s // blk_q, 1, blk_q)

    mask = dict(sub=sub, scale=scale, causal=causal, window=window,
                blocks=blocks)
    if at.sums_in_kernel(s, d, dv, q.dtype.itemsize):
        kv, group = k.shape[2], at.group
        rows_of = pl.BlockSpec(
            (1, s // blk_q, 1, blk_q),
            lambda bk, place: (bk * group + place, 0, 0, 0))
        dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_dkdv_group_kernel, blk_k=blk_k, **mask),
            grid=(b * kv, group),
            in_specs=[
                at.group_spec(s, d),
                at.group_spec(s, d, kv=True),
                at.group_spec(s, dv, kv=True),
                at.group_spec(s, dv),
                rows_of, rows_of,
            ],
            out_specs=[at.group_spec(s, d, kv=True),
                       at.group_spec(s, dv, kv=True)],
            out_shape=[jax.ShapeDtypeStruct((b * kv, s, d), k.dtype),
                       jax.ShapeDtypeStruct((b * kv, s, dv), v.dtype)],
            scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_GROUP_VMEM),
            interpret=interpret,
            name="flash_bwd_dkdv",
        )(qt, kt, vt, dot, along_lanes(lse), along_lanes(rt))
        join = at.back
    else:
        rows_of = pl.BlockSpec((1, s // blk_q, 1, blk_q),
                               lambda bh, ki: (bh, 0, 0, 0))
        dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, **mask),
            grid=(b * h, s // blk_k),
            in_specs=[
                at.spec(s, d, whole=True),
                at.spec(blk_k, d, kv=True),
                at.spec(blk_k, dv, kv=True),
                at.spec(s, dv, whole=True),
                rows_of, rows_of,
            ],
            out_specs=[at.slab_spec(blk_k, d), at.slab_spec(blk_k, dv)],
            out_shape=[at.slab_shape(b, s, d, k.dtype),
                       at.slab_shape(b, s, dv, v.dtype)],
            scratch_shapes=[pltpu.VMEM((blk_k, d), jnp.float32),
                            pltpu.VMEM((blk_k, dv), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dkdv",
        )(qt, kt, vt, dot, along_lanes(lse), along_lanes(rt))
        join = at.slab_sum
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, blk_k=blk_k, sub=sub,
                          scale=scale, causal=causal, window=window,
                          blocks=blocks),
        grid=(b * h, s // blk_q),
        in_specs=[
            at.spec(blk_q, d),
            at.spec(s, d, kv=True, whole=True),
            at.spec(s, dv, kv=True, whole=True),
            at.spec(blk_q, dv),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=at.spec(blk_q, d),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse[..., None], rt[..., None])
    return at.back(dq, b), join(dk, b), join(dv_, b)


def _bwd_blockwise(q, k, v, o, lse, do, *, blk: int, scale: float,
                   causal: bool, dlse=None, window: int | None = None,
                   blocks: tuple | None = None):
    """Flash backward in plain XLA, scanning KV blocks. q, o, do
    (B,S,H,.); k, v (B,S,KV,.), repeated here a query head each and
    dK, dV summed back over each group.

    With `dlse` (a (B*H, S) cotangent on the log-sum-exp output), the
    score gradient gains the softmax term: d(lse)/d(s_ij) = p_ij, so
    ds += p * dlse_row — this is what lets consumers of (o, lse)
    (the lse-combine in ring attention) differentiate through both.
    """
    b, s, h, d = q.shape
    q32 = q.astype(jnp.float32)
    k32 = _repeated(k, h).astype(jnp.float32)
    v32 = _repeated(v, h).astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    # delta_i = rowsum(dO_i * O_i)  (B,S,H)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)
    lse_b = lse.reshape(b, h, s).transpose(0, 2, 1)  # (B,S,H)
    dlse_bh = (None if dlse is None
               else dlse.reshape(b, h, s).astype(jnp.float32))  # (B,H,S)

    q_pos = jnp.arange(s)

    def kv_step(carry, ki):
        dq_acc = carry
        ksl = lax.dynamic_slice_in_dim(k32, ki * blk, blk, axis=1)
        vsl = lax.dynamic_slice_in_dim(v32, ki * blk, blk, axis=1)
        # scores for ALL q rows vs this kv block: (B,H,S,blk)
        sblk = jnp.einsum("bqhd,bkhd->bhqk", q32, ksl,
                          preferred_element_type=jnp.float32) * scale
        if causal:
            kv_pos = ki * blk + jnp.arange(blk)
            mask = _seen(q_pos[:, None], kv_pos[None, :], window, blocks)
            sblk = jnp.where(mask[None, None], sblk, _NEG_INF)
        p = jnp.exp(sblk - lse_b.transpose(0, 2, 1)[..., None])  # (B,H,S,blk)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, do32,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do32, vsl,
                        preferred_element_type=jnp.float32)
        # dL/ds_ij = p_ij * (dp_ij - delta_i + dlse_i); the trailing
        # *scale converts to the gradient w.r.t. the unscaled q.k
        row_term = delta.transpose(0, 2, 1)[..., None]
        if dlse_bh is not None:
            row_term = row_term - dlse_bh[..., None]
        ds = p * (dp - row_term) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, ksl,
                                     preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32,
                            preferred_element_type=jnp.float32)
        return dq_acc, (dk_blk, dv_blk)

    n_blocks = s // blk
    dq, (dk_blocks, dv_blocks) = lax.scan(
        kv_step, jnp.zeros_like(q32), jnp.arange(n_blocks))
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])
    kv = k.shape[2]
    return (dq.astype(q.dtype), _group_sum(dk, kv).astype(k.dtype),
            _group_sum(dv, kv).astype(v.dtype))


def _fit_block(s: int, want: int) -> int:
    """Largest MXU-friendly block <= want that divides s (128-granular,
    so any 128-divisible sequence works — e.g. S=640 gets 128 blocks)."""
    if want >= s:
        if s % 128 == 0 or s <= 512:
            return s
    for b in (want, 512, 384, 256, 128):
        if b <= want and s % b == 0:
            return b
    raise ValueError(f"sequence {s} not divisible by any block size "
                     f"<= {want} (pad the sequence to a multiple of 128)")


def _diag_sub(blk_q: int, blk_k: int) -> int:
    """Side of the sub-blocks the diagonal block pair is worked in: half
    a block where the blocks are equal (one pair a row of blocks is on
    the diagonal) and the halves are still 128-granular, so the quarter
    of that pair in the future is never computed. 0: the blocks differ,
    or do not halve; the pairs the diagonal crosses are masked whole."""
    return blk_q // 2 if blk_q == blk_k and blk_q % 256 == 0 else 0


def _stat_width(blk_k: int, sub: int) -> int:
    """Lanes of the forward's running max and partial sums of
    exponentials: the 128 of a vector register where every piece of keys
    (a block, a sub-block) is whole slabs of them, else the one piece's
    width."""
    piece = sub or blk_k
    return 128 if piece % 128 == 0 else piece


def block_pairs(s: int, blk_q: int, blk_k: int, causal: bool,
                window: int | None = None, blocks: tuple | None = None
                ) -> str:
    """What each of the three kernels works through for one head, for
    the log: the blocking, and the block pairs that run unmasked, under
    the mask, and not at all."""
    n_q, n_k = s // blk_q, s // blk_k
    if not causal:
        return f"blocks {blk_q}x{blk_k}, pairs a head: {n_q * n_k} full"
    if blocks is not None:
        # as `_over_keys` counts them
        size, strict = blocks

        def edge(r):
            return (r // size + (0 if strict else 1)) * size
        full = sum(min(edge(qi * blk_q) // blk_k, n_k) for qi in range(n_q))
        seen = sum(min(-(-edge((qi + 1) * blk_q - 1) // blk_k), n_k)
                   for qi in range(n_q))
        text = (f"blocks {blk_q}x{blk_k}, keys of "
                f"{'earlier blocks' if strict else 'blocks up to the own'} "
                f"of {size}, pairs a head: ")
        sub = _diag_sub(blk_q, blk_k)
        if sub and sub % size == 0:
            n = blk_q // sub
            return text + (
                f"{full} full, {n_q} on the staircase, "
                f"{n_q * n_k - full - n_q} skipped; a staircase pair as "
                f"{n}x{n} of {sub}: {n * (n - 1) // 2} full, {n} masked, "
                f"{n * (n - 1) // 2} skipped")
        return text + (f"{full} full, {seen - full} on the staircase, "
                       f"{n_q * n_k - seen} skipped, masked whole")
    # as the kernels count them: kv blocks wholly at or before a q
    # block's first row, and those with any column at or before its last
    full = sum((qi * blk_q + 1) // blk_k for qi in range(n_q))
    seen = sum(-(-(qi + 1) * blk_q // blk_k) for qi in range(n_q))
    text = f"blocks {blk_q}x{blk_k}, pairs a head: "
    if window is None:
        text += (f"{full} full, {seen - full} on the diagonal, "
                 f"{n_q * n_k - seen} skipped")
    else:
        # of the pairs before the diagonal: those the window's far edge
        # crosses, and those wholly older than it, as `_over_keys_in_band`
        edge = old = 0
        for qi in range(n_q):
            q0, q1 = qi * blk_q, (qi + 1) * blk_q - 1
            n_full = (q0 + 1) // blk_k
            first = max(q0 - (window - 1), 0) // blk_k
            near = min(max(q1 - window + blk_k, 0) // blk_k, n_full)
            edge, old = edge + near - first, old + first
        text += (f"window {window}: {full - edge - old} full, "
                 f"{seen - full} on the diagonal, {edge} on the window's "
                 f"edge, {n_q * n_k - seen + old} skipped")
    sub = _diag_sub(blk_q, blk_k)
    if sub:
        n = blk_q // sub
        cuts = [_sub_cut(i, j, sub, window) if window is not None
                else ("" if i > j else _CAUSAL)
                for i in range(n) for j in range(i + 1)]
        text += (f"; a diagonal pair as {n}x{n} of {sub}: "
                 f"{cuts.count('')} full, "
                 f"{len(cuts) - cuts.count('') - cuts.count(None)} masked, "
                 f"{n * n - len(cuts) + cuts.count(None)} skipped")
    else:
        text += ", masked whole"
    return text


_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret_kernels():
    """Test hook: run the Pallas kernels (fwd AND bwd) in interpret mode
    even off-TPU — the parity tests compare them against the XLA
    blockwise paths through the public API."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = False


def _kernel_interpret(what: str, q, kv: int, blk_q: int, blk_k: int,
                      causal: bool, window: int | None = None,
                      blocks: tuple | None = None) -> bool | None:
    """Which path this trace takes: the Pallas `interpret` flag (False
    = compiled, on TPU; True = the test hook), or None for the compiled
    XLA blockwise paths — off-TPU, where interpret-mode Pallas is
    orders of magnitude slower and would throttle the CPU
    elastic/multipod worlds. Logged per trace, so a trainer's log says
    which attention its step was built from, in which blocks, and (a
    line of its own, with the forward's) how many key/value heads the
    kernels serve the query heads from."""
    if jax.default_backend() == "tpu":
        mode, interpret = "pallas kernel, compiled", False
    elif _FORCE_INTERPRET:
        mode, interpret = "pallas kernel, interpret mode", True
    else:
        mode, interpret = "xla blockwise", None
    if interpret is not None:
        mode += "; " + block_pairs(q.shape[1], blk_q, blk_k, causal, window,
                                   blocks)
    elif window is not None:
        mode += f", window {window}"
    elif blocks is not None:
        mode += f", by blocks of {blocks[0]}" + ", strictly" * blocks[1]
    log.info("flash attention %s %s: %s", what, tuple(q.shape), mode)
    if interpret is not None and what == "fwd":
        log.info("flash %s kv %d: a key/value head by its index, one to "
                 "%d query heads", tuple(q.shape), kv, q.shape[2] // kv)
    return interpret


def _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal, window, blocks):
    interpret = _kernel_interpret("fwd", q, k.shape[2], blk_q, blk_k,
                                  causal, window, blocks)
    if interpret is None:
        return _fwd_blockwise(q, k, v, blk=blk_k, scale=scale,
                              causal=causal, window=window, blocks=blocks)
    return _fwd(q, k, v, blk_q=blk_q, blk_k=blk_k, scale=scale,
                causal=causal, interpret=interpret, window=window,
                blocks=blocks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, blk_q, blk_k, scale, causal, window, blocks):
    return _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal, window,
                         blocks)


# What a `jax.checkpoint` around a caller may keep of a call, by name
# (`save_only_these_names`): the forward's two results, which are also
# the backward's residuals. Kept, the replay has no use for the forward
# kernel; outside a checkpoint the names lower to nothing.
KEPT_O, KEPT_LSE = "flash_o", "flash_lse"


def _flash_lse_fwd(q, k, v, blk_q, blk_k, scale, causal, window, blocks):
    o, lse = _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal, window,
                           blocks)
    # named before the residuals are formed: the backward reads the
    # named values, and naming the call's result in the caller would
    # name another variable and leave the kernel in the replay
    o, lse = checkpoint_name(o, KEPT_O), checkpoint_name(lse, KEPT_LSE)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(blk_q, blk_k, scale, causal, window, blocks, res,
                   cotangents):
    q, k, v, o, lse = res
    do, dlse = cotangents
    interpret = _kernel_interpret("bwd", q, k.shape[2], blk_q, blk_k,
                                  causal, window, blocks)
    if interpret is None:
        return _bwd_blockwise(q, k, v, o, lse, do, blk=blk_k,
                              scale=scale, causal=causal, dlse=dlse,
                              window=window, blocks=blocks)
    return _bwd_pallas(q, k, v, o, lse, do, blk_q=blk_q, blk_k=blk_k,
                       scale=scale, causal=causal, dlse=dlse,
                       interpret=interpret, window=window, blocks=blocks)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: float | None = None,
                        block_q: int = 512, block_k: int = 512,
                        window: int | None = None,
                        blocks: tuple[int, bool] | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """flash_attention that ALSO returns the per-row log-sum-exp
    ((B, H*... reshaped) -> (B, S, H)) — the combinable statistic for
    composing partial attentions (ring attention's per-block kernel:
    two normalized outputs merge exactly via their lse weights).
    Fully differentiable through both outputs.
    """
    b, s, h, d = q.shape
    if (k.ndim != 4 or v.ndim != 4 or k.shape[:2] != (b, s)
            or k.shape[3] != d or v.shape[:3] != k.shape[:3]
            or h % k.shape[2]):
        raise ValueError(
            f"q/k/v shape mismatch: q {q.shape}, k {k.shape}, v {v.shape}: "
            "q is (B, S, H, D), k is (B, S, KV, D) and v is (B, S, KV, Dv) "
            "with a head size of its own or the same; KV divides H, and "
            "query head h reads key/value head h // (H / KV)")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} counts the keys up to and "
                         "including a query's own: it needs causal=True "
                         "and at least 1")
    if window is not None and window >= s:
        window = None  # no query has that many keys behind it
    if blocks is not None:
        size, strict = blocks
        if not causal or window is not None or size < 1 or s % size:
            raise ValueError(
                f"blocks={blocks} is visibility by block index, a causal "
                f"mask made coarser: it needs causal=True, no window and "
                f"a size that divides the sequence ({s})")
        blocks = (int(size), bool(strict))
    blk_q = _fit_block(s, block_q)
    blk_k = _fit_block(s, block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    o, lse = _flash_lse(q, k, v, blk_q, blk_k, scale, causal, window,
                        blocks)
    return o, lse.reshape(b, h, s).transpose(0, 2, 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512,
                    window: int | None = None,
                    blocks: tuple[int, bool] | None = None) -> jax.Array:
    """Fused causal attention. q: (B, S, H, D); k: (B, S, KV, D); v:
    (B, S, KV, Dv), a head size of its own (latent attention: keys of
    192, values of 128) or the same -> (B, S, H, Dv). KV divides H:
    grouped-query attention hands its key/value heads over unrepeated,
    and dK, dV come back at KV heads. The scale, where not given, is
    from the key size: 1 / sqrt(D). D need not be whole 128-lane
    registers: the products contract it as it is (PERF.md §5).

    `window` (static; None = every earlier key): query i sees keys
    i - window + 1 .. i, and the kernels visit only the block pairs
    that hold such a key (`block_pairs`).

    `blocks` (static; (size, strict)): visibility by block index
    instead, b(i) = i // size: query i sees key j iff b(j) <= b(i), or
    b(j) < b(i) where `strict`. A strict query of the first block sees
    no key: its output is no attention at all (a mean of values), and
    its `lse` is `_NEG_INF`, so that a merge by `lse` with a part that
    saw a key gives it the weight 0 exactly, forward and backward.

    Blocks auto-fit any 128-divisible sequence (pad upstream otherwise —
    the transformer's static max_len already guarantees this). One
    custom_vjp serves this and `flash_attention_lse`: the unused lse
    output's cotangent is zero, which `_bwd_blockwise` folds away.
    """
    return flash_attention_lse(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               window=window, blocks=blocks)[0]
