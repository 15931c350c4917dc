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
scan version, is kept as the reference oracle for the kernel parity
tests; profiling showed it at ~29% of LM step time for ~6% of model
FLOPs (it masks instead of skipping and round-trips fp32 score tensors
through HBM), which is what motivated the kernels.

Layout contract: (B, S, H, D) in, (B, S, H, D) out (the transformer's
native layout; the kernel grid works on (B*H, S, D) views). On non-TPU
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

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.ops.flash_attention")

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk_k: int,
                scale: float, causal: bool):
    """One (batch*head, q-block) program: stream K/V blocks online.

    q_ref: (1, BLK_Q, D); k_ref/v_ref: (1, S, D); o_ref: (1, BLK_Q, D);
    lse_ref: (1, BLK_Q, 1) log-sum-exp for the backward (trailing 1 dim:
    TPU block shapes need the last dims tileable-or-full).
    """
    _, blk_q, d = q_ref.shape
    s = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    def body(ki, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
        sblk = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            kv_pos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (1, blk_k), 1)
            sblk = jnp.where(q_pos >= kv_pos, sblk, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1, keepdims=True))
        p = jnp.exp(sblk - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o = o * corr + jnp.dot(p, v_blk,
                               preferred_element_type=jnp.float32)
        return o, m_new, l

    o0 = jnp.zeros((blk_q, d), jnp.float32)
    m0 = jnp.full((blk_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    if causal:
        # blocks strictly after this q block never contribute
        n_blocks = lax.div((qi + 1) * blk_q + blk_k - 1, blk_k)
    else:
        n_blocks = s // blk_k
    o, m, l = lax.fori_loop(0, n_blocks, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _fwd(q, k, v, *, blk_q: int, blk_k: int, scale: float, causal: bool,
         interpret: bool):
    b, s, h, d = q.shape
    # (B, S, H, D) -> (B*H, S, D) program-per-head views
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    grid = (b * h, s // blk_q)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, blk_k=blk_k, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return o, lse[..., 0]


def _fwd_blockwise(q, k, v, *, blk: int, scale: float, causal: bool):
    """Flash forward in plain XLA (KV-block scan with the online
    softmax) — the off-TPU fallback. Returns (o, lse) exactly as `_fwd`
    does: o (B,S,H,D) in q.dtype, lse (B*H, S) fp32."""
    b, s, h, d = q.shape
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    q_pos = jnp.arange(s)

    def kv_step(carry, ki):
        m, l, acc = carry  # (B,H,S), (B,H,S), (B,S,H,D)
        ksl = lax.dynamic_slice_in_dim(k32, ki * blk, blk, axis=1)
        vsl = lax.dynamic_slice_in_dim(v32, ki * blk, blk, axis=1)
        sblk = jnp.einsum("bqhd,bkhd->bhqk", q32, ksl,
                          preferred_element_type=jnp.float32) * scale
        if causal:
            kv_pos = ki * blk + jnp.arange(blk)
            mask = q_pos[:, None] >= kv_pos[None, :]
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
            jnp.zeros((b, s, h, d), jnp.float32))
    (m, l, acc), _ = lax.scan(kv_step, init, jnp.arange(s // blk))
    l = jnp.maximum(l, 1e-30)  # same guard as the kernel
    o = (acc / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    lse = (m + jnp.log(l)).reshape(b * h, s)
    return o, lse


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref,
                     dk_ref, dv_ref, *, blk_q: int, scale: float,
                     causal: bool):
    """One (batch*head, kv-block) program: K/V block resident, stream Q
    blocks (causal: only blocks that can see this KV block), accumulate
    dK/dV in fp32 VMEM.

    q_ref/do_ref: (1, S, D); k_ref/v_ref/dk_ref/dv_ref: (1, BLK_K, D);
    lse_ref/rt_ref: (1, S, 1) fp32 — lse from the forward; rt is the
    row term delta - dlse (delta = rowsum(dO*O)), precomputed in XLA so
    one kernel serves both the plain and the lse-cotangent vjp.
    """
    _, blk_k, d = k_ref.shape
    s = q_ref.shape[1]
    ki = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    kv_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * blk_q, blk_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * blk_q, blk_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * blk_q, blk_q), :]
        rt = rt_ref[0, pl.ds(qi * blk_q, blk_q), :]
        sblk = jnp.dot(q, k_blk.T,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(sblk - lse)  # (blk_q, blk_k)
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(
                jnp.int32, (blk_q, 1), 0)
            p = jnp.where(q_pos >= kv_pos, p, 0.0)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - rt) * scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # the first q block that can see any row of this kv block
        q_start = lax.div(ki * blk_k, blk_q)
    else:
        q_start = 0
    zeros = jnp.zeros((blk_k, d), jnp.float32)
    dk, dv = lax.fori_loop(q_start, s // blk_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, rt_ref, dq_ref,
                   *, blk_k: int, scale: float, causal: bool):
    """One (batch*head, q-block) program: Q block resident, stream KV
    blocks (causal skip as in the forward), accumulate dQ."""
    _, blk_q, d = q_ref.shape
    s = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    rt = rt_ref[0]
    q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, 1), 0)

    def body(ki, dq):
        k_blk = k_ref[0, pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
        sblk = jnp.dot(q, k_blk.T,
                       preferred_element_type=jnp.float32) * scale
        p = jnp.exp(sblk - lse)
        if causal:
            kv_pos = ki * blk_k + lax.broadcasted_iota(
                jnp.int32, (1, blk_k), 1)
            p = jnp.where(q_pos >= kv_pos, p, 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - rt) * scale
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    if causal:
        n_blocks = lax.div((qi + 1) * blk_q + blk_k - 1, blk_k)
    else:
        n_blocks = s // blk_k
    dq = lax.fori_loop(0, n_blocks, body,
                       jnp.zeros((blk_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, *, blk_q: int, blk_k: int,
                scale: float, causal: bool, dlse, interpret: bool):
    """Pallas flash backward: same math as `_bwd_blockwise` (the XLA
    reference used by the parity tests) but with scores recomputed in
    VMEM — nothing S^2-shaped touches HBM — and the causal block skip
    in BOTH directions (the XLA scan masks instead of skipping, doing
    2x the needed work). The trace that motivated this: the scan
    backward was ~29% of LM step time for ~6% of model FLOPs."""
    b, s, h, d = q.shape
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    # row term = delta - dlse, delta_i = rowsum(dO_i * O_i): cheap
    # elementwise XLA; folding it here keeps the kernels single-purpose
    delta = jnp.sum(dot.astype(jnp.float32)
                    * o.transpose(0, 2, 1, 3).reshape(b * h, s, d)
                    .astype(jnp.float32), axis=-1, keepdims=True)
    rt = delta if dlse is None else delta - dlse[..., None].astype(
        jnp.float32)
    lse3 = lse[..., None]

    common_in = [qt, kt, vt, dot, lse3, rt]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, blk_q=blk_q, scale=scale,
                          causal=causal),
        grid=(b * h, s // blk_k),
        in_specs=[
            pl.BlockSpec((1, s, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, blk_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, s, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, s, 1), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, s, 1), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*common_in)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, blk_k=blk_k, scale=scale,
                          causal=causal),
        grid=(b * h, s // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, blk_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*common_in)

    def back(x):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return back(dq), back(dk), back(dv)


def _bwd_blockwise(q, k, v, o, lse, do, *, blk: int, scale: float,
                   causal: bool, dlse=None):
    """Flash backward in plain XLA, scanning KV blocks. All (B,S,H,D).

    With `dlse` (a (B*H, S) cotangent on the log-sum-exp output), the
    score gradient gains the softmax term: d(lse)/d(s_ij) = p_ij, so
    ds += p * dlse_row — this is what lets consumers of (o, lse)
    (the lse-combine in ring attention) differentiate through both.
    """
    b, s, h, d = q.shape
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
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
            mask = q_pos[:, None] >= kv_pos[None, :]
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
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


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


def _kernel_interpret(what: str, q) -> bool | None:
    """Which path this trace takes: the Pallas `interpret` flag (False
    = compiled, on TPU; True = the test hook), or None for the compiled
    XLA blockwise paths — off-TPU, where interpret-mode Pallas is
    orders of magnitude slower and would throttle the CPU
    elastic/multipod worlds. Logged per trace, so a trainer's log says
    which attention its step was built from."""
    if jax.default_backend() == "tpu":
        mode, interpret = "pallas kernel, compiled", False
    elif _FORCE_INTERPRET:
        mode, interpret = "pallas kernel, interpret mode", True
    else:
        mode, interpret = "xla blockwise", None
    log.info("flash attention %s %s: %s", what, tuple(q.shape), mode)
    return interpret


def _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal):
    interpret = _kernel_interpret("fwd", q)
    if interpret is None:
        return _fwd_blockwise(q, k, v, blk=blk_k, scale=scale,
                              causal=causal)
    return _fwd(q, k, v, blk_q=blk_q, blk_k=blk_k, scale=scale,
                causal=causal, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, blk_q, blk_k, scale, causal):
    return _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal)


def _flash_lse_fwd(q, k, v, blk_q, blk_k, scale, causal):
    o, lse = _fwd_dispatch(q, k, v, blk_q, blk_k, scale, causal)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(blk_q, blk_k, scale, causal, res, cotangents):
    q, k, v, o, lse = res
    do, dlse = cotangents
    interpret = _kernel_interpret("bwd", q)
    if interpret is None:
        return _bwd_blockwise(q, k, v, o, lse, do, blk=blk_k,
                              scale=scale, causal=causal, dlse=dlse)
    return _bwd_pallas(q, k, v, o, lse, do, blk_q=blk_q, blk_k=blk_k,
                       scale=scale, causal=causal, dlse=dlse,
                       interpret=interpret)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: float | None = None,
                        block_q: int = 512, block_k: int = 512
                        ) -> tuple[jax.Array, jax.Array]:
    """flash_attention that ALSO returns the per-row log-sum-exp
    ((B, H*... reshaped) -> (B, S, H)) — the combinable statistic for
    composing partial attentions (ring attention's per-block kernel:
    two normalized outputs merge exactly via their lse weights).
    Fully differentiable through both outputs.
    """
    b, s, h, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shape mismatch: {q.shape} {k.shape} "
                         f"{v.shape}")
    blk_q = _fit_block(s, block_q)
    blk_k = _fit_block(s, block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    o, lse = _flash_lse(q, k, v, blk_q, blk_k, scale, causal)
    return o, lse.reshape(b, h, s).transpose(0, 2, 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512) -> jax.Array:
    """Fused causal attention. q/k/v: (B, S, H, D) -> (B, S, H, D).

    Blocks auto-fit any 128-divisible sequence (pad upstream otherwise —
    the transformer's static max_len already guarantees this). One
    custom_vjp serves this and `flash_attention_lse`: the unused lse
    output's cotangent is zero, which `_bwd_blockwise` folds away.
    """
    return flash_attention_lse(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)[0]
