"""Streamed-vocab softmax cross-entropy for LM heads.

The last big activation in the LM step is the logits tensor: at
B=16, S=1024, V=32768 it is 2 GB of fp32 that exists only to be
log-softmaxed and gathered. This op never materializes it — the head
matmul and the CE fuse into one pass that streams VOCAB CHUNKS, keeping
a running (max, sum-exp) and the target's logit per row, exactly the
flash-attention trick applied to the classifier axis. The backward
replays the chunks from the saved log-sum-exp: d_logits for a chunk is
(softmax - onehot) — formed chunk-at-a-time and immediately contracted
into d_hidden and that chunk's d_kernel, so the full logits gradient
never exists either. Peak transient memory drops from O(N*V) to
O(N*chunk), which is what lets the LM batch grow past the logits wall.

Plain XLA inside (`lax.fori_loop`/`dynamic_slice` + MXU matmuls with
fp32 accumulation) under a `jax.custom_vjp` — the compiler tiles these
matmuls well; the win here is the memory schedule, not hand-written
vector code.

No reference counterpart (its models are CNNs); net-new tpu-first
capability like ops/flash_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _chunks(v: int, want: int) -> int:
    """Chunk width: v if it fits, else `want` (the loop handles a ragged
    tail by clamped slices + masking — any vocab keeps the O(N*chunk)
    bound, including primes like GPT-2's 50257)."""
    return v if v <= want else want


def _chunk_cols(ci, chunk, v):
    """(start, global col index grid (1, chunk)) for clamped chunk ci.

    dynamic_slice clamps an out-of-bounds start, so the final ragged
    chunk re-reads some columns of the previous one; the caller masks by
    comparing the global index against the chunk's true [c0, c0+chunk)
    window, which zeroes the overlap exactly once."""
    c0 = ci * chunk
    start = jnp.minimum(c0, v - chunk)
    cols = start + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    return c0, start, cols


# The scope names the two `while` loops (the forward's here, the
# backward's below) in a device trace, which otherwise shows them as
# anonymous fusions. Names are metadata: the compiled loops are the same.
@jax.named_scope("xent")
def _fwd_pass(hidden, kernel, targets, chunk):
    """Returns (lse (N,), target_logit (N,)) streaming vocab chunks."""
    n, d = hidden.shape
    v = kernel.shape[1]
    h32 = hidden.astype(jnp.float32)
    k32 = kernel.astype(jnp.float32)
    n_chunks = -(-v // chunk)

    def body(ci, carry):
        m, l, tgt = carry
        c0, start, cols = _chunk_cols(ci, chunk, v)
        k_blk = lax.dynamic_slice(k32, (0, start), (d, chunk))
        logits = jnp.dot(h32, k_blk,
                         preferred_element_type=jnp.float32)  # (N, C)
        valid = (cols >= c0) & (cols < v)
        logits = jnp.where(valid, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(valid, jnp.exp(logits - m_new[:, None]), 0.0),
            axis=-1)
        local = targets - start
        in_chunk = (targets >= c0) & (targets < jnp.minimum(c0 + chunk, v))
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        tgt = jnp.where(in_chunk, picked, tgt)
        return m_new, l, tgt

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    t0 = jnp.zeros((n,), jnp.float32)
    m, l, tgt = lax.fori_loop(0, n_chunks, body, (m0, l0, t0))
    return m + jnp.log(l), tgt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def streamed_lm_xent(hidden, kernel, targets, chunk: int = 8192):
    """Mean CE of softmax(hidden @ kernel) vs integer targets.

    hidden: (N, d); kernel: (d, V); targets: (N,) int32 in [0, V).
    Equivalent to
    ``-mean(log_softmax(hidden @ kernel)[arange(N), targets])`` without
    ever materializing the (N, V) logits.
    """
    chunk = _chunks(kernel.shape[1], chunk)
    lse, tgt = _fwd_pass(hidden, kernel, targets, chunk)
    return jnp.mean(lse - tgt)


def _xent_fwd(hidden, kernel, targets, chunk):
    chunk = _chunks(kernel.shape[1], chunk)
    lse, tgt = _fwd_pass(hidden, kernel, targets, chunk)
    return jnp.mean(lse - tgt), (hidden, kernel, targets, lse)


@jax.named_scope("xent")
def _xent_bwd(chunk, res, g):
    hidden, kernel, targets, lse = res
    n, d = hidden.shape
    v = kernel.shape[1]
    chunk = _chunks(v, chunk)
    h32 = hidden.astype(jnp.float32)
    k32 = kernel.astype(jnp.float32)
    scale = g / n  # d(mean)/d(row)
    n_chunks = -(-v // chunk)

    def body(ci, carry):
        dh, dk = carry
        c0, start, cols = _chunk_cols(ci, chunk, v)
        k_blk = lax.dynamic_slice(k32, (0, start), (d, chunk))
        logits = jnp.dot(h32, k_blk, preferred_element_type=jnp.float32)
        valid = (cols >= c0) & (cols < v)
        p = jnp.where(valid, jnp.exp(logits - lse[:, None]), 0.0)
        local = targets - start
        in_chunk = (targets >= c0) & (targets < jnp.minimum(c0 + chunk, v))
        onehot = (lax.broadcasted_iota(jnp.int32, (1, chunk), 1) ==
                  jnp.clip(local, 0, chunk - 1)[:, None]) & in_chunk[:, None]
        dlogits = (p - onehot.astype(jnp.float32)) * scale
        dh = dh + jnp.dot(dlogits, k_blk.T,
                          preferred_element_type=jnp.float32)
        dk_blk = jnp.dot(h32.T, dlogits,
                         preferred_element_type=jnp.float32)
        # accumulate into the preallocated (d, V) gradient in place —
        # read-add-write is overlap-safe because masked columns
        # contribute exactly 0 from the ragged chunk
        cur = lax.dynamic_slice(dk, (0, start), (d, chunk))
        dk = lax.dynamic_update_slice(dk, cur + dk_blk, (0, start))
        return dh, dk

    dh, dk = lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros((n, d), jnp.float32), jnp.zeros((d, v), jnp.float32)))
    return (dh.astype(hidden.dtype), dk.astype(kernel.dtype), None)


streamed_lm_xent.defvjp(_xent_fwd, _xent_bwd)
