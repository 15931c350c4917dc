"""Softmax cross-entropy for LM heads: the loss and its gradient in one
sweep over row blocks that see the whole vocabulary.

The logits of an LM step are the largest tensor it could make: at 6
sequences of 2,048 positions and 50,257 words, 2.5 GB of float32 that
exist only to be log-softmaxed and gathered. This op never holds them.
It walks the rows in blocks (a few thousand rows, all V columns): one
matmul makes the block's logits, the row's log-sum-exp and the target's
logit come from them, and at once ``dlogits = (softmax - onehot) / n``
goes into ``d_hidden`` of the block and is added into ``d_kernel``. A
block's logits exist once and are never replayed: three N x d x V
matmuls a step, which is what the result needs, and a transient of
O(block x V), not O(N x V).

The `custom_vjp`'s forward rule keeps ``(d_hidden, d_kernel)`` as its
residuals and the backward rule only multiplies them by the incoming
cotangent (1 under `jax.value_and_grad`). Without a gradient asked
(evaluation) the sweep makes the loss alone, one matmul.

Plain XLA inside (`lax.fori_loop` + MXU matmuls with float32
accumulation): the compiler tiles these matmuls well, and a whole
vocabulary a block leaves it nothing to clamp or mask. The loop walks
the rows it is given: where the batch is sharded over chips,
`models.transformer.lm_loss_fused` runs the op on each chip's own rows.

No reference counterpart (its models are CNNs); net-new tpu-first
capability like ops/flash_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Rows x columns of one block's logits: 0.34 GB in float32. On a v5e at
# d=2048, V=50,257 the sweep is fastest at 1,536 rows a block (3.9 us a
# row; 1,024: 4.2, 2,048: 4.6, 512: 5.0, 256: 7.4; the op alone, PR 29):
# below that the read-add-write of d_kernel (8 x d x V bytes a block)
# shows beside the matmul that makes it (rows / 4 FLOP a byte against
# the chip's ridge of 240), above it nothing is gained for the memory.
_BLOCK_ELEMS = 80 << 20


def blocking(rows: int, vocab: int,
             block_rows: int | None = None) -> tuple[int, int]:
    """(blocks, rows of a block), from the shapes alone: the most rows,
    in steps of 256, whose block of logits stays under `_BLOCK_ELEMS`.
    ``block_rows`` (tests) is taken as it is. The last block may reach
    past ``rows``: the sweep pads with rows of weight 0."""
    if block_rows is None:
        block_rows = max(256, _BLOCK_ELEMS // vocab // 256 * 256)
    block_rows = min(block_rows, rows)
    return -(-rows // block_rows), block_rows


def describe(rows: int, vocab: int) -> str:
    """What a run logs of the blocking it ran (`lm_train --fused-loss`)."""
    blocks, block_rows = blocking(rows, vocab)
    return (f"one sweep, {blocks} blocks of {block_rows} rows x {vocab}, "
            "3 matmuls")


# The scope names the sweep in a device trace, which otherwise shows
# anonymous fusions; the narrower ones name its three matmuls. Names are
# metadata: the compiled loop is the same with and without them.
@jax.named_scope("xent")
def _sweep(hidden, kernel, targets, n_rows, block_rows, with_grad,
           weights=None):
    """Sum over the rows with a target >= 0 of (lse - target's logit) / n_rows,
    and with ``with_grad`` its gradient for hidden and kernel. With
    ``weights`` (float32, one a row) the sum over all rows of weight x
    (lse - target's logit) instead, and ``n_rows`` is not read."""
    n, d = hidden.shape
    v = kernel.shape[1]
    blocks, r = blocking(n, v, block_rows)
    if blocks * r > n:
        hidden = jnp.pad(hidden, ((0, blocks * r - n), (0, 0)))
        targets = jnp.pad(targets, (0, blocks * r - n), constant_values=-1)
        if weights is not None:
            weights = jnp.pad(weights, (0, blocks * r - n))
    k32 = kernel.astype(jnp.float32)
    if weights is None:
        scale = 1.0 / n_rows.astype(jnp.float32)

    # A block is held vocabulary-major, (V, R), and d_kernel as (V, d):
    # the layouts the compiler picks by itself for a vocabulary that is
    # no multiple of 128 and does not for one that is, where the
    # d_kernel product then runs at half its rate (35 against 25 ms a
    # step in olmoe_d1.steady; my chip runs, PR 29).
    def body(i, carry):
        h32 = lax.dynamic_slice(hidden, (i * r, 0), (r, d)
                                ).astype(jnp.float32)
        t = lax.dynamic_slice(targets, (i * r,), (r,))
        with jax.named_scope("xent_logits"):
            logits = lax.dot_general(k32, h32, (((0,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=0)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=0))
        picked = jnp.take_along_axis(
            logits, jnp.maximum(t, 0)[None, :], axis=0)[0]
        if weights is None:
            w = jnp.where(t >= 0, scale, 0.0)
        else:
            w = lax.dynamic_slice(weights, (i * r,), (r,))
        loss = carry[0] + jnp.sum(w * (lse - picked))
        if not with_grad:
            return (loss,)
        _, dh, dk = carry
        onehot = lax.broadcasted_iota(jnp.int32, (v, 1), 0) == t
        dlogits = (jnp.exp(logits - lse) - onehot.astype(jnp.float32)) * w
        with jax.named_scope("xent_dh"):
            dh_blk = lax.dot_general(dlogits, k32, (((0,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        with jax.named_scope("xent_dk"):
            dk = dk + jnp.dot(dlogits, h32,
                              preferred_element_type=jnp.float32)
        dh = lax.dynamic_update_slice(dh, dh_blk.astype(dh.dtype), (i * r, 0))
        return loss, dh, dk

    init = (jnp.zeros((), jnp.float32),)
    if with_grad:
        init += (jnp.zeros_like(hidden), jnp.zeros((v, d), jnp.float32))
    out = lax.fori_loop(0, blocks, body, init)
    if not with_grad:
        return out[0]
    return out[0], out[1][:n], out[2].T.astype(kernel.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _xent(hidden, kernel, targets, n_rows, block_rows):
    return _sweep(hidden, kernel, targets, n_rows, block_rows, False)


def _xent_fwd(hidden, kernel, targets, n_rows, block_rows):
    loss, dh, dk = _sweep(hidden, kernel, targets, n_rows, block_rows, True)
    return loss, (dh, dk)


@jax.named_scope("xent")
def _xent_bwd(block_rows, res, g):
    dh, dk = res
    return (g * dh).astype(dh.dtype), (g * dk).astype(dk.dtype), None, None


_xent.defvjp(_xent_fwd, _xent_bwd)


# The same sweep under a weight a row: a rule of its own, so that the
# callers that give no weights trace the program they always did.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _xent_weighted(hidden, kernel, targets, weights, block_rows):
    return _sweep(hidden, kernel, targets, None, block_rows, False, weights)


def _xent_weighted_fwd(hidden, kernel, targets, weights, block_rows):
    loss, dh, dk = _sweep(hidden, kernel, targets, None, block_rows, True,
                          weights)
    return loss, (dh, dk)


_xent_weighted.defvjp(_xent_weighted_fwd, _xent_bwd)


def streamed_lm_xent(hidden, kernel, targets, block_rows: int | None = None,
                     n_rows=None, weights=None):
    """Mean CE of softmax(hidden @ kernel) against integer targets.

    hidden: (..., d); kernel: (d, V); targets: (...) int32 in [0, V), or
    negative for a row that does not count (an LM batch's last
    positions). Equivalent to the mean of
    ``-log_softmax(hidden @ kernel)[..., targets]`` over the rows that
    count, without ever holding more than a block of the logits.
    ``n_rows`` is what the sum is divided by where that is not this
    call's own count of rows (a caller that holds a share of the batch
    gives the whole batch's).

    ``weights`` (...) float32: the sum over the rows of weight x CE
    instead of the mean (an objective that weighs its tokens one by one,
    each target in [0, V)); they get no gradient."""
    if weights is not None:
        return _xent_weighted(
            hidden.reshape(-1, hidden.shape[-1]), kernel,
            targets.reshape(-1), weights.reshape(-1).astype(jnp.float32),
            block_rows)
    if n_rows is None:
        n_rows = jnp.sum(targets >= 0)
    return _xent(hidden.reshape(-1, hidden.shape[-1]), kernel,
                 targets.reshape(-1), n_rows, block_rows)
