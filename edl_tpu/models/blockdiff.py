"""Training by diffusion over blocks (BD3-LMs, arXiv:2503.09573; what
SDAR, arXiv:2510.06303, trains by), the part that is not the block.

A row of L tokens is cut into blocks of B. For each block a noise level
t is drawn and each of its tokens is masked with probability t (the
loader's part: data/block_noise.py). The model runs the noised copy and
the clean copy of the row in one pass, [noised ; clean] along the
sequence, through every linear layer, norm and expert layer as one
batch; only attention tells them apart. With b(i) = i // B:

    a noised query i sees   noised keys j with b(j) == b(i)   (its block)
                            clean keys j with b(j) <  b(i)    (the past)
    a clean query i sees    clean keys j with b(j) <= b(i)
                            no noised key

The loss is on the noised copy's masked tokens, the logits those at the
token's own place (no shift), each weighed by 1 / t of its block, over
rows x L: in expectation a token's cross-entropy.

`attention` is that mask as three pieces, none over 2L and none with a
dense mask: the clean copy through `flash_attention(blocks=(B, False))`;
the noised queries against the clean copy's earlier blocks through
`flash_attention_lse(blocks=(B, True))`; the noised queries against
their own block as a batched (L/B, B, B) product; the last two joined
by their log-sum-exps. A query of the first block has no clean key:
the kernel's `lse` is `_NEG_INF` there, its weight in the join 0 exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from edl_tpu.ops.flash_attention import flash_attention, flash_attention_lse


def own_block(q, k, v, block: int, scale: float):
    """Every query against the keys of its own block, all of them.
    q: (R, L, H, D); k, v: (R, L, KV, D), query head h reading
    key/value head h // (H / KV). Returns (o (R, L, H, D) in q's type,
    lse (R, L, H) float32)."""
    r, n, h, d = q.shape
    kv = k.shape[2]
    qb = q.reshape(r, n // block, block, kv, h // kv, d)
    kb = k.reshape(r, n // block, block, kv, d)
    vb = v.reshape(r, n // block, block, kv, d)
    s = jnp.einsum("rnikgd,rnjkd->rnikgj", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(v.dtype)
    o = jnp.einsum("rnikgj,rnjkd->rnikgd", p, vb,
                   preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(q.dtype), lse.reshape(r, n, h)


def merge(o1, lse1, o2, lse2):
    """One softmax over the keys of two parts, from each part's output
    and log-sum-exp. lse2 is finite everywhere (a query sees its own
    block); where lse1 is `_NEG_INF` (no key seen) the first part's
    weight is exp(-1e30 - lse2) = 0 exactly, and so is every gradient
    into it."""
    m = jnp.maximum(lse1, lse2)
    w1, w2 = jnp.exp(lse1 - m), jnp.exp(lse2 - m)
    o = (w1[..., None] * o1.astype(jnp.float32)
         + w2[..., None] * o2.astype(jnp.float32)) / (w1 + w2)[..., None]
    return o.astype(o1.dtype)


def attention(q, k, v, *, block: int, scale: float | None = None):
    """q: (R, 2L, H, D), k, v: (R, 2L, KV, D), [noised ; clean], after
    norms and positions. Returns (R, 2L, H, D)."""
    n = q.shape[1] // 2
    if n % block:
        raise ValueError(f"blocks of {block} do not divide a row of {n}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    with jax.named_scope("blockdiff_assemble"):
        q_noised, q_clean = q[:, :n], q[:, n:]
        k_noised, v_noised = k[:, :n], v[:, :n]
        k_clean, v_clean = k[:, n:], v[:, n:]
    with jax.named_scope("attn_clean"):
        o_clean = flash_attention(q_clean, k_clean, v_clean, scale=scale,
                                  blocks=(block, False))
    with jax.named_scope("attn_noised"):
        o_past, lse_past = flash_attention_lse(
            q_noised, k_clean, v_clean, scale=scale, blocks=(block, True))
    with jax.named_scope("attn_own_block"):
        o_own, lse_own = own_block(q_noised, k_noised, v_noised, block, scale)
    with jax.named_scope("attn_merge"):
        o_noised = merge(o_past, lse_past, o_own, lse_own)
    with jax.named_scope("blockdiff_assemble"):
        return jnp.concatenate([o_noised, o_clean], axis=1)


def noised_batch(batch: dict, mask_id: int):
    """(the rows' noised copy, the weight of each token's cross-entropy)
    from a batch of `tokens` (R, L), `masked` (R, L) bool and `t` (R, L),
    the noise level of each token's block."""
    with jax.named_scope("blockdiff_assemble"):
        tokens, masked = batch["tokens"], batch["masked"]
        noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
        weights = masked.astype(jnp.float32) / (
            batch["t"].astype(jnp.float32) * tokens.size)
    return noised, weights


def with_masked_share(loss_and_metrics: tuple, batch: dict) -> tuple:
    """The step line's `masked=`: the share of the batch's tokens that
    were masked, 0.5 in expectation."""
    loss, metrics = loss_and_metrics
    return loss, {**metrics,
                  "masked": jnp.mean(batch["masked"].astype(jnp.float32))}
