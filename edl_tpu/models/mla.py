"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 §2.1.1)
and the multi-token-prediction module of depth 1 (§2.2): what
`joyai_config` adds to the block, imported only where a configuration
has a latent (`cfg.kv_lora_rank > 0`) or an MTP module.

For a token x at position p, H heads:

    c_q = RMSNorm(x W_qa)                        d -> q_lora_rank
    [q_nope | q_pe] = c_q W_qb                   a head: nope | rope
    [c_kv | k_pe] = x W_kva                      kv_lora_rank | rope
    [k_nope | v] = RMSNorm(c_kv) W_kvb           a head: nope | v
    q = [q_nope | R_p(q_pe)], k = [k_nope | R_p(k_pe)]
    o = softmax(q k^T / sqrt(nope + rope), causal) v;  out = o W_o

`k_pe` is one vector for all heads and is not normed. R_p turns the
interleaved pairs (x_2j, x_2j+1) by p * theta^(-2j/rope): positions on
a part of the head only. Keys and queries are nope + rope wide (192),
values v wide (128): `ops/flash_attention.py` takes a value head size of
its own. `k_pe` is repeated to the H heads outside the kernels: it is a
part of every head's key, not a head that an index could pick, as a
grouped-query model's key/value heads are picked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from edl_tpu.models.transformer import (RMSNorm, TransformerConfig,
                                        _causal_attention, _dense, _norm,
                                        remat_block)


def rope_pairs(x: jax.Array, theta: float, positions=None) -> jax.Array:
    """Rotary positions on (B, S, H, D) over all of D by interleaved
    pairs: (x_2j, x_2j+1) turned by pos * theta^(-2j/D). The caller
    hands in the part of the head that turns. Float32 inside, cast
    back; no reshape to pairs (a minor dimension of 2 wastes the lanes):
    each element's partner comes by a roll of one lane either way."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None]
    # a pair's two lanes share an angle
    cos, sin = (jnp.repeat(t, 2, axis=-1)[None, :, None, :]
                for t in (jnp.cos(angles), jnp.sin(angles)))
    x32 = x.astype(jnp.float32)
    even = jnp.arange(d) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x32, -1, -1), jnp.roll(x32, 1, -1))
    return (x32 * cos + partner * sin).astype(x.dtype)


class LatentAttention(nn.Module):
    """The mixer above under the name `Attention` has in a block
    (`attn`), so the scopes of a device trace read `block<i>/attn/...`:
    `mla_q` (down-projection, latent norm, up-projection, q put
    together), `mla_kv` (the same for the key/value latent, `k_pe`
    repeated, k put together), `rope`, the flash calls, `out`."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, train: bool = True):
        cfg = self.cfg
        h, nope, pe = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank = cfg.kv_lora_rank
        if cfg.use_ring:
            raise ValueError("ring attention has no value head size of its "
                             "own: latent attention runs on one chip's rows")

        def up(features, name):  # latent -> a head's features
            return nn.DenseGeneral(
                (h, features), axis=-1, dtype=cfg.dtype, use_bias=False,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.variance_scaling(
                        1.0, "fan_in", "normal"), ("mlp", "heads", "kv")),
                name=name)

        with jax.named_scope("mla_q"):
            c_q = _dense(cfg.q_lora_rank, ("embed", "mlp"), cfg,
                         name="q_a")(x)
            c_q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_a_norm")(c_q)
            q = up(nope + pe, "q_b")(c_q)
            q_nope, q_pe = q[..., :nope], q[..., nope:]
        with jax.named_scope("mla_kv"):
            c_kv = _dense(rank + pe, ("embed", "mlp"), cfg, name="kv_a")(x)
            k_pe = c_kv[..., None, rank:]                   # (B, S, 1, pe)
            c_kv = RMSNorm(cfg.norm_eps, cfg.dtype,
                           name="kv_a_norm")(c_kv[..., :rank])
            kv = up(nope + cfg.v_head_dim, "kv_b")(c_kv)
            k_nope, v = kv[..., :nope], kv[..., nope:]
        with jax.named_scope("rope"):
            q_pe = rope_pairs(q_pe, cfg.rope_theta)
            k_pe = rope_pairs(k_pe, cfg.rope_theta)
        with jax.named_scope("mla_q"):
            q = jnp.concatenate([q_nope, q_pe], -1)
        with jax.named_scope("mla_kv"):
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], pe))],
                -1)
        # "full": the positions are on already, and every key is seen
        o = _causal_attention(cfg, "full", q, k, v, None)
        o = cfg.constrain(o, ("batch", "seq", "heads", "kv"))
        o = nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, use_bias=False,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("heads", "kv", "embed")), name="out")(o)
        return cfg.constrain(o, ("batch", "seq", "embed"))


class MTPModule(nn.Module):
    """One multi-token-prediction module: from the main model's output
    h (after its final norm, what feeds the head) and the embedding e of
    the NEXT token at each place,

        u = [RMSNorm_e(e) ; RMSNorm_h(h)] W_eh       2d -> d
        z = RMSNorm_out(Block(u))                    a whole expert block

    whose logits through the main model's own head predict the token
    after the next. Under the name `mtp` in `Transformer`, so a device
    trace reads `mtp/...`: `mtp_merge` the two norms and the
    projection, `block` (rematerialised as the others), `ln`."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, e, train: bool = True):
        cfg = self.cfg
        with jax.named_scope("mtp_merge"):
            u = jnp.concatenate([_norm(cfg, "enorm")(e),
                                 _norm(cfg, "hnorm")(h)], -1)
            u = _dense(cfg.d_model, ("mlp", "embed"), cfg, name="eh_proj")(u)
        u = remat_block(cfg)(cfg, "attention", True, name="block")(u, train)
        with jax.named_scope("ln"):
            return _norm(cfg, "norm")(u)
