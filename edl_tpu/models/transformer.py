"""Decoder-only transformer LM — the long-context / multi-axis flagship.

Net-new relative to the reference (its models are CNNs + BOW/ERNIE-distill,
SURVEY.md §5): a causal LM whose parameters carry flax *logical axis names*
(`vocab/embed/heads/kv/mlp`) so `edl_tpu.parallel.sharding` rules shard them
over any `dp x fsdp x tp x sp` mesh, and whose attention switches to
`edl_tpu.parallel.ring_attention` when the mesh has a real `sp` axis —
sequence/context parallelism with k/v blocks rotating over ICI.

Everything is static-shaped and jit-traceable; remat is applied per block
(`jax.checkpoint`) to trade FLOPs for HBM when configured.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field as dfield
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from edl_tpu.ops.flash_attention import KEPT_LSE, KEPT_O
from edl_tpu.parallel import ring_attention as ra
from edl_tpu.parallel import sharding as shd


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 2048
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # attention kernel: "auto" = ring when the mesh has sp>1, else the
    # Pallas flash kernel on TPU (ops/flash_attention.py), else XLA
    # dense; "flash"/"dense" force a single-device kernel choice.
    attention: str = "auto"
    # mesh: when set (and it has sp>1) attention runs the ring kernel and
    # activations get logical sharding constraints. None = single-device.
    mesh: Mesh | None = dfield(default=None, hash=False, compare=False)
    rules: tuple = shd.DEFAULT_RULES
    # -- mixture of experts (dense fallback: moe=False leaves every
    # existing config byte-identical — blocks keep the plain MLP).
    # moe=True swaps each block's MLP for MoEMLP: a softmax top-k
    # router over n_experts expert FFNs whose tables carry the
    # ("expert", ...) logical axis — sharded over ep by
    # sharding.DEFAULT_RULES, so they enter the checkpoint index as
    # ep-sharded leaves and re-shard on resize like any sharded state.
    moe: bool = False
    n_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25  # the wire's buffers only
    moe_aux_weight: float = 0.01
    # -- the block's kind. The defaults are the GPT-2-shaped block this
    # file always built (LayerNorm, a learned position table, gelu
    # experts, renormalised gates) and leave its parameter tree, names
    # and arithmetic as they were; `olmoe_config` below sets them all.
    norm: str = "layernorm"        # | "rmsnorm" (scale only, float32)
    norm_eps: float = 1e-6
    pos: str = "learned"           # | "rope" (rotate-half, whole head)
    #                                | "none" (no table, no rotation)
    rope_theta: float = 10000.0
    qk_norm: bool = False          # RMSNorm on q and k over all heads
    moe_gated: bool = False        # SwiGLU experts: w_gate, w_up, w_down
    moe_renorm: bool = True        # kept top-k gates renormalised to 1
    moe_z_weight: float = 0.0      # router z-loss, mean logsumexp^2
    # moe_wire: transport for expert dispatch/combine. None = the
    # dropless sort-and-gather dispatch inside the jit step. Inside a
    # manual shard_map region, train/comm injects its hierarchical
    # all-to-all wire here (an object with dispatch/combine/local_slice
    # — see comm.MoEWire), and the capacity router fills its buffers.
    moe_wire: Any = dfield(default=None, hash=False, compare=False)
    # -- what a hybrid state-space model adds (`granite_hybrid_config`
    # sets them all; the defaults are the blocks above, untouched).
    # layer_types: one kind a block, "attention" | "mamba" (a Mamba-2
    # mixer, `Mamba2Mixer`); None = attention everywhere.
    layer_types: tuple | None = None
    n_kv_heads: int = 0            # 0 = n_heads; else grouped-query
    attn_scale: float | None = None    # None = head_dim ** -0.5
    mlp_gated: bool = False        # dense SwiGLU: gate, up, out
    tie_embeddings: bool = False   # the head is the embedding table
    embed_scale: float = 1.0       # x = E[tokens] * embed_scale
    logits_scale: float = 1.0      # logits = h E^T / logits_scale
    residual_scale: float = 1.0    # x = x + residual_scale * f(norm(x))
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # -- what a mixture with sliding-window and global attention layers,
    # a shared expert and a routing bias adds (`afmoe_config` sets them
    # all; the defaults are the blocks above, untouched). layer_types
    # then also knows "sliding" (causal over the `window` newest keys,
    # positions as `pos` says) and "full" (every earlier key, and no
    # positions whatever `pos` says).
    head_size: int = 0             # 0 = d_model // n_heads
    window: int = 0                # keys a "sliding" layer's query sees
    qk_norm_heads: bool = False    # RMSNorm on q and k over each head
    attn_gate: bool = False        # heads' output * sigmoid(x W_g)
    sandwich_norm: bool = False    # mixer and MLP outputs normed too
    n_dense_layers: int = 0        # moe: leading blocks keep a dense MLP
    moe_d_ff: int = 0              # an expert's width; 0 = d_ff
    moe_score: str = "softmax"     # | "sigmoid"
    moe_route_scale: float = 1.0   # the kept gates are multiplied by it
    moe_shared: int = 0            # shared experts, added to every token
    # moe_bias_rate > 0: top-k is taken over score + bias, the gates
    # from the scores alone; the bias (E floats a layer, collection
    # "batch_stats": state that no gradient trains) moves by this much a
    # step against each expert's load
    moe_bias_rate: float = 0.0
    # the chip's share of the experts: the router and top-k are over all
    # n_experts, the tables hold experts_held of them from
    # experts_offset on, and the layer computes their part of the result
    experts_held: int = 0          # 0 = n_experts
    experts_offset: int = 0
    # -- training by diffusion over blocks (`sdar_config` sets it; 0 is
    # the next-token model above, untouched). block_length > 0: the model
    # runs a noised and a clean copy of every row in one pass,
    # [noised ; clean] along the sequence, both at positions 0..L-1, and
    # attention sees by block index (models/blockdiff.py); the loss is
    # on the noised copy's masked tokens, no shift. The mask token is
    # the vocabulary's last id.
    block_length: int = 0
    # -- latent attention and a multi-token-prediction module
    # (`joyai_config` sets them all; 0 is the blocks above, untouched).
    # kv_lora_rank > 0: every attention layer is models/mla.py's mixer:
    # queries through a latent of q_lora_rank, keys and values through
    # one of kv_lora_rank, heads of qk_nope_head_dim + qk_rope_head_dim
    # for q and k (rotary positions on the second part alone, by
    # interleaved pairs, its key shared by all heads) and of v_head_dim
    # for v. mtp_layers = 1: one more block behind the final norm that
    # predicts the token after the next through the same embedding and
    # head, its loss added with mtp_weight.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mtp_layers: int = 0
    mtp_weight: float = 0.3

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm={self.norm!r} "
                             "(layernorm|rmsnorm)")
        if self.pos not in ("learned", "rope", "none"):
            raise ValueError(f"unknown pos={self.pos!r} "
                             "(learned|rope|none)")
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            if len(kinds) != self.n_layers or not set(kinds) <= {
                    "attention", "mamba", "sliding", "full"}:
                raise ValueError(
                    f"layer_types must name n_layers={self.n_layers} "
                    f"kinds of attention|mamba|sliding|full, got {kinds}")
            if "mamba" in kinds and self.ssm_heads < 1:
                raise ValueError("mamba layers need ssm_heads >= 1")
            if "sliding" in kinds and self.window < 1:
                raise ValueError("sliding layers need window >= 1")
        if self.block_length and (
                self.block_length < 0 or self.layer_types is not None
                or self.pos == "learned" or self.attention == "dense"):
            raise ValueError(
                "block_length > 0 (diffusion over blocks) runs every layer "
                "as attention by block index through ops/flash_attention: "
                "no layer_types, no learned positions, no dense attention")
        if self.kv_lora_rank and not (
                self.q_lora_rank > 0 and self.qk_nope_head_dim > 0
                and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
                and self.qk_rope_head_dim % 2 == 0 and self.pos == "rope"
                and not self.n_kv_heads and not self.block_length
                and self.layer_types is None):
            raise ValueError(
                "kv_lora_rank > 0 (latent attention) needs q_lora_rank, "
                "qk_nope_head_dim, an even qk_rope_head_dim and v_head_dim "
                "above 0 and pos='rope', and knows no key/value groups, "
                "layer_types or block_length")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and (self.block_length
                                     or self.tie_embeddings)):
            raise ValueError(
                "mtp_layers is 0 or 1 (one module that predicts the token "
                "after the next, through an untied head), and not under "
                "block_length")
        if self.n_kv_heads and self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must divide n_heads="
                f"{self.n_heads}")
        if self.moe:
            if self.n_experts < 2:
                raise ValueError(
                    f"moe needs n_experts >= 2, got {self.n_experts}")
            if not 1 <= self.moe_top_k <= self.n_experts:
                raise ValueError(
                    f"moe_top_k must be in [1, n_experts="
                    f"{self.n_experts}], got {self.moe_top_k}")
            if self.moe_capacity_factor <= 0:
                raise ValueError(
                    f"moe_capacity_factor must be > 0, got "
                    f"{self.moe_capacity_factor}")
            if self.moe_score not in ("softmax", "sigmoid"):
                raise ValueError(f"unknown moe_score={self.moe_score!r} "
                                 "(softmax|sigmoid)")
            if not (0 <= self.experts_offset and self.experts_offset
                    + self.held_experts <= self.n_experts):
                raise ValueError(
                    f"experts {self.experts_offset} to "
                    f"{self.experts_offset + self.held_experts - 1} are "
                    f"not among n_experts={self.n_experts}")

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:  # a query's and a key's; `value_head_dim`
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_size:
            return self.head_size
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def value_head_dim(self) -> int:
        return self.v_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def experts_one_cotangent(self) -> bool:
        """SwiGLU experts hand their rows one cotangent, from one grouped
        product against [w_gate | w_up] (`_swiglu_rows`): wherever no
        mesh axis splits the tables' "mlp" axis, along which the two are
        put side by side (a concatenation along a split axis would move
        weights between chips)."""
        return self.moe_gated and (self.mesh is None or not tuple(
            shd.logical_to_spec(("mlp",), self.rules, self.mesh)))

    def moe_layer(self, layer: int) -> bool:
        return self.moe and layer >= self.n_dense_layers

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def kind(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else "attention"

    def constrain(self, x, logical):
        return shd.constrain(x, logical, self.mesh, self.rules)

    @property
    def use_ring(self) -> bool:
        return (self.mesh is not None and "sp" in self.mesh.axis_names
                and self.mesh.shape["sp"] > 1)

    def use_flash(self, seq_len: int) -> bool:
        if self.attention not in ("auto", "flash", "dense"):
            raise ValueError(f"unknown attention={self.attention!r} "
                             "(auto|flash|dense)")
        if self.attention == "flash":
            return True
        if self.attention != "auto":
            return False
        # auto: the Pallas kernel needs a TPU backend (interpret mode is
        # for tests), a 128-divisible sequence, and a mesh without model
        # sharding on heads (tp shards heads; flash is per-head so it
        # composes, but XLA partitions the dense path equally well — keep
        # flash for the unsharded-attention case where it clearly wins).
        return (jax.default_backend() == "tpu" and seq_len % 128 == 0
                and (self.mesh is None
                     or all(self.mesh.shape.get(a, 1) == 1
                            for a in ("tp", "sp"))))

    def flash(self, q, k, v, window: int | None = None):
        """Flash attention, shard_mapped over the mesh's batch axes —
        a pallas_call is opaque to the XLA partitioner, so without this
        a dp-sharded input would be gathered to every device."""
        from edl_tpu.ops.flash_attention import flash_attention
        fn = partial(flash_attention, causal=True, scale=self.attn_scale,
                     window=window)
        if self.mesh is None or all(s == 1 for s in
                                    self.mesh.shape.values()):
            return fn(q, k, v)
        from jax.sharding import PartitionSpec as P
        batch = tuple(a for a in ("dp", "fsdp")
                      if self.mesh.shape.get(a, 1) > 1) or None
        spec = P(batch)
        from edl_tpu.parallel.compat import shard_map
        return shard_map(fn, mesh=self.mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)

    def xent_shards(self) -> int:
        """Chips that each sweep their own share of the batch in `xent`:
        the mesh's batch axes where nothing else is sharded (tp shards
        the head kernel's vocabulary, which is not the streamed CE's
        case), else 1."""
        shape = self.mesh.shape if self.mesh is not None else {}
        sharded = {a: n for a, n in shape.items() if n > 1}
        if not set(sharded) <= {"dp", "fsdp"}:
            return 1
        return math.prod(sharded.values())

    def xent(self, hidden, kernel, targets, block_rows=None, weights=None):
        """The streamed CE (ops/fused_xent.py), shard_mapped over the
        mesh's batch axes as `flash` is. Each chip sweeps its own
        sequences against the whole head kernel, so the op sizes its
        blocks from the rows a chip holds, the kernel is gathered once
        a step where fsdp shards it, and d_kernel is summed over the
        chips once after the sweep: left to the partitioner, the loop
        would reduce it once a block."""
        from edl_tpu.ops.fused_xent import streamed_lm_xent
        if weights is not None:
            if self.xent_shards() != 1:
                raise ValueError("a weight a row rides one chip's sweep; "
                                 "the sharded sweep takes none yet")
            return streamed_lm_xent(hidden, kernel, targets, block_rows,
                                    weights=weights)
        if self.xent_shards() == 1:
            return streamed_lm_xent(hidden, kernel, targets, block_rows)
        from jax.sharding import PartitionSpec as P
        from edl_tpu.parallel.compat import shard_map
        batch = tuple(a for a in ("dp", "fsdp")
                      if self.mesh.shape.get(a, 1) > 1)
        kspec = shd.logical_to_spec(("embed", "vocab"), self.rules, self.mesh)
        embed_axes = kspec[0] if len(kspec) else None

        def local(h, k, t, n):
            if embed_axes is not None:
                k = jax.lax.all_gather(k, embed_axes, axis=0, tiled=True)
            return streamed_lm_xent(h, k, t, block_rows, n)[None]

        # the scope puts the gather and the reduce-scatter beside the
        # sweep they serve in a device trace
        with jax.named_scope("xent"):
            parts = shard_map(local, mesh=self.mesh,
                              in_specs=(P(batch), kspec, P(batch), P()),
                              out_specs=P(batch))(
                hidden, kernel, targets, jnp.sum(targets >= 0))
            return jnp.sum(parts)


def _dense(features, names, cfg, name=None):
    return nn.DenseGeneral(
        features, axis=-1, dtype=cfg.dtype, name=name, use_bias=False,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            names))


class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * scale, in float32, cast back."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


class _NormScale(nn.Module):
    """The scale an `RMSNorm` of this name would hold, alone: for a
    caller that runs the norm itself (the mixer's, inside its gate's
    kernel)."""

    @nn.compact
    def __call__(self, features: int):
        return self.param("scale", nn.initializers.ones, (features,))


def _scaled(x, by: float):
    """x * by, the product made in float32 and cast back (0.22 is no
    bfloat16 number); x itself where ``by`` is 1."""
    if by == 1.0:
        return x
    return (x.astype(jnp.float32) * by).astype(x.dtype)


def _norm(cfg: TransformerConfig, name: str) -> nn.Module:
    if cfg.norm == "rmsnorm":
        return RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def rope(x: jax.Array, theta: float, positions=None) -> jax.Array:
    """Rotary positions on (B, S, H, D), over the whole head, in the
    rotate-half convention: x*cos + cat(-x[D/2:], x[:D/2])*sin with
    angles pos * theta^(-2i/D), i < D/2, repeated twice. Float32
    inside, cast back. ``positions`` (S,): each place's position where
    that is not its index."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None]
    angles = jnp.concatenate([angles, angles], -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * jnp.cos(angles) + half * jnp.sin(angles)).astype(x.dtype)


def _causal_attention(cfg: TransformerConfig, kind: str, q, k, v, window):
    """Causal attention of one sequence a row: positions, and the path
    the config picks."""
    if cfg.pos == "rope" and kind != "full":
        with jax.named_scope("rope"):
            q, k = _rope(cfg, q), _rope(cfg, k)
    flash = not cfg.use_ring and cfg.use_flash(q.shape[1])
    if cfg.kv_heads != cfg.n_heads and not flash:
        # grouped-query: every key/value head serves n_heads/kv_heads
        # query heads. The flash kernels find a query head's key/value
        # head by index; the ring and dense paths are XLA's, so the
        # heads are repeated for them and autodiff sums dK and dV over
        # each group.
        group = cfg.n_heads // cfg.kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    q = cfg.constrain(q, ("batch", "seq", "heads", "kv"))
    k = cfg.constrain(k, ("batch", "seq", "heads", "kv"))
    v = cfg.constrain(v, ("batch", "seq", "heads", "kv"))

    if cfg.use_ring:
        return ra.ring_attention(q, k, v, mesh=cfg.mesh, causal=True,
                                 scale=cfg.attn_scale)
    if flash:
        return cfg.flash(q, k, v, window)
    return ra.dense_attention(q, k, v, causal=True,
                              scale=cfg.attn_scale, window=window)


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"        # | "sliding" | "full"

    @nn.compact
    def __call__(self, x, train: bool = True):
        cfg = self.cfg
        b, s, _ = x.shape
        window = cfg.window if self.kind == "sliding" else None
        if window is not None and cfg.use_ring:
            raise ValueError("ring attention has no sliding window")
        proj = partial(nn.DenseGeneral, axis=-1, dtype=cfg.dtype,
                       use_bias=False)
        qkv_init = nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            ("embed", "heads", "kv"))
        q = proj((cfg.n_heads, cfg.head_dim), kernel_init=qkv_init,
                 name="query")(x)
        k = proj((cfg.kv_heads, cfg.head_dim), kernel_init=qkv_init,
                 name="key")(x)
        v = proj((cfg.kv_heads, cfg.head_dim), kernel_init=qkv_init,
                 name="value")(x)
        if cfg.qk_norm:  # over all heads' features, before the split
            q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(
                q.reshape(b, s, -1)).reshape(q.shape)
            k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(
                k.reshape(b, s, -1)).reshape(k.shape)
        if cfg.qk_norm_heads:  # over each head's features, one scale
            with jax.named_scope("attn_qk_norm"):
                q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(q)
                k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(k)
        if cfg.block_length:
            # [noised ; clean]: each copy at its place within the row
            from edl_tpu.models import blockdiff
            if cfg.pos == "rope":
                with jax.named_scope("rope"):
                    at = jnp.arange(s) % (s // 2)
                    q = _rope(cfg, q, at)
                    k = _rope(cfg, k, at)
            o = blockdiff.attention(q, k, v, block=cfg.block_length,
                                    scale=cfg.attn_scale)
        else:
            o = _causal_attention(cfg, self.kind, q, k, v, window)
        o = cfg.constrain(o, ("batch", "seq", "heads", "kv"))
        if cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                gate = proj((cfg.n_heads, cfg.head_dim),
                            kernel_init=qkv_init, name="gate")(x)
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)

        out_init = nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            ("heads", "kv", "embed"))
        o = nn.DenseGeneral(cfg.d_model, axis=(-2, -1), dtype=cfg.dtype,
                            use_bias=False, kernel_init=out_init,
                            name="out")(o)
        return cfg.constrain(o, ("batch", "seq", "embed"))


def moe_capacity(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert token buffer size: ceil(cf * T * k / E), at least 1.

    Static (T is a trace-time constant), so every dispatch buffer —
    and therefore the all-to-all wire — has a fixed shape regardless
    of where the router actually sends tokens."""
    import math
    return max(1, math.ceil(capacity_factor * n_tokens * top_k
                            / n_experts))


def router_topk(logits: jax.Array, top_k: int, capacity: int
                ) -> tuple[jax.Array, jax.Array, dict]:
    """Top-k capacity-factor routing (Switch/GShard style), pure dense
    math so it jits on any backend and tests can hit the capacity
    edges without flax.

    logits: (T, E) router scores. Each token picks its top_k experts by
    softmax probability; within each expert, slots are granted in
    CHOICE-MAJOR order (every token's first choice is placed before any
    second choice), and assignments past ``capacity`` are dropped —
    the token's output falls through the residual connection, the
    standard capacity-factor contract.

    Returns ``(combine, dispatch, aux)``: combine (T, E, C) fp32 gate
    weights (renormalized over the kept top-k), dispatch (T, E, C)
    bool one-hot slot assignment, and aux = {load_balance (the Shazeer
    f·p loss, 1.0 at perfect balance), dropped_frac (fraction of the
    T*k assignments dropped by capacity — the accounting the tests
    pin)}.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)                 # (T, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)          # (T, k, E)
    # position of each assignment inside its expert's buffer,
    # choice-major: flatten to (k*T, E) with choice as the slow dim
    flat = oh.transpose(1, 0, 2).reshape(top_k * t, e)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos_flat * flat, axis=-1).reshape(top_k, t).T
    pos = pos.astype(jnp.int32)                             # (T, k)
    kept = pos < capacity
    # one_hot of `capacity` (out of range) is the all-zero row, so a
    # dropped assignment vanishes from dispatch AND combine
    pos_oh = jax.nn.one_hot(jnp.where(kept, pos, capacity), capacity,
                            dtype=jnp.float32)              # (T, k, C)
    dispatch = jnp.einsum("tke,tkc->tec", oh, pos_oh) > 0
    combine = jnp.einsum("tk,tke,tkc->tec", gate, oh, pos_oh)
    f = jnp.mean(jnp.sum(oh, axis=1), axis=0) / top_k       # (E,)
    p = jnp.mean(probs, axis=0)
    aux = {"load_balance": e * jnp.sum(f * p),
           "dropped_frac": 1.0 - jnp.mean(kept.astype(jnp.float32))}
    return combine, dispatch, aux


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch_rows(x, order, inv, here, k: int):
    """Rows of x (T, d) in expert order: x[order // k], where ``order``
    sorts the T*k token-major assignments by expert and ``inv`` is its
    inverse. The backward is a gather as well (the assignments' rows
    back in token order, summed over the k slots), so no scatter-add
    over repeated rows appears in either direction.

    ``here`` (T, k) says which assignments are in a group of the
    grouped matmul, or is None where all are. The others' rows lie past
    the groups, where that matmul's backward writes nothing: what the
    backward's gather brings from there is selected away before the
    sum, in token order, where the select fuses into the reduction."""
    return x[order // k]


def _dispatch_rows_fwd(x, order, inv, here, k):
    return x[order // k], (inv, here)


def _dispatch_rows_bwd(k, res, g):
    inv, here = res
    g = g[inv].reshape(-1, k, g.shape[-1])
    if here is not None:
        g = jnp.where(here[..., None], g, 0)
    return (jnp.sum(g.astype(jnp.float32), 1).astype(g.dtype),
            None, None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(out, gate, order, inv, here):
    """sum_k gate[t, k] * out[inv][t, k]: the expert buffer ``out``
    (T*k, d), in expert order, weighted back to its tokens, (T, d) in
    float32. A slot that is not ``here`` (as `_dispatch_rows` has it)
    gives nothing, by a select and not by a zero gate: its row may hold
    NaN. The backward (`_combine_rows_bwd`, at the end of this file as
    `_rope` is, for the compile cache's key) keeps the buffer in expert
    order, its own residual, and makes nothing of the shape (T, k, d):
    the weighted sum keeps no copy of the buffer in token order, so a
    rematerialised block replays this gather for no one."""
    out = out[inv].reshape(*gate.shape, out.shape[-1])
    if here is not None:
        out = jnp.where(here[..., None], out, 0)
    return jnp.einsum("tk,tkd->td", gate.astype(out.dtype), out,
                      preferred_element_type=jnp.float32)


def _expert_ffn(x, tables, matmul, dtype):
    """One expert FFN over rows grouped by expert; ``matmul(x, table)``
    is the grouped product of the caller's layout. Two tables: gelu
    (w_in, w_out); three: SwiGLU (w_gate, w_up, w_down)."""
    *w_in, w_out = (t.astype(dtype) for t in tables)
    h = matmul(x, w_in[0])
    h = nn.silu(h) * matmul(x, w_in[1]) if len(w_in) == 2 else nn.gelu(h)
    return matmul(h, w_out)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _swiglu_rows(x, w_gate, w_up, counts, replayed: bool):
    """silu(x @ w_gate) * (x @ w_up) over rows grouped by expert
    (``counts``), as `_expert_ffn` computes it, with a backward of its
    own (`_swiglu_rows_bwd`): x is the left operand of two grouped
    products, so autodiff makes two d(lhs) results of x's size and adds
    them (`add_any`: two reads and a write of the T*k-row buffer a
    layer). The backward makes d(h) for h = [gate | up] whole and takes
    both transposes as one product each against [w_gate | w_up]: one
    cotangent of x, summed over the 2f columns in float32 in the
    kernel's accumulator. The forward stays two products: one against
    the joined table measured slower (PERF.md §6, PR 53)."""
    return _swiglu_rows_fwd(x, w_gate, w_up, counts, replayed)[0]


def _swiglu_rows_fwd(x, w_gate, w_up, counts, replayed):
    gate, up = (jax.lax.ragged_dot(x, w, counts) for w in (w_gate, w_up))
    return nn.silu(gate) * up, (x, w_gate, w_up, counts, gate, up)


def _swiglu_rows_bwd(replayed, res, da):
    """d(h) = [da up silu'(gate) | da silu(gate)] as one pass that reads
    gate, up and da and writes (T*k, 2f), every factor widened to 2f
    columns first and the halves told apart by a select, in float32 and
    rounded once. Written as `concatenate([d_gate, d_up])` XLA makes two
    results of f columns and a second pass that joins them, because it
    fuses nothing into a pad's operand unless that is a whole array; the
    three widened here are."""
    x, w_gate, w_up, counts, gate, up = res
    f = gate.shape[-1]

    def wide(a):
        return jnp.concatenate([a, a], -1).astype(jnp.float32)
    gate, up, da = wide(gate), wide(up), wide(da)
    s = jax.nn.sigmoid(gate)
    left = jax.lax.broadcasted_iota(jnp.int32, gate.shape, gate.ndim - 1) < f
    d_h = (da * s * jnp.where(left, up * (1 + gate * (1 - s)), gate)
           ).astype(x.dtype)
    # the product at which `jax.vjp` linearises is read by no one: XLA
    # drops it (seven grouped matmuls a layer in the compiled step)
    d_x, d_w = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, counts), x,
                       jnp.concatenate([w_gate, w_up], -1))[1](d_h)
    d_ws = d_w[..., :f], d_w[..., f:]
    if replayed:
        # cut out here, not inside each table's AdamW pass: those passes
        # then run where two products' results let them run, and XLA's
        # memory-space assignment keeps the next replayed block's rows
        # in fast memory for its gather (JoyAI: four gathers a step from
        # HBM otherwise, 3.6 ms each). Nothing is replayed without
        # remat, and the slices fuse into AdamW: OLMoE's are 268 MB each
        d_ws = jax.lax.optimization_barrier(d_ws)
    return d_x, *d_ws, None


_swiglu_rows.defvjp(_swiglu_rows_fwd, _swiglu_rows_bwd)


class MoEMLP(nn.Module):
    """Mixture-of-experts MLP: a softmax top-k router over n_experts
    FFNs (gelu, or SwiGLU under cfg.moe_gated) whose (E, ...) tables
    carry the "expert" logical axis (sharded over ep by
    sharding.DEFAULT_RULES — the leaves the checkpoint index stores
    ep-sharded and re-shards on resize).

    Two dispatches, one set of tables:
    - cfg.moe_wire=None (default): dropless. The T*k assignments are
      stable-sorted by expert, their rows gathered, the FFN run as
      grouped matmuls over the ragged groups (`jax.lax.ragged_dot`; a
      Mosaic kernel of XLA's on a TPU), and the results un-permuted
      and weighted, once: the combine's backward stays in expert order
      (`_combine_rows`), so remat replays no gather of the buffer. No
      capacity, no (T, E, C) array, nothing dropped. SwiGLU experts
      take `_swiglu_rows`, whose backward hands the rows one cotangent
      (`cfg.experts_one_cotangent`). A chip's share
      (`cfg.held_experts` < n_experts) sorts the absent experts'
      assignments last, into no group; the kernel leaves their rows
      unwritten, both ways, and a select where they return to token
      order (a zero gate would not stop NaN) masks them, no pass.
    - cfg.moe_wire set (inside train/comm's manual shard_map region):
      the capacity router `router_topk`, whose fixed-shape (E, C, d)
      buffer the wire object carries to the experts' owner chips
      (hierarchical ICI/DCN all-to-all, optionally int8 on the DCN
      leg) and back; each chip computes only its local expert slice.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        e, k = cfg.n_experts, cfg.moe_top_k
        held, first = cfg.held_experts, cfg.experts_offset
        share = held != e              # some experts live on other chips
        width = cfg.moe_d_ff or cfg.d_ff
        t = b * s
        router = self.param(
            "router",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("embed", "expert_router")),
            (cfg.d_model, e))
        table_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))

        def table(name, d_in, d_out, axes):
            return self.param(name, nn.with_logical_partitioning(
                table_init, ("expert", *axes)), (held, d_in, d_out))
        names = ("w_gate", "w_up") if cfg.moe_gated else ("w_in",)
        tables = [table(n, cfg.d_model, width, ("embed", "mlp"))
                  for n in names]
        tables.append(table("w_down" if cfg.moe_gated else "w_out",
                            width, cfg.d_model, ("mlp", "embed")))

        xf = x.reshape(t, d)
        with jax.named_scope("moe_router"):
            logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                                router.astype(jnp.float32))
        wire = cfg.moe_wire
        if wire is not None:
            if share or cfg.moe_score != "softmax" or cfg.moe_shared:
                raise ValueError(
                    "the wire's capacity router knows softmax gates over "
                    "experts that are all held, and no shared expert")
            cap = moe_capacity(t, e, k, cfg.moe_capacity_factor)
            combine, dispatch, aux = router_topk(logits, k, cap)
            self.sow("intermediates", "moe_aux", aux["load_balance"])
            self.sow("intermediates", "moe_dropped", aux["dropped_frac"])
            buf = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype), xf)
            out = _expert_ffn(
                wire.dispatch(buf),             # (E/W, W*cap, d)
                [wire.local_slice(w) for w in tables],
                partial(jnp.einsum, "ecd,edf->ecf"), cfg.dtype)
            out = wire.combine(out)             # back to (E, cap, d)
            y = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), out)
            return y.reshape(b, s, d)

        with jax.named_scope("moe_router"):
            if cfg.moe_score == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                probs = jax.nn.softmax(logits, axis=-1)
            if cfg.moe_bias_rate > 0:
                # the bias chooses and never weighs: the gates are the
                # scores of the chosen, as they are
                bias = self.variable("batch_stats", "expert_bias",
                                     jnp.zeros, (e,), jnp.float32)
                _, idx = jax.lax.top_k(
                    probs + jax.lax.stop_gradient(bias.value), k)
                gate = jnp.take_along_axis(probs, idx, axis=-1)
            else:
                gate, idx = jax.lax.top_k(probs, k)         # (T, k)
            if cfg.moe_score == "sigmoid":
                gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
            elif cfg.moe_renorm:
                gate = gate / jnp.maximum(
                    jnp.sum(gate, -1, keepdims=True), 1e-9)
            gate = _scaled(gate, cfg.moe_route_scale)
            counts = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.int32),
                             axis=(0, 1))                   # (E,)
            # what the loss pools over the layers (`_moe_terms`)
            self.sow("intermediates", "moe_frac", counts / (t * k))
            # the experts of every token, for whoever asks (a checker
            # that takes gradients on the same assignments)
            self.sow("intermediates", "moe_idx", idx)
            if cfg.moe_score == "softmax":
                self.sow("intermediates", "moe_probs", jnp.mean(probs, 0))
                self.sow("intermediates", "moe_z", jnp.mean(jnp.square(
                    jax.nn.logsumexp(logits, axis=-1))))
            self.sow("intermediates", "moe_dropped",
                     jnp.zeros((), jnp.float32))
        if cfg.moe_bias_rate > 0 and not self.is_initializing() \
                and self.is_mutable_collection("batch_stats"):
            with jax.named_scope("moe_bias_update"):
                # towards the mean load, by the sign alone, centred
                load = counts.astype(jnp.float32)
                delta = cfg.moe_bias_rate * jnp.sign(jnp.mean(load) - load)
                bias.value = bias.value + (delta - jnp.mean(delta))
        with jax.named_scope("moe_dispatch"):
            # assignment a = token * k + slot; a stable sort by expert
            # keeps the tokens of one expert in token order
            key = idx.reshape(t * k)
            here = None     # (T, k): the slot's expert is held; None: all
            if share:
                # this chip's experts first, the absent ones' rows after
                # them in no group: neither computed nor dropped
                local = key - first
                here = (local >= 0) & (local < held)
                key = jnp.where(here, local, held)
                counts = jax.lax.dynamic_slice_in_dim(counts, first, held)
                here = here.reshape(t, k)
                gate = jnp.where(here, gate, 0.0)
                self.sow("intermediates", "moe_held",
                         jnp.sum(counts) / (t * k))
            order = jnp.argsort(key, stable=True)
            inv = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
            # the grouped matmul on the chip neither reads nor writes a
            # row that is in no group, in either direction (its time
            # follows the groups: PERF.md §6, PR 36), so past the groups
            # its output and its cotangent hold whatever the buffer held,
            # NaN for all anyone knows. No pass over the buffer zeroes
            # that tail: `here` masks each of the two where its gather
            # brings it back to token order (`_dispatch_rows`' backward;
            # `moe_combine`)
            rows = _dispatch_rows(xf, order, inv, here, k)  # (T*k, d)
        with jax.named_scope("moe_experts"):
            if cfg.experts_one_cotangent:
                w_gate, w_up, w_down = (t.astype(cfg.dtype) for t in tables)
                out = jax.lax.ragged_dot(
                    _swiglu_rows(rows, w_gate, w_up, counts, cfg.remat),
                    w_down, counts)
            else:
                out = _expert_ffn(
                    rows, tables,
                    lambda a, w: jax.lax.ragged_dot(a, w, counts), cfg.dtype)
        with jax.named_scope("moe_combine"):
            # to token order once, forward. The backward stays in expert
            # order: d(out) is dy gathered as the dispatch gathers x,
            # times the slot's gate (0 on an absent slot, so zeros go to
            # the tail), d(gate) a row's dot with the buffer, and only
            # those T x k scalars are un-permuted. A rematerialised
            # block's replay stops at the buffer: nothing reads `y`
            # again, and no (T, k, d) array is anyone's residual
            y = _combine_rows(out, gate, order, inv, here)
        if cfg.moe_shared:
            with jax.named_scope("moe_shared"):
                wide = cfg.moe_shared * width
                up = _dense(wide, ("embed", "mlp"), cfg, name="shared_up")(xf)
                sh = nn.silu(_dense(wide, ("embed", "mlp"), cfg,
                                    name="shared_gate")(xf)) * up
                y = y + _dense(cfg.d_model, ("mlp", "embed"), cfg,
                               name="shared_down")(sh)
        return y.astype(cfg.dtype).reshape(b, s, d)


def _ssm_a_log_init(key, shape, dtype=jnp.float32):
    """A = -exp(A_log) with A drawn from U[1, 16], as Mamba-2's."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _ssm_dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step size drawn log-uniformly from [1e-3, 0.1],
    as Mamba-2's."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (Dao & Gu 2024) as `GraniteMoeHybridMambaLayer`
    runs it, one B/C group: H heads of size P over a state of N.

        [z | xBC | dt] = u W_in                  d -> HP + (HP + 2N) + H
        xBC = silu(conv1d_causal_depthwise(xBC, k) + b_conv)
        [x | B | C] = xBC                        x as (H, P)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)       per head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        out = (RMSNorm(y * silu(z)) * w) W_out   the gate, then the norm
                                                 over all H*P features

    The recurrence runs in its chunked form (ops/ssd.py), the conv and
    the gate with its norm as ops/ssm_stages.py has them (a kernel each
    on a TPU). dt, A, the norm and the conv's sum are float32 inside."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from edl_tpu.ops import ssm_stages
        from edl_tpu.ops.ssd import ssd_scan
        cfg = self.cfg
        bsz, s, _ = u.shape
        h, p, n, width = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                          cfg.ssm_conv)
        inner, conv_dim = h * p, h * p + 2 * n
        with jax.named_scope("ssm_in_proj"):
            # z | xBC | dt, left whole: the two stages read their columns
            # out of it (ops/ssm_stages.py)
            zxbcdt = _dense(inner + conv_dim + h, ("embed", "mlp"), cfg,
                            name="in_proj")(u)
            dt = zxbcdt[..., inner + conv_dim:]
        # output t sums taps k of input t - (width - 1) + k
        taps = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (width, conv_dim))
        bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        # x twice, one for each of its two users (see `conv`)
        x, b, c, x_skip = ssm_stages.conv(zxbcdt, taps, bias, start=inner,
                                          sizes=(inner, n, n))
        x, x_skip = (t.reshape(bsz, s, h, p) for t in (x, x_skip))
        a_log = self.param("A_log", _ssm_a_log_init, (h,))
        dt_bias = self.param("dt_bias", _ssm_dt_bias_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        # `ssm_scan` is the scan's alone (ops/ssd.py opens it around its
        # forward and its backward), `ssm_conv` and the rest of
        # `ssm_gate_norm` the two stages' (ops/ssm_stages.py likewise);
        # the step sizes' softplus is elementwise work beside them
        with jax.named_scope("ssm_gate_norm"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        y = ssd_scan(x, dt, -jnp.exp(a_log.astype(jnp.float32)), b, c,
                     chunk=cfg.ssm_chunk)
        y = ssm_stages.gate_norm(y, x_skip, zxbcdt, skip,
                                 _NormScale(name="norm")(inner),
                                 eps=cfg.norm_eps)
        with jax.named_scope("ssm_out_proj"):
            out = _dense(cfg.d_model, ("mlp", "embed"), cfg,
                         name="out_proj")(y)
        return cfg.constrain(out, ("batch", "seq", "embed"))


# What a rematerialised block (`cfg.remat`) keeps beside its input: the
# values whose bytes are small against the time their replay takes. The
# flash forward's `o` and `lse` (ops/flash_attention.py names them where
# its backward's residuals are formed): with them the replay holds no
# forward kernel. The first half's result, as the mixer returns it: its
# output projection then is dead code in the replay, with or without a
# norm behind it (the residual stream `x + h` would not do: a sandwich
# norm's backward reads the projection's output). The second half's
# result, named only where a sandwich norm reads it (`Block`): without
# one the replay never needed it.
#
# Not kept, by what a GB of each buys at 2 x 8192 tokens on a v5e
# (doc/design_step.md): the mixer's `in_proj` output (279 MB a layer for
# 3.3 ms), `mlp_gate` / `mlp_up` (268 MB each for 3.0 ms), a gated
# attention's query / gate (134 MB for about 2 ms), the T x k-row expert
# buffers (537 MB), q (134 MB) and the key/value heads (17 MB each; the
# flash kernels take them unrepeated) for less than their projections,
# norms and rope cost: 11-17 ms a GB, against 21 for a half's result and
# 39-116 for `o`.
KEPT_MIXER_OUT, KEPT_MLP_OUT = "block_mixer_out", "block_mlp_out"
KEPT = (KEPT_O, KEPT_LSE, KEPT_MIXER_OUT, KEPT_MLP_OUT)


class Block(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"        # the mixer: | "mamba" | "sliding" | "full"
    experts: bool = True           # under cfg.moe: False keeps the dense MLP

    @nn.compact
    def __call__(self, x, train: bool = True):
        cfg = self.cfg
        # The module names are scopes of a device trace already
        # (`block<i>/attn/...`); `ln` and `mlp` group what has no module
        # of its own. Scopes are metadata: no parameter path changes.
        with jax.named_scope("ln"):
            h = _norm(cfg, "ln_attn")(x)
        if self.kind == "mamba":
            h = Mamba2Mixer(cfg, name="ssm")(h)
        elif cfg.kv_lora_rank:
            from edl_tpu.models.mla import LatentAttention
            h = LatentAttention(cfg, name="attn")(h, train)
        else:
            h = Attention(cfg, self.kind, name="attn")(h, train)
        h = checkpoint_name(h, KEPT_MIXER_OUT)
        if cfg.sandwich_norm:
            with jax.named_scope("ln"):
                h = _norm(cfg, "ln_attn_out")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        h = _scaled(h, cfg.residual_scale)
        x = x + h
        with jax.named_scope("ln"):
            h = _norm(cfg, "ln_mlp")(x)
        if cfg.moe and self.experts:
            with jax.named_scope("mlp"):
                h = MoEMLP(cfg, name="moe_mlp")(h)
        elif cfg.mlp_gated:
            with jax.named_scope("mlp"):
                gate = _dense(cfg.d_ff, ("embed", "mlp"), cfg,
                              name="mlp_gate")(h)
                up = _dense(cfg.d_ff, ("embed", "mlp"), cfg,
                            name="mlp_up")(h)
                h = cfg.constrain(nn.silu(gate) * up,
                                  ("batch", "seq", "mlp"))
                h = _dense(cfg.d_model, ("mlp", "embed"), cfg,
                           name="mlp_out")(h)
        else:
            with jax.named_scope("mlp"):
                h = _dense(cfg.d_ff, ("embed", "mlp"), cfg,
                           name="mlp_in")(h)
                h = nn.gelu(h)
                h = cfg.constrain(h, ("batch", "seq", "mlp"))
                h = _dense(cfg.d_model, ("mlp", "embed"), cfg,
                           name="mlp_out")(h)
        if cfg.sandwich_norm:
            h = checkpoint_name(h, KEPT_MLP_OUT)
            with jax.named_scope("ln"):
                h = _norm(cfg, "ln_mlp_out")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        h = _scaled(h, cfg.residual_scale)
        return x + h


class Transformer(nn.Module):
    """Causal LM: tokens (B, S) int32 -> logits (B, S, vocab) fp32."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False, noised=None,
                 mtp: bool = False):
        """return_hidden=True skips the lm_head and yields the final-LN
        hidden states (B, S, d) — the input of the streamed-vocab fused
        CE (ops/fused_xent.py), which reads the head kernel straight
        from the param tree. Init must use the default path so the
        lm_head params exist.

        Under cfg.block_length ``noised`` (B, S) is the rows' noised
        copy (the rows themselves where not given: init). Both copies
        run as one (B, 2S) batch, and what comes back, hidden states or
        logits, is the noised copy's (B, S): the clean copy never meets
        the head.

        Under cfg.mtp_layers ``mtp=True`` returns a pair: the above, and
        the same from the multi-token-prediction module (`_mtp_hidden`),
        hidden states or logits through the same head, place i's for
        token i + 2. The default leaves the module out (inference needs
        none of it); init runs it, so that its parameters exist."""
        cfg = self.cfg
        half = tokens.shape[1]
        if cfg.block_length:
            with jax.named_scope("blockdiff_assemble"):
                tokens = jnp.concatenate(
                    [tokens if noised is None else noised, tokens], axis=1)
        # Table axes use the dedicated (vocab_table, embed_table) logical
        # names: vocab stays unsharded so the token gather partitions
        # trivially (no involuntary table rematerialization), embed splits
        # over tp. See sharding.DEFAULT_RULES.
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab_table", "embed_table")),
            name="tok_embed")
        pos_embed = self.param(
            "pos_embed",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("seq", "embed")),
            (cfg.max_len, cfg.d_model)) if cfg.pos == "learned" else None
        with jax.named_scope("embed"):
            x = embed(tokens)
            x = _scaled(x, cfg.embed_scale)
            if pos_embed is not None:
                x = x + pos_embed[None, :tokens.shape[1]].astype(cfg.dtype)
        x = cfg.constrain(x, ("batch", "seq", "embed"))
        block = remat_block(cfg)
        for i in range(cfg.n_layers):
            x = block(cfg, cfg.kind(i), cfg.moe_layer(i),
                      name=f"block{i}")(x, train)
        if cfg.block_length:
            with jax.named_scope("blockdiff_assemble"):
                x = x[:, :half]
        with jax.named_scope("ln"):
            x = _norm(cfg, "ln_final")(x)
        z = None
        if cfg.mtp_layers and (mtp or self.is_initializing()):
            z = _mtp_hidden(cfg, embed, x, tokens, train)
        if return_hidden:
            return (x, z) if mtp else x
        if cfg.tie_embeddings:
            # the head is the embedding table: logits = h E^T / scale
            with jax.named_scope("lm_head"):
                return jnp.einsum(
                    "bsd,vd->bsv", x.astype(jnp.float32),
                    embed.embedding.astype(jnp.float32)) / cfg.logits_scale
        # Tied-untied head: separate projection, fp32 logits for stable CE.
        head = nn.DenseGeneral(
            cfg.vocab_size, axis=-1, dtype=jnp.float32, use_bias=False,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("embed", "vocab")),
            name="lm_head")
        return (head(x), head(z)) if mtp else head(x)


def _model_of(apply_fn, aux_weight, z_weight) -> tuple:
    """(the config of the `Transformer` whose ``apply`` this is, the
    (aux, z) weights of the routers' terms). The config is None for any
    other callable; the weights are a keyword given, else a moe model's
    own, else None: the loss takes no such terms."""
    cfg = getattr(getattr(apply_fn, "__self__", None), "cfg", None)
    if aux_weight is None and cfg is not None and cfg.moe:
        aux_weight = cfg.moe_aux_weight
        z_weight = cfg.moe_z_weight if z_weight is None else z_weight
    return cfg, None if aux_weight is None else (aux_weight, z_weight or 0.0)


def _run(state, apply_fn, params, tokens, moe, **kw) -> tuple:
    """(what ``apply_fn`` returns on the model's variables, what it
    mutated: the routers' sown terms where a loss takes them, and the
    state no gradient trains, `state.batch_stats`, where there is any)."""
    variables, mutable = {"params": params}, []
    if moe:
        mutable.append("intermediates")
    stats = getattr(state, "batch_stats", None)
    if stats is not None:
        variables["batch_stats"] = stats
        mutable.append("batch_stats")
    if not mutable:
        return apply_fn(variables, tokens, train=True, **kw), None
    return apply_fn(variables, tokens, train=True, mutable=mutable, **kw)


def _with_router_terms(ce, mutated, weights) -> tuple[jax.Array, dict]:
    """(loss, metrics) of either LM loss from its cross-entropy. The
    new `batch_stats` ride the metrics to `make_train_step`, which
    folds them into the state."""
    metrics = {"ppl": jnp.exp(ce)}
    if mutated is not None and "batch_stats" in mutated:
        metrics["batch_stats"] = mutated["batch_stats"]
    if weights is None:
        return ce, metrics
    extra, counters = _moe_terms(mutated, *weights)
    return ce + extra.astype(ce.dtype), {**metrics, **counters}


def lm_loss_fn(state, params, batch, *, aux_weight: float | None = None,
               z_weight: float | None = None, apply_fn=None):
    """Causal LM loss for {'tokens': (B,S)} batches (next-token CE). On
    a moe=True model (found on the bound ``apply_fn``) the routers'
    auxiliary terms (`_moe_terms`) are added with the weights of its
    config, and the metrics carry the step line's `moe_*` counters;
    ``aux_weight`` / ``z_weight`` override the weights. ``apply_fn``
    overrides state.apply_fn when the loss must run a DIFFERENT model
    binding than the state was built with (the manual-dispatch path
    rebinds cfg.moe_wire without touching the params)."""
    apply_fn = apply_fn or state.apply_fn
    cfg, moe = _model_of(apply_fn, aux_weight, z_weight)
    if cfg is not None and cfg.block_length:
        from edl_tpu.models import blockdiff
        noised, weights = blockdiff.noised_batch(batch, cfg.mask_id)
        logits, mutated = _run(state, apply_fn, params, batch["tokens"],
                               moe, noised=noised)
        ll = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                 batch["tokens"][..., None], axis=-1)[..., 0]
        return blockdiff.with_masked_share(_with_router_terms(
            -jnp.sum(weights * ll), mutated, moe), batch)
    mtp = {"mtp": True} if cfg is not None and cfg.mtp_layers else {}
    logits, mutated = _run(state, apply_fn, params, batch["tokens"], moe,
                           **mtp)
    if mtp:
        logits, mtp_logits = logits
    targets = batch["tokens"][:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    out = _with_router_terms(-jnp.mean(ll), mutated, moe)
    if mtp:  # place i's logits against token i + 2
        ll = jnp.take_along_axis(
            jax.nn.log_softmax(mtp_logits[:, :-2]),
            batch["tokens"][:, 2:, None], axis=-1)[..., 0]
        out = _with_mtp(cfg, out, -jnp.mean(ll))
    return out


def lm_loss_fused(state, params, batch, *, block_rows: int | None = None,
                  aux_weight: float | None = None,
                  z_weight: float | None = None, apply_fn=None):
    """lm_loss_fn without the (B,S,V) logits tensor: hidden states feed
    the streamed CE (ops/fused_xent.py), which reads the lm_head
    kernel from the param tree and makes the loss and its gradient in
    one sweep. Numerically equivalent to lm_loss_fn, the routers' terms
    of a moe=True model and the keywords included; use for large-vocab
    models where the logits dominate memory. ``block_rows`` (tests)
    sets the rows of a block, which else follow from the shapes.

    Mesh note: intended for dp/fsdp worlds (kernel replicated or sharded
    on the embed dim): the model's config, found on the bound
    ``apply_fn``, runs the op on each chip's share of the batch
    (`TransformerConfig.xent`). Under tp the head kernel is sharded on
    the VOCAB dim, and a block's whole-vocabulary matmul would make XLA
    gather the full table — use the dense lm_loss_fn there (its
    vocab-parallel softmax partitions cleanly)."""
    from edl_tpu.ops.fused_xent import streamed_lm_xent

    apply_fn = apply_fn or state.apply_fn
    cfg, moe = _model_of(apply_fn, aux_weight, z_weight)
    if cfg is not None and cfg.block_length:
        # the noised copy's hidden states against the clean tokens at the
        # same places, each under its own weight: no shift, no row left out
        from edl_tpu.models import blockdiff
        noised, weights = blockdiff.noised_batch(batch, cfg.mask_id)
        hidden, mutated = _run(state, apply_fn, params, batch["tokens"],
                               moe, return_hidden=True, noised=noised)
        return blockdiff.with_masked_share(_with_router_terms(
            cfg.xent(hidden, params["lm_head"]["kernel"], batch["tokens"],
                     block_rows, weights=weights), mutated, moe), batch)
    mtp = {"mtp": True} if cfg is not None and cfg.mtp_layers else {}
    hidden, mutated = _run(state, apply_fn, params, batch["tokens"], moe,
                           return_hidden=True, **mtp)
    if mtp:
        hidden, mtp_hidden = hidden
    tokens = batch["tokens"]
    # a sequence's last position has no next token: a row of weight 0,
    # so the (B, S, d) hidden states go in as they are
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    if cfg is not None and cfg.tie_embeddings:
        # the head is the embedding table (V, d), vocabulary-major as
        # the sweep holds its blocks; the logits' divisor goes onto the
        # hidden states, which the sweep reads in float32
        kernel = params["tok_embed"]["embedding"].T
        hidden = _scaled(hidden, 1.0 / cfg.logits_scale)
    else:
        kernel = params["lm_head"]["kernel"]
    xent = cfg.xent if cfg is not None else streamed_lm_xent
    out = _with_router_terms(xent(hidden, kernel, targets, block_rows),
                             mutated, moe)
    if mtp:
        # the same sweep again on the same head kernel: place i's state
        # of the module against token i + 2, the last two places of a
        # row at weight 0
        with jax.named_scope("mtp"):
            out = _with_mtp(cfg, out, xent(mtp_hidden, kernel, jnp.concatenate(
                [tokens[:, 2:], jnp.full_like(tokens[:, :2], -1)], axis=1),
                block_rows))
    return out


def _sown(intermediates, name: str) -> list:
    """Collect every `self.sow`-ed value called ``name`` in a
    variables['intermediates'] tree (one per MoE block)."""
    from jax.tree_util import tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(intermediates)
    return [v for path, v in leaves
            if any(getattr(kk, "key", None) == name for kk in path)]


def _moe_terms(mutated, aux_weight: float, z_weight: float
               ) -> tuple[jax.Array, dict]:
    """(aux_weight * balance + z_weight * z, the step line's counters)
    from what the MoE blocks sowed.

    ``balance`` is 1.0 at perfect balance: E * sum_e f_e * p_e with f
    the share of the T*k assignments an expert got and p its mean
    router probability. The dropless layers sow f and p as vectors, and
    they are pooled over the layers before the product, as the
    published `load_balancing_loss_func` pools the tokens of all layers
    (which is top_k times this: `olmoe_config` folds that into the
    weight). The capacity router sows one scalar a layer, averaged."""
    inter = mutated.get("intermediates", {})
    zero = jnp.zeros((), jnp.float32)

    def mean(name):
        got = _sown(inter, name)
        return jnp.mean(jnp.stack(got), 0) if got else zero
    metrics = {"moe_dropped": mean("moe_dropped")}
    frac = _sown(inter, "moe_frac")
    extra = zero
    if frac and _sown(inter, "moe_probs"):
        frac, p = jnp.stack(frac), mean("moe_probs")     # (layers, E), (E,)
        e = p.shape[0]
        metrics.update(
            moe_balance=e * jnp.sum(jnp.mean(frac, 0) * p),
            moe_z=mean("moe_z"),
            # the fullest expert's tokens over the mean, worst layer
            moe_max_load=e * jnp.max(frac))
        extra = aux_weight * metrics["moe_balance"] \
            + z_weight * metrics["moe_z"]
    elif frac:  # sigmoid scores: no term of theirs is in any loss
        frac = jnp.stack(frac)
        metrics["moe_max_load"] = frac.shape[1] * jnp.max(frac)
    else:
        metrics["moe_balance"] = mean("moe_aux")
        extra = aux_weight * metrics["moe_balance"]
    if _sown(inter, "moe_held"):
        # the share of the T*k assignments on experts this chip holds
        metrics["moe_held"] = mean("moe_held")
    return extra, metrics


def olmoe_config(*, vocab_size: int = 50304, d_model: int = 2048,
                 n_heads: int = 16, n_layers: int = 16, d_ff: int = 1024,
                 max_len: int = 4096, n_experts: int = 64,
                 moe_top_k: int = 8, **kw) -> TransformerConfig:
    """OLMoE-1B-7B (arXiv:2409.02060; `model_type: olmoe`): RMSNorm
    pre-norm (eps 1e-5), RoPE (theta 10000), RMSNorm on q and k, 64
    SwiGLU experts of width ``d_ff``, 8 a token, gates not renormalised,
    nothing dropped. The sizes default to the published ones. The
    source's load-balance term is top_k at perfect balance where
    `_moe_terms`'s is 1, so its coefficient 0.01 is held as
    0.01 * top_k; 0.001 is the paper's z-loss weight."""
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=max_len, norm="rmsnorm",
        norm_eps=1e-5, pos="rope", rope_theta=10000.0, qk_norm=True,
        moe=True, moe_gated=True, moe_renorm=False, n_experts=n_experts,
        moe_top_k=moe_top_k, moe_aux_weight=0.01 * moe_top_k,
        moe_z_weight=0.001, **kw)


def granite_hybrid_config(*, vocab_size: int = 100352, d_model: int = 2048,
                          n_heads: int = 32, n_layers: int = 40,
                          d_ff: int = 8192, max_len: int = 131072,
                          n_kv_heads: int = 8, layer_types=None,
                          ssm_heads: int = 64, ssm_head_dim: int = 64,
                          ssm_state: int = 128, ssm_conv: int = 4,
                          ssm_chunk: int = 256, **kw) -> TransformerConfig:
    """granite-4.0-h-micro (`model_type: granitemoehybrid`, no experts):
    RMSNorm pre-norm (eps 1e-5), no positions at all, Mamba-2 mixers
    with grouped-query attention at every tenth layer (5, 15, 25, 35
    of 40: ``layer_types`` where not given), a dense SwiGLU MLP, a tied
    head, and the four multipliers: embedding x 12, logits / 8, residual
    branches x 0.22, attention scores x 1/64 (not 1/sqrt(64)). ``n_layers`` cuts the published pattern from its
    start, so a depth of ten is one whole period; the sizes default to
    the published ones."""
    if layer_types is None:
        layer_types = tuple(
            "attention" if i % 10 == 5 else "mamba"
            for i in range(n_layers))
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=max_len, norm="rmsnorm",
        norm_eps=1e-5, pos="none", n_kv_heads=n_kv_heads,
        attn_scale=0.015625, mlp_gated=True, tie_embeddings=True,
        embed_scale=12.0, logits_scale=8.0, residual_scale=0.22,
        layer_types=tuple(layer_types), ssm_heads=ssm_heads,
        ssm_head_dim=ssm_head_dim, ssm_state=ssm_state, ssm_conv=ssm_conv,
        ssm_chunk=ssm_chunk, **kw)


def afmoe_config(*, vocab_size: int = 200192, d_model: int = 2048,
                 n_heads: int = 32, n_layers: int = 32, d_ff: int = 6144,
                 max_len: int = 131072, n_kv_heads: int = 4,
                 head_size: int = 128, window: int = 2048,
                 layer_types=None, n_dense_layers: int = 2,
                 moe_d_ff: int = 1024, n_experts: int = 128,
                 moe_top_k: int = 8, experts_held: int = 0,
                 experts_offset: int = 0, **kw) -> TransformerConfig:
    """Trinity-Mini (arcee-ai, `model_type: afmoe`): RMSNorm (eps 1e-5)
    before and after the mixer and the MLP, embedding x sqrt(d_model),
    an untied head; grouped-query attention with a head size of its
    own, RMSNorm on q and k over each head, an output gate, three layers
    over a window of 2,048 keys with RoPE (theta 10,000) to one over
    every key with no positions (``layer_types`` where not given);
    ``n_dense_layers`` leading dense SwiGLU layers of width ``d_ff``,
    then experts of width ``moe_d_ff``: sigmoid scores, top-8 of 128
    over score + bias, gates renormalised and x 2.826, one shared
    expert, the bias moved by 0.001 a step, and no auxiliary loss.
    ``experts_held`` / ``experts_offset`` give a chip its share of the
    experts (0 = all); ``n_layers`` cuts the published pattern from its
    start. The sizes default to the published ones."""
    if layer_types is None:
        layer_types = tuple("full" if i % 4 == 3 else "sliding"
                            for i in range(n_layers))
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=max_len, norm="rmsnorm",
        norm_eps=1e-5, pos="rope", rope_theta=10000.0,
        n_kv_heads=n_kv_heads, head_size=head_size, window=window,
        layer_types=tuple(layer_types), qk_norm_heads=True, attn_gate=True,
        sandwich_norm=True, mlp_gated=True, embed_scale=d_model ** 0.5,
        moe=True, moe_gated=True, n_dense_layers=n_dense_layers,
        moe_d_ff=moe_d_ff, n_experts=n_experts, moe_top_k=moe_top_k,
        moe_score="sigmoid", moe_route_scale=2.826, moe_shared=1,
        moe_bias_rate=0.001, moe_aux_weight=0.0, moe_z_weight=0.0,
        experts_held=experts_held, experts_offset=experts_offset, **kw)


def sdar_config(*, vocab_size: int = 151936, d_model: int = 2048,
                n_heads: int = 32, n_layers: int = 48, d_ff: int = 768,
                max_len: int = 32768, n_kv_heads: int = 4,
                head_size: int = 128, n_experts: int = 128,
                moe_top_k: int = 8, experts_held: int = 0,
                experts_offset: int = 0, block_length: int = 4,
                **kw) -> TransformerConfig:
    """SDAR-30B-A3B-Chat (JetLM, `model_type: sdar_moe`; arXiv:2510.06303),
    the Qwen3-MoE block trained by diffusion over blocks (BD3-LMs,
    arXiv:2503.09573): RMSNorm pre-norm (eps 1e-6), RoPE (theta 1e6),
    grouped-query attention with a head size of its own and RMSNorm on q
    and k over each head, no biases, an untied head; 128 SwiGLU experts
    of width ``d_ff``, softmax scores, top-8, the kept gates
    renormalised, no shared expert, no router term in the loss.
    ``block_length`` is the objective's (`TransformerConfig.
    block_length`; the family's released chat models generate in blocks
    of 4); ``experts_held`` / ``experts_offset`` give a chip its share of
    the experts (0 = all). The sizes default to the published ones."""
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=max_len, norm="rmsnorm",
        norm_eps=1e-6, pos="rope", rope_theta=1000000.0,
        n_kv_heads=n_kv_heads, head_size=head_size, qk_norm_heads=True,
        moe=True, moe_gated=True, moe_renorm=True, n_experts=n_experts,
        moe_top_k=moe_top_k, moe_aux_weight=0.0, moe_z_weight=0.0,
        experts_held=experts_held, experts_offset=experts_offset,
        block_length=block_length, **kw)


def joyai_config(*, vocab_size: int = 129280, d_model: int = 2048,
                 n_heads: int = 32, n_layers: int = 40, d_ff: int = 7168,
                 max_len: int = 131072, n_dense_layers: int = 1,
                 moe_d_ff: int = 768, n_experts: int = 256,
                 moe_top_k: int = 8, experts_held: int = 0,
                 experts_offset: int = 0, q_lora_rank: int = 1536,
                 kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                 qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                 mtp_layers: int = 1, **kw) -> TransformerConfig:
    """JoyAI-LLM-Flash (jdopensource, `model_type: joyai_llm_flash`;
    DeepSeek-V3's block, arXiv:2412.19437, at smaller sizes): RMSNorm
    pre-norm (eps 1e-6), no biases, an untied head; latent attention
    (models/mla.py: queries through a latent of 1,536, keys and values
    through one of 512, 32 heads of 128 + 64 for q and k and 128 for v,
    RoPE theta 32e6 by interleaved pairs on the 64 alone); one leading
    dense SwiGLU layer of width ``d_ff``, then 256 SwiGLU experts of
    width ``moe_d_ff``: sigmoid scores, top-8 over score + bias, gates
    renormalised and x 2.5, one shared expert, the bias moved by 0.001
    a step, no auxiliary loss; one multi-token-prediction module whose
    loss is added x 0.3. ``experts_held`` / ``experts_offset`` give a
    chip its share of the experts (0 = all). The sizes default to the
    published ones; the bias's rate and the module's weight are the
    paper's, the config.json holds neither."""
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=max_len, norm="rmsnorm",
        norm_eps=1e-6, pos="rope", rope_theta=32000000.0,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        mlp_gated=True, moe=True, moe_gated=True,
        n_dense_layers=n_dense_layers, moe_d_ff=moe_d_ff,
        n_experts=n_experts, moe_top_k=moe_top_k, moe_score="sigmoid",
        moe_route_scale=2.5, moe_shared=1, moe_bias_rate=0.001,
        moe_aux_weight=0.0, moe_z_weight=0.0, experts_held=experts_held,
        experts_offset=experts_offset, mtp_layers=mtp_layers,
        mtp_weight=0.3, **kw)


def choose_remat(cfg: TransformerConfig, batch_size: int,
                 seq_len: int | None = None,
                 hbm_bytes: int | None = None,
                 budget_frac: float = 0.6) -> bool:
    """Autotuned remat knob: does the backward's activation footprint
    fit, or should blocks be checkpointed?

    Pure arithmetic over the config (deterministic, testable): the
    no-remat backward keeps every block's saved activations live at
    once — roughly 12 d_model-wide tensors per block (embeddings, qkv,
    attn out, both mlp halves), plus the (heads, S, S) score matrix
    when attention is dense, plus under moe the k-fold expert rows —
    while remat keeps ONE block's worth and
    recomputes the rest. If the no-remat estimate exceeds
    ``budget_frac`` of what is left after params + fp32 moments, remat
    pays its ~30% recompute FLOPs. ``hbm_bytes`` defaults to the
    backend device's reported memory; a TPU that reports none is an
    error, and the CPU harness (which reports none) is sized as one
    16 GiB chip so tests decide as a v5e would.
    """
    # under block_length a row is two copies' positions
    seq = (seq_len or cfg.max_len) * (2 if cfg.block_length else 1)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    per_block = 12 * batch_size * seq * cfg.d_model * itemsize
    ffn = 2 * cfg.d_model * cfg.d_ff
    if cfg.moe:
        # every token's row is held once per chosen expert: gathered
        # input and output (d_model wide), the hidden products (d_ff)
        tables = 3 if cfg.moe_gated else 2
        per_block += batch_size * seq * cfg.moe_top_k * itemsize * (
            2 * cfg.d_model + tables * cfg.d_ff)
        ffn = cfg.n_experts * tables * cfg.d_model * cfg.d_ff \
            + cfg.d_model * cfg.n_experts
    if cfg.attention == "dense" or (
            cfg.attention == "auto" and not cfg.use_ring
            and jax.default_backend() != "tpu"):
        per_block += batch_size * cfg.n_heads * seq * seq * itemsize
    activations = cfg.n_layers * per_block
    n_params = (cfg.vocab_size * cfg.d_model * 2          # embed + head
                + cfg.max_len * cfg.d_model * (cfg.pos == "learned")
                + cfg.n_layers * (4 * cfg.d_model ** 2 + ffn))
    resident = n_params * (4 + 8)                          # fp32 + adam
    if hbm_bytes is None:
        dev = jax.devices()[0]
        hbm_bytes = (dev.memory_stats() or {}).get("bytes_limit")
        if hbm_bytes is None:
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev.device_kind} reports no bytes_limit: pass "
                    "hbm_bytes, remat cannot be sized from a guess")
            hbm_bytes = 16 * (1 << 30)
    return activations > budget_frac * max(hbm_bytes - resident,
                                           hbm_bytes // 8)


def kept_bytes(cfg: TransformerConfig, batch_size: int,
               seq_len: int | None = None) -> dict[str, int]:
    """Bytes a step that a rematerialised model holds under each name of
    `KEPT`, beside its blocks' inputs, for ``batch_size`` sequences:
    arithmetic over the config, as `choose_remat` is. A name that no
    layer of this model carries reads 0 (`o` and `lse` where attention
    does not run through ops/flash_attention.py)."""
    seq = seq_len or cfg.max_len
    itemsize = jnp.dtype(cfg.dtype).itemsize
    # under block_length a row is two copies, each with its own call
    rows = batch_size * seq * (2 if cfg.block_length else 1)
    flash = 0       # layers whose attention is a flash call, as `Attention`
    layers = cfg.n_layers + cfg.mtp_layers  # the module holds a block
    if cfg.block_length or (not cfg.use_ring and cfg.use_flash(seq)):
        flash = sum(cfg.kind(i) != "mamba" for i in range(cfg.n_layers)) \
            + cfg.mtp_layers
    half = layers * rows * cfg.d_model * itemsize
    return {KEPT_O: flash * rows * cfg.n_heads * cfg.value_head_dim
            * itemsize,
            KEPT_LSE: flash * rows * cfg.n_heads * 4,
            KEPT_MIXER_OUT: half,
            KEPT_MLP_OUT: half if cfg.sandwich_norm else 0}


def auto_remat(cfg: TransformerConfig, batch_size: int,
               seq_len: int | None = None,
               hbm_bytes: int | None = None) -> TransformerConfig:
    """cfg with ``remat`` set by :func:`choose_remat` (no-op when the
    estimate says activations fit)."""
    import dataclasses

    return dataclasses.replace(
        cfg, remat=choose_remat(cfg, batch_size, seq_len, hbm_bytes))


def _rope(cfg: TransformerConfig, x, positions=None):
    """`rope` as the blocks apply it: one pass in `ops/rope.py`'s kernel
    where its rule takes x (a TPU, heads of 128 lanes, nothing sharded
    by the mesh), the formula above everywhere else. Down here because
    the lines above a kernel's call are in the compile cache's key
    (ROADMAP S13)."""
    from edl_tpu.ops import rope as kernel
    if rows := kernel.rows_for(x, cfg.mesh):
        return kernel.rotate(x, cfg.rope_theta, positions, rows)
    return rope(x, cfg.rope_theta, positions)


def _combine_rows_fwd(out, gate, order, inv, here):
    return _combine_rows(out, gate, order, inv, here), (
        out, gate, order, inv, here)


def _rows_to(places, values):
    """``values`` with element i moved to ``places[i]``, a permutation:
    values[inverse of places], made by a sort on ``places``. XLA's gather
    on a TPU goes index by index (0.93 ms for the T*k = 131,072 scalars
    of a layer on a v5e, the time of a gather of as many 4 KB rows from
    fast memory); its sort of as many pairs takes under 0.3."""
    return jax.lax.sort((places, values), num_keys=1)[1]


def _combine_rows_bwd(res, dy):
    """From dy (T, d), in expert order throughout: dy's rows gathered as
    `_dispatch_rows` gathers x's (a (T, d) source), d(out) their product
    with the slots' gates, d(gate) their row-wise dot with the buffer.
    Past the groups the buffer may hold NaN and so may that dot: the
    select comes after its T*k scalars are back in token order."""
    out, gate, order, inv, here = res
    k = gate.shape[1]
    rows = dy.astype(out.dtype)[order // k]                 # (T*k, d)
    held = gate if here is None else jnp.where(here, gate, 0)
    d_out = _rows_to(inv, held.reshape(-1))[:, None] * rows.astype(
        jnp.float32)
    d_gate = jnp.sum(out.astype(jnp.float32) * rows.astype(jnp.float32), -1)
    d_gate = _rows_to(order, d_gate).reshape(gate.shape)
    if here is not None:
        d_gate = jnp.where(here, d_gate, 0)
    return (d_out.astype(out.dtype), d_gate.astype(gate.dtype),
            None, None, None)


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def remat_block(cfg: TransformerConfig):
    """`Block`, rematerialised under `KEPT` where the config says so."""
    if not cfg.remat:
        return Block
    return nn.remat(
        Block, static_argnums=(2,),
        policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


def _mtp_hidden(cfg: TransformerConfig, embed, h, tokens, train: bool):
    """What the multi-token-prediction module (models/mla.py) makes of
    the main model's output ``h``: place i is fed token i + 1's
    embedding, the tokens rolled left by one. A row's last place gets
    its first token, which no earlier place sees (causal) and whose own
    target has weight 0 in the losses."""
    from edl_tpu.models.mla import MTPModule
    with jax.named_scope("mtp"):
        e = _scaled(embed(jnp.roll(tokens, -1, axis=1)), cfg.embed_scale)
    return MTPModule(cfg, name="mtp")(h, e, train)


def _with_mtp(cfg: TransformerConfig, out: tuple, mtp_ce) -> tuple:
    """(loss, metrics) with the module's cross-entropy added at the
    config's weight, and on the step line beside it."""
    loss, metrics = out
    return (loss + cfg.mtp_weight * mtp_ce.astype(loss.dtype),
            {**metrics, "mtp_loss": mtp_ce})
