"""Ring attention — sequence/context parallelism over a mesh axis.

Net-new capability (the reference has none — SURVEY.md §5 "Long-context /
sequence parallelism: ABSENT"): shards the sequence dim of q/k/v over the
`sp` mesh axis and rotates k/v blocks around the ring with `ppermute` while
accumulating flash-style (running max / running denominator), so attention
over sequence length S costs O(S/n) memory per device and the k/v transfer
overlaps with the block matmuls riding ICI.

Algorithm (Liu et al., Ring Attention; blockwise softmax accumulation):
each of the n steps computes q_local x k_block^T on the MXU in fp32,
rescales the running (o, l, m) accumulators, then ppermutes the k/v block
to the next device. Causal masking uses global positions derived from
`axis_index`, so step blocks that are entirely in the future contribute
nothing (their probabilities underflow to 0 via the -1e30 mask constant).

Autodiff: implemented with `lax.scan` (reverse-differentiable); the
backward pass replays the ring in reverse via transposed ppermute, which
JAX derives automatically.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _local_ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          axis_name: str, causal: bool, scale: float
                          ) -> jax.Array:
    """Per-shard body under shard_map. q/k/v: (B, S_local, H, D)."""
    axis_size = lax.psum(1, axis_name)
    my_index = lax.axis_index(axis_name)
    s_local = q.shape[1]
    b, _, h, d = q.shape

    q32 = q.astype(jnp.float32)
    q_pos = my_index * s_local + jnp.arange(s_local)          # (S,)

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        src = (my_index - i) % axis_size                      # block origin
        kv_pos = src * s_local + jnp.arange(s_local)
        # (B, H, Sq, Sk) scores in fp32 — MXU matmul with fp32 accumulate.
        s = jnp.einsum("bqhd,bkhd->bhqk", q32,
                       k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])                     # (B,H,Sq,Sk)
        correction = jnp.exp(m - m_new)
        l = l * correction + jnp.sum(p, axis=-1)
        o = o * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        k_next = lax.ppermute(
            k_blk, axis_name,
            perm=[(j, (j + 1) % axis_size) for j in range(axis_size)])
        v_next = lax.ppermute(
            v_blk, axis_name,
            perm=[(j, (j + 1) % axis_size) for j in range(axis_size)])
        return (o, m_new, l, k_next, v_next), None

    o0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    m0 = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    (o, _, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(axis_size))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)     # (B,S,H,D)


def _local_ring_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool, scale: float
                      ) -> jax.Array:
    """Ring body whose per-block attention is the flash kernel.

    Each ring step runs `flash_attention_lse` on (q_local, kv_block) —
    O(S_local * flash_block) live memory instead of the dense body's
    S_local^2 score block — and merges the normalized partial outputs
    by their log-sum-exp weights (the exact blockwise-softmax combine).
    Global causality decides the block's kernel mode: past blocks are
    dense-allowed (causal=False), the diagonal block is causal, future
    blocks contribute nothing.
    """
    from edl_tpu.ops.flash_attention import flash_attention_lse

    axis_size = lax.psum(1, axis_name)
    my_index = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape

    def past(q, kb, vb):
        o, lse = flash_attention_lse(q, kb, vb, causal=False, scale=scale)
        # fp32 so all switch branches (incl. `future`) agree for bf16 io
        return o.astype(jnp.float32), lse

    def diag(q, kb, vb):
        o, lse = flash_attention_lse(q, kb, vb, causal=True, scale=scale)
        return o.astype(jnp.float32), lse

    def future(q, kb, vb):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.full((b, s_local, h), _NEG_INF, jnp.float32))

    def combine(o, lse, o_b, lse_b):
        o_b = o_b.astype(jnp.float32)
        m = jnp.maximum(lse, lse_b)
        safe = m > _NEG_INF / 2
        w1 = jnp.where(safe, jnp.exp(lse - m), 0.0)
        w2 = jnp.where(safe, jnp.exp(lse_b - m), 0.0)
        den = jnp.maximum(w1 + w2, 1e-30)
        o_new = (o * w1[..., None] + o_b * w2[..., None]) / den[..., None]
        lse_new = jnp.where(safe, m + jnp.log(den), m)
        return o_new, lse_new

    def step(carry, i):
        o, lse, k_blk, v_blk = carry
        src = (my_index - i) % axis_size
        case = jnp.where(src == my_index, 0,
                         jnp.where(src < my_index, 1, 2))
        if causal:
            o_b, lse_b = lax.switch(case, (diag, past, future),
                                    q, k_blk, v_blk)
        else:
            o_b, lse_b = past(q, k_blk, v_blk)
        o, lse = combine(o, lse, o_b, lse_b)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        return (o, lse, lax.ppermute(k_blk, axis_name, perm=perm),
                lax.ppermute(v_blk, axis_name, perm=perm)), None

    o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    lse0 = jnp.full((b, s_local, h), _NEG_INF, jnp.float32)
    (o, _, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                               jnp.arange(axis_size))
    return o.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, sp_axis: str = "sp",
                   batch_axes: Sequence[str] = ("dp", "fsdp"),
                   head_axis: str = "tp", causal: bool = True,
                   scale: float | None = None,
                   use_flash: bool = False) -> jax.Array:
    """Global-view ring attention. q/k/v: (B, S, H, D), S sharded on sp_axis.

    Call under jit with global arrays; shard_map splits them so each device
    holds its sequence block, heads additionally sharded over `head_axis`.
    `use_flash=True` runs the flash kernel per block pair (O(S_local*blk)
    memory instead of S_local^2; enable on TPU for long local blocks).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    batch = tuple(a for a in batch_axes
                  if a in mesh.axis_names and mesh.shape[a] > 1) or None
    heads = head_axis if (head_axis in mesh.axis_names
                          and mesh.shape[head_axis] > 1) else None
    spec = P(batch, sp_axis, heads)
    body = _local_ring_flash if use_flash else _local_ring_attention
    fn = functools.partial(body, axis_name=sp_axis,
                           causal=causal, scale=scale)
    from edl_tpu.parallel.compat import shard_map
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    window: int | None = None) -> jax.Array:
    """Plain (single-device / XLA-partitioned) reference attention.

    Used when the mesh has no sp axis, and as the numerical oracle in
    tests. Same fp32-accumulate contract as the ring path. `window`
    (causal only): a query sees its own key and the `window - 1` before.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = s.shape[-2], s.shape[-1]
        back = jnp.arange(s_q)[:, None] - jnp.arange(s_k)[None, :]
        mask = back >= 0 if window is None else (back >= 0) & (back < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)
