"""int8 gradient-bucket pack/unpack for the compressed DCN leg.

Two row-block-gridded Pallas passes over a flat gradient shard on TPU:
per-block abs-max partials (reduced to the one symmetric scale
outside), then round-to-nearest int8 under that scale. The grid keeps
each step's blocks a fixed size (`_BLOCK_ROWS` x 128 lanes), so any
bucket size fits the scoped VMEM limit; scalars ride SMEM. Everywhere
else the plain-XLA expression is used — interpret-mode Pallas is orders
of magnitude slower and this sits in the hot step (same split as
ops/flash_attention.py; `force_pallas_interpret()` is the test hook
that runs the kernel path on CPU to pin equivalence).

The wire format (what `train/comm.py` ships over DCN): int8 payload of
the shard + ONE fp32 scale. Symmetric around zero — no zero-point, so
dequantize is a single multiply and a zero gradient round-trips to
exactly zero. Error feedback upstream (comm._cross_int8) carries the
rounding error, so the format's bias is bounded by scale/2 per element
per step and reclaimed on later steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QMAX = 127.0
_LANE = 128         # TPU lane width: kernel operands reshape to (-1, 128)
# Rows per grid step. 1024 x 128 is 512 KiB of fp32 per operand: the
# widest kernel (quantized Adam: 2 fp32 + 4 int8 planes in, 3 fp32
# out, each double-buffered) stays under v5e's 16 MiB scoped-VMEM limit,
# and a multiple of the (32, 128) int8 tile.
_BLOCK_ROWS = 1024
_FORCE_INTERPRET = False


def force_pallas_interpret():
    """Test hook: route pack/unpack through the Pallas kernels in
    interpret mode on non-TPU backends (equivalence pinning only —
    interpret mode is far too slow for the hot step)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = True


def _use_pallas() -> bool:
    return _FORCE_INTERPRET or jax.default_backend() == "tpu"


# -- shared symmetric-int8 math (single source of truth) ---------------------
# Every int8 quantizer in the tree — this pack/unpack wire, the DGC int8
# value wire (train/dgc.py), and the fused-optimizer moment quantizer
# (ops/opt_kernels.py) — routes through these three expressions, so
# equivalence pinned here holds everywhere. All three are jnp-traceable
# and safe inside Pallas kernel bodies.


def scale_of_amax(amax: jnp.ndarray, qmax: float = _QMAX) -> jnp.ndarray:
    """fp32 scale mapping ``amax`` -> ``qmax``; 1.0 for an all-zero
    input so q == 0 and dequantize is exact."""
    return jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)


def symmetric_scale(x: jnp.ndarray) -> jnp.ndarray:
    """fp32 scale mapping |x|max -> 127 (see :func:`scale_of_amax`)."""
    return scale_of_amax(jnp.max(jnp.abs(x)))


def quantize_int8(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Round-to-nearest symmetric int8 under ``scale`` (no zero-point)."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -_QMAX, _QMAX).astype(jnp.int8)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8` — one fp32 multiply."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


# -- plain-XLA reference (the non-TPU hot path) ------------------------------


def _pack_xla(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    scale = symmetric_scale(x)
    return quantize_int8(x, scale), scale


# -- Pallas kernels ----------------------------------------------------------
# Shared with ops/opt_kernels.py: operands are (rows, 128) planes walked
# by a 1-D grid of row blocks; per-call scalars ride one SMEM vector;
# whole-plane abs-max comes out as one SMEM partial per block (max is
# exact, so reducing the partials outside equals the XLA reduction).


def row_block_call(name: str, kernel, scalars, planes, out_dtypes,
                   n_amax: int, interpret: bool) -> tuple:
    """Run ``kernel`` over the row blocks of ``planes`` ((rows, 128)
    each), as the Mosaic call ``name`` (what a device trace shows it
    under). The kernel sees: the SMEM vector of ``scalars`` (fp32; left
    out when there are none), a VMEM block per plane, a VMEM block per
    ``out_dtypes`` entry, then ``n_amax`` SMEM vectors holding one
    partial per grid step (write them with `block_amax`). Returns the
    output planes, then the ``n_amax`` whole-plane abs-maxes.

    A plane shorter than one block is a single full-extent block
    (always a legal block shape, whatever the dtype's tile)."""
    rows = planes[0].shape[0]
    blk = min(rows, _BLOCK_ROWS)
    steps = pl.cdiv(rows, blk)
    block = pl.BlockSpec((blk, _LANE), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    head = [jnp.stack(scalars)] if scalars else []
    outs = pl.pallas_call(
        functools.partial(kernel, rows=rows),
        grid=(steps,),
        in_specs=[smem] * len(head) + [block] * len(planes),
        out_specs=[block] * len(out_dtypes) + [smem] * n_amax,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), d)
                   for d in out_dtypes]
        + [jax.ShapeDtypeStruct((steps,), jnp.float32)] * n_amax,
        interpret=interpret,
        name=name,
    )(*head, *planes)
    n = len(out_dtypes)
    return tuple(outs[:n]) + tuple(jnp.max(a) for a in outs[n:])


def block_amax(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """abs-max of this grid step's block. The last block of a ragged
    plane reads past ``rows``; those rows hold garbage and are masked
    to zero (zero never wins an abs-max)."""
    if rows % x.shape[0]:
        row = (pl.program_id(0) * x.shape[0]
               + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
        x = jnp.where(row < rows, x, 0.0)
    return jnp.max(jnp.abs(x))


def _amax_kernel(x_ref, a_ref, *, rows):
    a_ref[pl.program_id(0)] = block_amax(
        x_ref[:].astype(jnp.float32), rows)


def _quantize_kernel(s_ref, x_ref, q_ref, *, rows):
    del rows
    q_ref[:] = quantize_int8(x_ref[:], s_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_pallas(x2d: jnp.ndarray, interpret: bool):
    (amax,) = row_block_call("pack_amax", _amax_kernel, [], [x2d], [], 1,
                             interpret)
    scale = scale_of_amax(amax)
    (q,) = row_block_call("pack_quantize", _quantize_kernel, [scale],
                          [x2d], [jnp.int8], 0, interpret)
    return q, scale


# -- public API --------------------------------------------------------------


def pack_int8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Flat float shard -> (int8 payload of the same shape, fp32 scale).

    Traceable (used inside jit/shard_map). Kernel path on TPU; the
    ragged tail past a multiple of the 128-lane width is padded with
    zeros for the kernel and sliced back off (zeros never win the
    abs-max, so padding cannot perturb the scale).
    """
    if not _use_pallas():
        return _pack_xla(x)
    n = x.shape[0] if x.ndim == 1 else int(np.prod(x.shape))
    flat = x.reshape(-1)
    pad = (-n) % _LANE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    q2d, scale = _pack_pallas(flat.reshape(-1, _LANE),
                              interpret=jax.default_backend() != "tpu")
    q = q2d.reshape(-1)[:n].reshape(x.shape)
    return q, scale


def unpack_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int8` (one multiply — no kernel needed;
    XLA fuses it into the consumer)."""
    return dequantize_int8(q, scale)


# -- shared collective wires --------------------------------------------------
# Every cross-chip int8 hop in the tree rides ONE of these two helpers,
# so the allreduce wire (train/comm._cross_int8, train/dgc.sparse_psum)
# and the MoE all-to-all wire (train/comm.moe_all_to_all) encode with
# the same scale/round math and cannot drift: the interpret-mode
# equivalence pin on pack_int8 covers them all. Both are for use INSIDE
# shard_map (they issue lax collectives over a named axis).


def all_gather_int8(x: jnp.ndarray, axis_name: str, *,
                    axis_index_groups=None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The int8 GATHER wire: pack -> all_gather(q, scale) -> dequantize.

    ``x`` is one chip's flat (1-D) float contribution. Returns
    ``(gathered, local)``: the (group, n) fp32 dequantized
    contributions of every chip in the group, and this chip's own
    dequantized round-trip (what error-feedback callers subtract to
    keep the quantization error local). Wire bytes per chip: n int8
    payload + one fp32 scale.
    """
    from jax import lax
    q, scale = pack_int8(x)
    all_q = lax.all_gather(q, axis_name,
                           axis_index_groups=axis_index_groups)
    all_s = lax.all_gather(scale, axis_name,
                           axis_index_groups=axis_index_groups)
    return (dequantize_int8(all_q, all_s[:, None]),
            dequantize_int8(q, scale))


def all_to_all_int8(x: jnp.ndarray, axis_name: str, *,
                    axis_index_groups=None) -> jnp.ndarray:
    """The int8 ALL-TO-ALL wire: per-destination-block pack ->
    all_to_all(q, scales) -> dequantize.

    ``x`` is destination-major: dim 0 enumerates the group's chips (or
    slices) and block ``x[i]`` is the payload bound for position ``i``
    of the group. Each block gets its OWN symmetric scale (blocks bound
    for different destinations have unrelated magnitudes — one global
    scale would crush the small ones), the int8 payloads and fp32
    scales ride the same all_to_all pattern, and the receiver
    dequantizes source-major blocks. Wire bytes per chip: the off-chip
    payload at 1 byte/element + one fp32 scale per off-chip block. No
    error feedback — activations are transient; callers bound the
    rounding error with a loss-parity gate instead (train/comm's MoE
    dispatch gates).
    """
    from jax import lax
    g = x.shape[0]
    packed = [pack_int8(x[i]) for i in range(g)]  # static unroll:
    # keeps the Pallas kernel path per block on TPU (vmap over a
    # pallas_call would fall back to interpret rules)
    q = jnp.stack([p[0] for p in packed])
    scale = jnp.stack([p[1] for p in packed])
    q_r = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                         tiled=True, axis_index_groups=axis_index_groups)
    s_r = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0,
                         tiled=True, axis_index_groups=axis_index_groups)
    return dequantize_int8(q_r, s_r.reshape((g,) + (1,) * (x.ndim - 1)))
