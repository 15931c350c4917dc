"""Fused optimizer-update kernels over flat parameter buckets.

One row-block-gridded Pallas pass per bucket: grad + param + moments
stream through VMEM a block at a time (ops/pack.py `row_grid`) and the
whole momentum-SGD / Adam update happens in registers, instead of XLA's
chain of elementwise HLOs. Quantized moments take two more passes per
moment plane, because each of a plane's two per-tensor scales is a
whole-bucket abs-max that must be known before anything is rounded
under it: the update pass emits the new moment and its per-block
abs-max, the requant pass rounds it and emits the residual and its
abs-max, the last pass rounds the residual. Buckets come from the same
planner as the DCN gradient path (train/comm.py plan_buckets): flat,
dtype-grouped, lane-padded buffers of any size.

Backend split mirrors ops/pack.py exactly: the kernel path runs on TPU
(or under `force_pallas_interpret()` in tests), everywhere else the
plain-XLA expression is used. Both paths are built from the SAME jnp
math helpers (`_sgdm_math`, `_adam_math`, the shared quantize helpers
in ops/pack.py), so interpret-mode kernel output is bitwise-identical
to the XLA fallback by construction — the equivalence the tests pin.

Quantized resident moments (`quant='int8'`/`'fp8'`): between steps a
moment plane lives as TWO int8 payloads + two fp32 scales per bucket —
the symmetric-int8 quantization of the moment itself, plus the
symmetric-int8 quantization of the rounding RESIDUAL (error feedback,
generalizing the r21 residual machinery in train/comm.py). Since
|residual| <= scale/2, the residual's own scale is <= scale/254: the
pair behaves like ~16-bit fixed precision while costing 2 bytes per
element (vs 4 for fp32 — the >= 1.8x resident/checkpoint/migration
byte cut), and the mass dropped per requant is second-order
(<= scale/508 per element). 'fp8' stores float8_e4m3fn bits BITCAST to
int8 at rest, so serialization and the tensor wire never see an fp8
dtype ("fp8-shaped on CPU via the int8 wire").
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from edl_tpu.ops.pack import (_LANE, block_amax, dequantize_int8,
                              quantize_int8, row_block_call, scale_of_amax)

_FORCE_INTERPRET = False

OPTIMIZERS = ("sgdm", "adam")
QUANT_MODES = ("off", "int8", "fp8")


def force_pallas_interpret():
    """Test hook: route the fused update through the Pallas kernels in
    interpret mode on non-TPU backends (equivalence pinning only)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = True


def _use_pallas() -> bool:
    return _FORCE_INTERPRET or jax.default_backend() == "tpu"


# -- moment codecs (fp8 rides the int8 wire) ---------------------------------

FP8_MAX = 448.0     # float8_e4m3fn finite max
_QMAX = {"int8": 127.0, "fp8": FP8_MAX}


def fp8_dtype():
    return jnp.float8_e4m3fn


def _quantize_fp8(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    f8 = (x.astype(jnp.float32) / scale).astype(fp8_dtype())
    return jax.lax.bitcast_convert_type(f8, jnp.int8)


def _dequantize_fp8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    f8 = jax.lax.bitcast_convert_type(q, fp8_dtype())
    return f8.astype(jnp.float32) * scale.astype(jnp.float32)


def _quantize(x, scale, quant: str):
    return (quantize_int8 if quant == "int8" else _quantize_fp8)(x, scale)


def _dequantize(q, scale, quant: str):
    return (dequantize_int8 if quant == "int8"
            else _dequantize_fp8)(q, scale)


def _scale(x: jnp.ndarray, quant: str) -> jnp.ndarray:
    return scale_of_amax(jnp.max(jnp.abs(x)), _QMAX[quant])


# -- quantized moment plane --------------------------------------------------


# Adam's SECOND moment always uses the fp8-e4m3 codec (bits still ride
# the int8 wire): v spans many orders of magnitude and sits under a
# sqrt in the update's denominator, so a LINEAR int8 grid zero-floors
# small entries — u = m/(sqrt(0)+eps) then explodes wherever the m
# plane still resolves the entry. An exponent format keeps ~6% relative
# precision across v's whole range; the first moment (gradient-like,
# error-feedback-friendly) stays on the mode's own codec.
V_QUANT = "fp8"


class QPlane(NamedTuple):
    """One moment plane at rest: value payload + error-feedback residual.

    q/rq are int8 (fp8 mode: float8 bits bitcast to int8); scale/rscale
    are fp32 scalars. Serializes as four ordinary array leaves — the
    (q, scale) pairs checkpoints/migration ship at half the fp32 bytes.
    """

    q: jnp.ndarray
    scale: jnp.ndarray
    rq: jnp.ndarray
    rscale: jnp.ndarray


def _dq2(q, scale, rq, rscale, quant: str) -> jnp.ndarray:
    """Reassemble the full-precision moment: payload + residual."""
    return _dequantize(q, scale, quant) + _dequantize(rq, rscale, quant)


def _rq2(m: jnp.ndarray, quant: str):
    """Requantize an updated moment; the rounding error becomes the new
    residual (itself quantized — that is what halves the bytes)."""
    scale = _scale(m, quant)
    q = _quantize(m, scale, quant)
    r = m - _dequantize(q, scale, quant)
    rscale = _scale(r, quant)
    return q, scale, _quantize(r, rscale, quant), rscale


def quant_plane(m: jnp.ndarray, quant: str) -> QPlane:
    """Full-precision moment -> resident QPlane."""
    return QPlane(*_rq2(m.astype(jnp.float32), quant))


def dequant_plane(plane: QPlane, quant: str) -> jnp.ndarray:
    """Resident QPlane -> full-precision moment (payload + residual)."""
    return _dq2(*plane, quant)


def zero_plane(n: int, quant: str) -> QPlane:
    """Quantized zero moment (exact: symmetric format round-trips 0)."""
    del quant  # both codecs encode zero as q=0, scale=1
    return QPlane(q=jnp.zeros((n,), jnp.int8),
                  scale=jnp.ones((), jnp.float32),
                  rq=jnp.zeros((n,), jnp.int8),
                  rscale=jnp.ones((), jnp.float32))


# -- optimizer math (the single source of truth for BOTH backends) ----------
# Expression order matters: the momentum-SGD chain is written to be
# bitwise-identical to optax.chain(add_decayed_weights(wd),
# sgd(lr, momentum=mu)) + optax.apply_updates (tests pin it); Adam
# matches optax.adamw's expression order with bias-correction factors
# (c1, c2) precomputed outside and eps_root=0.


def _sgdm_math(p, g, m, lr, mu: float, wd: float):
    if wd:
        g = g + wd * p
    m_new = g + mu * m
    p_new = p + m_new * (-lr)
    return p_new, m_new


def _adam_math(p, g, m, v, lr, c1, c2, b1: float, b2: float,
               eps: float, wd: float):
    # v >= +0.0 exactly on the fp32 path (so the clamp is bitwise-
    # neutral there); a dequantized v can carry a tiny negative
    # residual error, which must not reach the sqrt.
    v = jnp.maximum(v, 0.0)
    m_new = (1 - b1) * g + b1 * m
    v_new = (1 - b2) * (g * g) + b2 * v
    u = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if wd:
        u = u + wd * p
    p_new = p + u * (-lr)
    return p_new, m_new, v_new


# -- Pallas kernel bodies ----------------------------------------------------
# Every kernel is one step of a 1-D grid over row blocks
# (ops/pack.py `row_block_call`):
# s_ref is the SMEM vector of this call's fp32 scalars, the other refs
# are (block, 128) VMEM blocks, and an abs-max ref is the SMEM vector of
# per-block partials. Hyperparameters that never change per step (mu,
# b1, ...) are compile-time statics.


def _sgdm_kernel(s_ref, p_ref, g_ref, *refs, mu, wd, quant, rows):
    if quant == "off":
        m_ref, po_ref, mo_ref = refs
        m = m_ref[:]
    else:
        q_ref, rq_ref, po_ref, mo_ref, am_ref = refs
        m = _dq2(q_ref[:], s_ref[1], rq_ref[:], s_ref[2], quant)
    p_new, m_new = _sgdm_math(p_ref[:], g_ref[:], m, s_ref[0], mu, wd)
    po_ref[:] = p_new
    mo_ref[:] = m_new
    if quant != "off":
        am_ref[pl.program_id(0)] = block_amax(m_new, rows)


def _adam_kernel(s_ref, p_ref, g_ref, *refs, b1, b2, eps, wd, quant,
                 rows):
    if quant == "off":
        m_ref, v_ref, po_ref, mo_ref, vo_ref = refs
        m, v = m_ref[:], v_ref[:]
    else:
        (qm_ref, rqm_ref, qv_ref, rqv_ref, po_ref, mo_ref, vo_ref,
         am_ref, av_ref) = refs
        m = _dq2(qm_ref[:], s_ref[3], rqm_ref[:], s_ref[4], quant)
        v = _dq2(qv_ref[:], s_ref[5], rqv_ref[:], s_ref[6], V_QUANT)
    p_new, m_new, v_new = _adam_math(
        p_ref[:], g_ref[:], m, v, s_ref[0], s_ref[1], s_ref[2], b1, b2,
        eps, wd)
    po_ref[:] = p_new
    mo_ref[:] = m_new
    vo_ref[:] = v_new
    if quant != "off":
        am_ref[pl.program_id(0)] = block_amax(m_new, rows)
        av_ref[pl.program_id(0)] = block_amax(v_new, rows)


def _requant_kernel(s_ref, m_ref, q_ref, r_ref, ar_ref, *, quant, rows):
    m = m_ref[:]
    q = _quantize(m, s_ref[0], quant)
    r = m - _dequantize(q, s_ref[0], quant)
    q_ref[:] = q
    r_ref[:] = r
    ar_ref[pl.program_id(0)] = block_amax(r, rows)


def _quantize_kernel(s_ref, x_ref, q_ref, *, quant, rows):
    del rows
    q_ref[:] = _quantize(x_ref[:], s_ref[0], quant)


# -- jitted XLA fallbacks ----------------------------------------------------
# The fallback expressions are jitted so XLA applies the SAME fusion
# (notably fma contraction) whether the bucket update runs standalone
# (the parity gate) or inlined in a jitted train step — eager op-by-op
# execution would differ from the compiled kernel path by an ulp.


@functools.partial(jax.jit, static_argnames=("mu", "wd"))
def _sgdm_xla_fp32(p, g, m, lr, *, mu, wd):
    return _sgdm_math(p, g, m, lr, mu, wd)


@functools.partial(jax.jit, static_argnames=("mu", "wd", "quant"))
def _sgdm_xla_q(p, g, q, s, rq, rs, lr, *, mu, wd, quant):
    m = _dq2(q, s, rq, rs, quant)
    p_new, m_new = _sgdm_math(p, g, m, lr, mu, wd)
    return (p_new,) + _rq2(m_new, quant)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adam_xla_fp32(p, g, m, v, lr, c1, c2, *, b1, b2, eps, wd):
    return _adam_math(p, g, m, v, lr, c1, c2, b1, b2, eps, wd)


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "quant"))
def _adam_xla_q(p, g, qm, sm, rqm, rsm, qv, sv, rqv, rsv, lr, c1, c2,
                *, b1, b2, eps, wd, quant):
    m = _dq2(qm, sm, rqm, rsm, quant)
    v = _dq2(qv, sv, rqv, rsv, V_QUANT)
    p_new, m_new, v_new = _adam_math(p, g, m, v, lr, c1, c2, b1, b2,
                                     eps, wd)
    return (p_new,) + _rq2(m_new, quant) + _rq2(v_new, V_QUANT)


# -- pallas_call wrappers (jitted once per bucket shape) ---------------------


def _requant_pallas(m_new, amax, quant: str, interpret: bool) -> QPlane:
    """fp32 moment plane + its abs-max -> QPlane (the kernel-path twin
    of `_rq2`, same expressions under the same two scales)."""
    f32, i8 = jnp.float32, jnp.int8
    scale = scale_of_amax(amax, _QMAX[quant])
    q, r, rmax = row_block_call(
        "opt_requant", functools.partial(_requant_kernel, quant=quant),
        [scale], [m_new], [i8, f32], 1, interpret)
    rscale = scale_of_amax(rmax, _QMAX[quant])
    (rq,) = row_block_call(
        "opt_quantize", functools.partial(_quantize_kernel, quant=quant),
        [rscale], [r], [i8], 0, interpret)
    return QPlane(q=q, scale=scale, rq=rq, rscale=rscale)


@functools.partial(jax.jit,
                   static_argnames=("mu", "wd", "quant", "interpret"))
def _sgdm_pallas(p2, g2, m, lr, *, mu, wd, quant, interpret):
    f32 = jnp.float32
    kernel = functools.partial(_sgdm_kernel, mu=mu, wd=wd, quant=quant)
    if quant == "off":
        return row_block_call("opt_sgdm", kernel, [lr], [p2, g2, m],
                              [f32, f32], 0, interpret)
    p_new, m_new, amax = row_block_call(
        "opt_sgdm", kernel, [lr, m.scale, m.rscale],
        [p2, g2, m.q, m.rq], [f32, f32], 1, interpret)
    return p_new, _requant_pallas(m_new, amax, quant, interpret)


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "quant",
                                    "interpret"))
def _adam_pallas(p2, g2, m, v, lr, c1, c2, *, b1, b2, eps, wd, quant,
                 interpret):
    f32 = jnp.float32
    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                               wd=wd, quant=quant)
    if quant == "off":
        return row_block_call("opt_adam", kernel, [lr, c1, c2],
                              [p2, g2, m, v], [f32, f32, f32], 0,
                              interpret)
    p_new, m_new, v_new, m_amax, v_amax = row_block_call(
        "opt_adam", kernel,
        [lr, c1, c2, m.scale, m.rscale, v.scale, v.rscale],
        [p2, g2, m.q, m.rq, v.q, v.rq], [f32, f32, f32], 2, interpret)
    return (p_new, _requant_pallas(m_new, m_amax, quant, interpret),
            _requant_pallas(v_new, v_amax, V_QUANT, interpret))


# -- per-bucket public entry points ------------------------------------------
# p/g are flat fp32 bucket buffers whose length is a multiple of the
# 128-element lane width (plan_buckets(align=128) guarantees it; the
# zero padding is a fixed point of both updates, so it never drifts).


def _shaped(state, shape):
    """A moment state (fp32 buffer or QPlane) with its planes reshaped."""
    if isinstance(state, QPlane):
        return state._replace(q=state.q.reshape(shape),
                              rq=state.rq.reshape(shape))
    return state.reshape(shape)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sgdm_bucket(p, g, m_state, lr, *, mu: float, wd: float,
                quant: str = "off"):
    """Fused momentum-SGD update of one bucket.

    m_state: fp32 buffer (quant='off') or :class:`QPlane`. Returns
    (p_new, m_state_new) in the same representation.
    """
    lr = jnp.asarray(lr, jnp.float32)
    if _use_pallas():
        lanes = (-1, _LANE)
        p2, m_new = _sgdm_pallas(
            p.reshape(lanes), g.reshape(lanes), _shaped(m_state, lanes),
            lr, mu=mu, wd=wd, quant=quant, interpret=_interpret())
        return p2.reshape(p.shape), _shaped(m_new, p.shape)
    if quant == "off":
        return _sgdm_xla_fp32(p, g, m_state, lr, mu=mu, wd=wd)
    p_new, *plane = _sgdm_xla_q(p, g, *m_state, lr, mu=mu, wd=wd,
                                quant=quant)
    return p_new, QPlane(*plane)


def adam_bucket(p, g, m_state, v_state, lr, c1, c2, *, b1: float,
                b2: float, eps: float, wd: float, quant: str = "off"):
    """Fused Adam(W) update of one bucket.

    c1/c2 are the bias-correction denominators (1 - b^t), precomputed
    by the caller so kernel and XLA paths consume identical scalars.
    Returns (p_new, m_state_new, v_state_new).
    """
    lr = jnp.asarray(lr, jnp.float32)
    c1 = jnp.asarray(c1, jnp.float32)
    c2 = jnp.asarray(c2, jnp.float32)
    hyper = dict(b1=b1, b2=b2, eps=eps, wd=wd)
    if _use_pallas():
        lanes = (-1, _LANE)
        p2, m_new, v_new = _adam_pallas(
            p.reshape(lanes), g.reshape(lanes), _shaped(m_state, lanes),
            _shaped(v_state, lanes), lr, c1, c2, quant=quant,
            interpret=_interpret(), **hyper)
        return (p2.reshape(p.shape), _shaped(m_new, p.shape),
                _shaped(v_new, p.shape))
    if quant == "off":
        return _adam_xla_fp32(p, g, m_state, v_state, lr, c1, c2, **hyper)
    p_new, *planes = _adam_xla_q(p, g, *m_state, *v_state, lr, c1, c2,
                                 quant=quant, **hyper)
    return p_new, QPlane(*planes[:4]), QPlane(*planes[4:])
