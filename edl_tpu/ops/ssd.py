"""The state-space scan of a Mamba-2 layer in its chunked (SSD) form,
forward and backward.

Per head, with a scalar decay a_t = exp(dt_t * A) (A < 0), a state S in
R^{P x N} and one B/C group shared by the heads:

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t        S_0 = 0
    y_t = S_t C_t

Walking t one step at a time is 8,192 dependent steps of rank-1
updates; the chunked form (Dao & Gu 2024, "state space duality") cuts
the sequence into chunks of Q positions and turns the work into matrix
products. With cs_i the running sum of dt*A inside a chunk (inclusive),
u_j = dt_j x_j, and `prev` the state that enters the chunk:

    y_i  = sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) u_j    inside the chunk
         + exp(cs_i) prev C_i                             what came before
    S    = sum_j exp(cs_last - cs_j) u_j (x) B_j          the chunk's own state
    prev_z = sum_{c<z} exp(sum_{c<k<z} cs_last_k) S_c     across chunks

The first line is a masked (Q, Q) product per chunk and head, `(C B^T o
L) u`; the others are (Q, N) x (N, P) products and one small product
over the chunks. Nothing here is sequential.

Numbers: the decay logs, dt, the running sums, every exponent and the
carried states are float32; exponents are always differences that are
<= 0 where they are used (never exp(cs_i) * exp(-cs_j), which overflows
inside one chunk at the published sizes). The matrix products take
their operands in x's dtype (bf16 under `--bf16`) and accumulate in
float32.

The backward is written out (`custom_vjp`), not left to autodiff: its
residuals are the inputs and the chunks' entering states, and it builds
the chunk-local products again from them, so no (Q, Q)-per-head tensor
outlives the call in either direction. The gradient of the running sums
uses the row/column identity of the masked product (sum_j dM_ij M_ij =
dy_i . y_i, sum_i dM_ij M_ij = u_j . du_j), both sides from the same
float32 accumulators, so the (Q, Q) product of dM and M is never formed.

Plain XLA inside (einsums under the `ssm_scan` scope), one
implementation for every backend. No reference counterpart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _local(x, dt, a, b, c):
    """What both directions build from the chunked inputs
    (x (B, C, Q, H, P), dt (B, C, Q, H), b and c (B, C, Q, N)): the
    running sums, the decay L head-major (0 above the diagonal), the
    masked product M = (c b^T) o L in x's dtype, u = dt x, the running
    sum at the chunk's end, the decay to it, and u under that decay."""
    q = x.shape[2]
    cs = jnp.cumsum(dt * a, axis=2)                         # (B, C, Q, H)
    cs_h = cs.transpose(0, 1, 3, 2)                         # (B, C, H, Q)
    seg = cs_h[..., :, None] - cs_h[..., None, :]           # cs_i - cs_j
    tril = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))         # L, 0 above
    g = _dot("bcin,bcjn->bcij", c, b)
    m = (g[:, :, None] * decay).astype(x.dtype)             # (B, C, H, Q, Q)
    u = (x * dt[..., None]).astype(x.dtype)
    last = cs[:, :, -1]                                     # (B, C, H)
    to_end = jnp.exp(last[:, :, None] - cs)                 # (B, C, Q, H)
    u_end = (x * (dt * to_end)[..., None]).astype(x.dtype)
    return cs, decay, m, u, last, to_end, u_end


def _transfer(last):
    """T[z, c] = exp(sum_{c<k<z} last_k) for c < z, else 0: how much of
    chunk c's own state is left when chunk z starts. The sums are made
    term by term (never as a difference of two long running sums, whose
    float32 error at 8,192 positions would show)."""
    n = last.shape[1]
    k = jnp.arange(n)
    between = (k[None, None, :] > k[None, :, None]) \
        & (k[None, None, :] < k[:, None, None])             # [z, c, k]
    seg = jnp.einsum("bkh,zck->bhzc", last, between.astype(_F32),
                     precision=_HIGHEST)
    return jnp.exp(jnp.where(k[:, None] > k[None, :], seg, -jnp.inf))


def _forward(x, dt, a, b, c):
    cs, _, m, u, last, _, u_end = _local(x, dt, a, b, c)
    own = _dot("bcjhp,bcjn->bchpn", u_end, b)               # (B, C, H, P, N)
    prev = jnp.einsum("bhzc,bchpn->bzhpn", _transfer(last), own,
                      precision=_HIGHEST)
    y = _dot("bchij,bcjhp->bcihp", m, u) + jnp.exp(cs)[..., None] * _dot(
        "bcin,bchpn->bcihp", c, prev.astype(x.dtype))
    return y, prev


def _chunked(t, chunk):
    return t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, a, b, c, chunk):
    return _ssd_fwd(x, dt, a, b, c, chunk)[0]


def _ssd_fwd(x, dt, a, b, c, chunk):
    with jax.named_scope("ssm_scan"):
        y, prev = _forward(_chunked(x, chunk), _chunked(dt, chunk), a,
                           _chunked(b, chunk), _chunked(c, chunk))
        return y.reshape(x.shape).astype(x.dtype), (x, dt, a, b, c, prev)


def _ssd_bwd(chunk, res, dy):
    shapes = [r.shape for r in res[:5]]
    x, dt, a, b, c, prev = res
    x, dt, b, c, dy = (_chunked(t, chunk) for t in (x, dt, b, c, dy))
    with jax.named_scope("ssm_scan"):
        cs, decay, m, u, last, to_end, u_end = _local(x, dt, a, b, c)
        lead = jnp.exp(cs)[..., None]                       # (B, C, Q, H, 1)
        prev_lo = prev.astype(x.dtype)
        # the output again, in float32: the row term of d(cs)
        y = _dot("bchij,bcjhp->bcihp", m, u) \
            + lead * _dot("bcin,bchpn->bcihp", c, prev_lo)
        dy_lead = (dy * lead).astype(x.dtype)
        # states: what each chunk's entering state and own state receive
        d_prev = _dot("bcihp,bcin->bchpn", dy_lead, c)
        d_own = jnp.einsum("bhzc,bzhpn->bchpn", _transfer(last), d_prev,
                           precision=_HIGHEST)
        d_own_lo = d_own.astype(x.dtype)
        du_end = to_end[..., None] * _dot("bchpn,bcjn->bcjhp", d_own_lo, b)
        du = _dot("bchij,bcihp->bcjhp", m, dy) + du_end
        # d(c b^T): the heads' dM = dy u^T under their decay, summed
        dg = jnp.sum(_dot("bcihp,bcjhp->bchij", dy, u) * decay, axis=2)
        dg = dg.astype(x.dtype)
        db = _dot("bcij,bcin->bcjn", dg, c) \
            + _dot("bcjhp,bchpn->bcjn", u_end, d_own_lo)
        dc = _dot("bcij,bcjn->bcin", dg, b) \
            + _dot("bcihp,bchpn->bcin", dy_lead, prev_lo)
        # running sums: rows minus columns, and at a chunk's last
        # position everything its end state carries on
        u32 = u.astype(_F32)
        d_cs = jnp.sum(dy.astype(_F32) * y, -1) - jnp.sum(u32 * du, -1)
        d_last = jnp.sum(u32 * du_end, axis=(2, 4)) \
            + jnp.exp(last) * jnp.sum(d_own * prev, axis=(3, 4))
        d_cs = d_cs.at[:, :, -1].add(d_last)
        d_la = jnp.cumsum(d_cs[:, :, ::-1], axis=2)[:, :, ::-1]
        x32 = x.astype(_F32)
        d_dt = d_la * a + jnp.sum(du * x32, -1)
        d_a = jnp.sum(d_la * dt, axis=(0, 1, 2))
        dx = du * dt[..., None]
    outs = (dx, d_dt, d_a, db, dc)
    return tuple(g.reshape(s).astype(r.dtype)
                 for g, s, r in zip(outs, shapes, res))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256) -> jax.Array:
    """y of the recurrence above, for every position.

    x: (B, S, H, P) inputs by head; dt: (B, S, H) float32 step sizes,
    positive (after the softplus); a: (H,) float32, negative; b, c:
    (B, S, N), one group for all heads, in x's dtype. Returns
    (B, S, H, P) in x's dtype. ``chunk`` must divide S (a shorter
    sequence is one chunk). The skip term D x is the caller's.
    """
    s = x.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"the sequence ({s}) is no multiple of the "
                         f"scan's chunk ({chunk})")
    return _ssd(x, dt.astype(_F32), a.astype(_F32), b, c, chunk)


def describe(chunk: int, heads: int, head_dim: int, state: int) -> str:
    """What a run logs of the scan it built (`lm_train`'s start line)."""
    return f"ssd chunk {chunk}, {heads} heads x {head_dim} x state {state}"
