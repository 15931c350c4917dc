"""granite-4.0-h-micro (`GraniteMoeHybridForCausalLM` with no experts;
Mamba-2 mixers of Dao & Gu 2024, arXiv:2405.21060) in plain float32
`jax.numpy`: no kernels, no chunks, no cache, no batching, no sharding.
One sequence at a time; the state-space layer as its recurrence, one
position after the other. It follows the published block as the
configuration file's issue wrote it down (d the hidden size, eps 1e-5,
no bias but the conv's):

    h = E[tokens] * embedding_multiplier        no position of any kind
    block i, both kinds:
      h = h + residual_multiplier * mixer_i(rms(h; w1))
      h = h + residual_multiplier * Wout(silu(Wg x) * (Wu x)), x = rms(h; w2)
    mixer "attention": q (H heads), k, v (KV heads) = x Wq, x Wk, x Wv
      head j of q reads key/value head j // (H / KV)
      softmax_causal(q k^T * attention_multiplier) v Wo     (1/64, not 1/8)
    mixer "mamba":  [z | xBC | dt] = x Win
      xBC = silu(conv(xBC) + b), conv_t = sum_k taps[k] xBC[t - 3 + k]
      [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t   per head, S_0 = 0
      y_t = S_t C_t + D x_t
      out = (rms(y * silu(z); w) over all heads' features) Wout
    logits = rms(h; wf) E^T / logits_scaling     the head is the table

and the training loss is the mean next-token cross-entropy.

On a TPU a float32 matrix product runs in lower precision unless asked
otherwise: callers run these functions under
`jax.default_matmul_precision("highest")` (`batch_stats` sets it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def conv(x, taps, bias):
    """Causal depthwise convolution as shifted products: x (S, C), taps
    (K, C); output t sums taps[k] * x[t - (K - 1) + k], zeros before
    the start."""
    width, s = taps.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + bias
    for k in range(width):
        back = width - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]], 0)
        out = out + taps[k] * shifted
    return out


SEGMENT = 128  # positions between two states kept for the gradient


def recurrence(x, dt, a, b, c):
    """x (S, H, P), dt (S, H), a (H,), b and c (S, N) -> y (S, H, P):
    one position after the other, the state (H, P, N) carried. For the
    gradient's sake alone the walk is cut into segments of `SEGMENT`
    positions: a state of 2 MB at every one of 8,192 positions is 17 GB
    a layer, so the backward keeps the state that enters each segment
    and walks the segment again. The arithmetic is the same walk."""
    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], -1)

    @jax.checkpoint
    def segment(state, ats):
        return jax.lax.scan(step, state, ats)
    s = x.shape[0]
    seg = SEGMENT if s % SEGMENT == 0 else s
    start = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), jnp.float32)
    cut = [t.reshape(s // seg, seg, *t.shape[1:]) for t in (x, dt, b, c)]
    return jax.lax.scan(segment, start, cut)[1].reshape(x.shape)


def mamba(u, p, hp):
    """u: (S, d). in: (d, HP + HP + 2N + H); out: (HP, d)."""
    s = u.shape[0]
    heads = p["A_log"].shape[0]
    inner = p["out"].shape[0]
    state = (p["taps"].shape[1] - inner) // 2
    z, xbc, dt = jnp.split(u @ p["in"], [inner, 2 * inner + 2 * state], -1)
    xbc = jax.nn.silu(conv(xbc, p["taps"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + state], -1)
    x = x.reshape(s, heads, -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c) \
        + p["D"][None, :, None] * x
    y = rms(y.reshape(s, inner) * jax.nn.silu(z), p["norm"], hp["eps"])
    return y @ p["out"]


def attention(x, p, hp):
    """x: (S, d). q: (d, H*D); k, v: (d, KV*D); o: (H*D, d). One
    key/value head at a time, with the query heads that read it."""
    s, kv = x.shape[0], hp["n_kv_head"]
    group = hp["n_head"] // kv
    q = (x @ p["q"]).reshape(s, kv, group, -1)
    k = (x @ p["k"]).reshape(s, kv, -1)
    v = (x @ p["v"]).reshape(s, kv, -1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint  # the gradient keeps no (G, S, S) of another head
    def one(j):
        scores = jnp.einsum("qgd,td->gqt", q[:, j], k[:, j]) \
            * hp["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("gqt,td->qgd", probs, v[:, j])     # (S, G, D)
    out = jax.lax.map(one, jnp.arange(kv))                   # (KV, S, G, D)
    return out.transpose(1, 0, 2, 3).reshape(s, -1) @ p["o"]


def block(h, p, hp):
    res = hp["residual_multiplier"]
    x = rms(h, p["norm_1"], hp["eps"])
    mixed = mamba(x, p["mamba"], hp) if "mamba" in p \
        else attention(x, p["attn"], hp)
    h = h + res * mixed
    x = rms(h, p["norm_2"], hp["eps"])
    m = p["mlp"]
    return h + res * ((jax.nn.silu(x @ m["gate"]) * (x @ m["up"]))
                      @ m["out"])


def forward(params, tokens, hp):
    """tokens: (S,) int32 -> logits (S, V). A gradient keeps a block's
    input and runs the block again (`jax.checkpoint`)."""
    h = params["embed"][tokens] * hp["embedding_multiplier"]
    for p in params["blocks"]:
        h = jax.checkpoint(lambda h, p: block(h, p, hp))(h, p)
    return rms(h, params["norm_f"], hp["eps"]) @ params["embed"].T \
        / hp["logits_scaling"]


def token_losses(params, tokens, hp):
    """(S-1,) next-token cross-entropies of one sequence."""
    logp = jax.nn.log_softmax(forward(params, tokens, hp)[:-1])
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]


def train_loss(params, batch, hp):
    """The scalar `jax.grad` differentiates: the reference gradient."""
    return jnp.mean(jnp.stack([token_losses(params, row, hp)
                               for row in batch]))


def batch_losses(params, batch, hp) -> list:
    """`token_losses` of every row of a (B, S) batch, one sequence at a
    time, as numpy arrays on the host."""
    import numpy as np
    frozen = tuple(sorted(hp.items()))
    fn = jax.jit(lambda p, row: token_losses(p, row, dict(frozen)))
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fn(params, jnp.asarray(row, jnp.int32)))
                for row in batch]


def batch_grads(params, batch, hp) -> dict:
    """The gradient of `train_loss` on a (B, S) batch of rows of one
    length, as numpy arrays on the host under ``params``' names: the
    mean of the rows' gradients, one sequence at a time (a device holds
    the parameters, one gradient and one block's activations)."""
    import numpy as np
    frozen = tuple(sorted(hp.items()))
    fn = jax.jit(jax.grad(lambda p, row: jnp.mean(
        token_losses(p, row, dict(frozen)))))
    total = None
    with jax.default_matmul_precision("highest"):
        for row in batch:
            one = jax.tree.map(np.asarray,
                               fn(params, jnp.asarray(row, jnp.int32)))
            total = one if total is None else jax.tree.map(
                np.add, total, one)
    return jax.tree.map(lambda g: g / np.float32(len(batch)), total)


def from_program(tree: dict) -> dict:
    """The program's flax parameter tree under this file's names."""
    blocks = []
    for i in range(sum(name.startswith("block") for name in tree)):
        b = tree[f"block{i}"]
        block = {"norm_1": b["ln_attn"]["scale"],
                 "norm_2": b["ln_mlp"]["scale"],
                 "mlp": {"gate": b["mlp_gate"]["kernel"],
                         "up": b["mlp_up"]["kernel"],
                         "out": b["mlp_out"]["kernel"]}}
        if "ssm" in b:
            m = b["ssm"]
            block["mamba"] = {
                "in": m["in_proj"]["kernel"], "out": m["out_proj"]["kernel"],
                "taps": m["conv_kernel"], "conv_bias": m["conv_bias"],
                "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
                "norm": m["norm"]["scale"]}
        else:
            a = b["attn"]
            d = a["query"]["kernel"].shape[0]
            block["attn"] = {"q": a["query"]["kernel"].reshape(d, -1),
                             "k": a["key"]["kernel"].reshape(d, -1),
                             "v": a["value"]["kernel"].reshape(d, -1),
                             "o": a["out"]["kernel"].reshape(-1, d)}
        blocks.append(block)
    return {"embed": tree["tok_embed"]["embedding"], "blocks": blocks,
            "norm_f": tree["ln_final"]["scale"]}
