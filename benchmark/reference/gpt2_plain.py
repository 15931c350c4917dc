"""GPT-2 (Radford et al. 2019; `GPT2LMHeadModel`) in plain float32
`jax.numpy`: no kernels, no cache, no batching, no sharding. It follows
the published block: learned token and position embeddings, pre-LN
blocks of causal multi-head attention (scores scaled by 1/sqrt(head
size)) and a `gelu_new` feed-forward, a final LayerNorm, and next-token
cross-entropy averaged over all predicted positions.

Departures, each because the configuration file says the program runs
it so (`departures` there): the output head is a matrix of its own
(`lm_head`) and not the transposed token embedding; dense layers have no
bias (a bias that a parameter set carries is used); no dropout;
LayerNorm's epsilon is the file's `layer_norm_epsilon`.

On a TPU a float32 matrix product runs in lower precision unless asked
otherwise, so every function here runs under
`jax.default_matmul_precision("highest")` (set by
`batch_token_losses`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p, n_head):
    """x: (S, d). q/k/v: (d, H, D); o: (H, D, d)."""
    s = x.shape[0]
    q = jnp.einsum("sd,dhk->hsk", x, p["q"])
    k = jnp.einsum("sd,dhk->hsk", x, p["k"])
    v = jnp.einsum("sd,dhk->hsk", x, p["v"])
    scores = jnp.einsum("hqk,htk->hqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqt,htk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", out, p["o"]) + p.get("o_b", 0.0)


def block(x, p, n_head, eps):
    x = x + attention(layer_norm(x, p["ln_1"], eps), p["attn"], n_head)
    h = layer_norm(x, p["ln_2"], eps) @ p["fc"] + p.get("fc_b", 0.0)
    return x + gelu_new(h) @ p["proj"] + p.get("proj_b", 0.0)


def logits(params, tokens, n_head, eps):
    """tokens: (S,) int32 -> (S, V) float32."""
    x = params["wte"][tokens] + params["wpe"][: tokens.shape[0]]
    for p in params["blocks"]:
        x = block(x, p, n_head, eps)
    return layer_norm(x, params["ln_f"], eps) @ params["lm_head"]


def token_losses(params, tokens, n_head, eps):
    """-log p(token t+1 | tokens <= t), for t = 0..S-2."""
    logp = jax.nn.log_softmax(logits(params, tokens, n_head, eps)[:-1])
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]


def batch_token_losses(params, batch, n_head, eps):
    """`token_losses` of every row of a (B, S) batch, one sequence at a
    time: (B, S-1) float32 on the host. Their mean is the batch's loss."""
    import numpy as np
    fn = jax.jit(token_losses, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(
            fn(params, jnp.asarray(row, jnp.int32), n_head, eps),
            np.float32) for row in batch])


def from_program(tree: dict, n_layer: int) -> dict:
    """The program's flax parameter tree under this file's names."""
    def ln(p):
        return {"g": p["scale"], "b": p["bias"]}
    blocks = []
    for i in range(n_layer):
        b = tree[f"block{i}"]
        blocks.append({
            "ln_1": ln(b["ln_attn"]), "ln_2": ln(b["ln_mlp"]),
            "attn": {"q": b["attn"]["query"]["kernel"],
                     "k": b["attn"]["key"]["kernel"],
                     "v": b["attn"]["value"]["kernel"],
                     "o": b["attn"]["out"]["kernel"]},
            "fc": b["mlp_in"]["kernel"], "proj": b["mlp_out"]["kernel"]})
    return {"wte": tree["tok_embed"]["embedding"], "wpe": tree["pos_embed"],
            "blocks": blocks, "ln_f": ln(tree["ln_final"]),
            "lm_head": tree["lm_head"]["kernel"]}
