"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; `OlmoeForCausalLM`)
in plain float32 `jax.numpy`: no kernels, no cache, no batching, no
sort, no sharding. One sequence at a time, the experts by a plain loop
over all of them (mask, dense SwiGLU, weight). It follows the published
block as the configuration file's issue wrote it down:

    h = embed(tokens)                      no scale, no position table
    x = rms(h; w1)                         x*rsqrt(mean(x^2)+eps)*w
    q, k, v = x Wq, x Wk, x Wv             no bias
    q, k = rms(q; wq), rms(k; wk)          over all heads' features
    q, k = rope(q), rope(k)                whole head, rotate-half
    h = h + softmax_causal(q k^T / sqrt(D)) v Wo
    x = rms(h; w2); p = softmax(x Wr)      float32, over all experts
    (w, idx) = top_k(p)                    NOT renormalised
    h = h + sum_j w_j * Wdown[idx_j](silu(Wgate[idx_j] x) * Wup[idx_j] x)
    logits = rms(h; wf) Whead              untied head

and the training loss CE + aux_coef * load-balance + z_coef * z-loss:
load-balance is the source's `load_balancing_loss_func`, E * sum over
the k slots and the experts of (share of tokens whose slot chose the
expert) * (mean router probability of the expert), over the tokens of
all layers and all sequences; z-loss is the mean over the same tokens
of logsumexp(router logits)^2. No token is ever dropped.

On a TPU a float32 matrix product runs in lower precision unless asked
otherwise: callers run these functions under
`jax.default_matmul_precision("highest")` (`batch_stats` sets it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, D). Position s turns the pair (x[i], x[i + D/2]) by the
    angle s * theta^(-2i/D)."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, p, hp):
    """x: (S, d). q/k/v: (d, H*D); o: (H*D, d)."""
    s, n_head = x.shape[0], hp["n_head"]
    q = rms(x @ p["q"], p["q_norm"], hp["eps"]).reshape(s, n_head, -1)
    k = rms(x @ p["k"], p["k_norm"], hp["eps"]).reshape(s, n_head, -1)
    v = (x @ p["v"]).reshape(s, n_head, -1)
    q, k = rope(q, hp["theta"]), rope(k, hp["theta"])
    scores = jnp.einsum("qhd,thd->hqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqt,thd->qhd", probs, v).reshape(s, -1) @ p["o"]


def route(x, router, top_k):
    """(weights (S, k), experts (S, k), probabilities (S, E), router
    logits (S, E)): softmax over all experts, the k largest, as they
    are."""
    logits = x @ router
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, top_k)
    return w, idx, probs, logits


def experts(x, w, idx, p):
    """sum_j w_j * expert[idx_j](x): every expert runs on every token,
    and a token keeps the output of the ones it chose."""
    y = jnp.zeros_like(x)
    for e in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), -1)          # (S,)
        out = (jax.nn.silu(x @ p["gate"][e]) * (x @ p["up"][e])) \
            @ p["down"][e]
        y = y + weight[:, None] * out
    return y


def forward(params, tokens, hp):
    """tokens: (S,) int32 -> (logits (S, V), one routing tuple a layer)."""
    h = params["embed"][tokens]
    routing = []
    for p in params["blocks"]:
        h = h + attention(rms(h, p["norm_1"], hp["eps"]), p["attn"], hp)
        x = rms(h, p["norm_2"], hp["eps"])
        r = route(x, p["router"], hp["top_k"])
        routing.append(r)
        h = h + experts(x, r[0], r[1], p["experts"])
    return rms(h, params["norm_f"], hp["eps"]) @ params["lm_head"], routing


def sequence_stats(params, tokens, hp):
    """What the loss pools over sequences: the tokens' losses (S-1,),
    and a layer (leading axis) how often slot j chose expert e (k, E),
    the experts' summed probabilities (E,), the summed logsumexp^2."""
    logits, routing = forward(params, tokens, hp)
    logp = jax.nn.log_softmax(logits[:-1])
    e = routing[0][2].shape[-1]
    return {
        "token_losses": -jnp.take_along_axis(
            logp, tokens[1:, None], axis=-1)[:, 0],
        "slot_counts": jnp.stack([jnp.sum(jax.nn.one_hot(
            idx, e, dtype=jnp.float32), 0) for _, idx, _, _ in routing]),
        "prob_sums": jnp.stack([jnp.sum(p, 0) for _, _, p, _ in routing]),
        "z_sums": jnp.stack([jnp.sum(jnp.square(jax.nn.logsumexp(lg, -1)))
                             for _, _, _, lg in routing])}


def pool(stats: list[dict], hp) -> dict:
    """The training loss and its three terms from the sequences'
    statistics (jnp or numpy arrays)."""
    tokens = sum(s["token_losses"].shape[0] + 1 for s in stats)
    layers, _, e = stats[0]["slot_counts"].shape
    routed = tokens * layers               # tokens of all layers, pooled
    ce = sum(s["token_losses"].sum() for s in stats) / (tokens - len(stats))
    share = sum(s["slot_counts"].sum(0) for s in stats) / routed  # (k, E)
    prob = sum(s["prob_sums"].sum(0) for s in stats) / routed     # (E,)
    balance = e * (share * prob[None, :]).sum()
    z = sum(s["z_sums"].sum() for s in stats) / routed
    return {"loss": ce + hp["aux_coef"] * balance + hp["z_coef"] * z,
            "ce": ce, "balance": balance, "z": z}


def train_loss(params, batch, hp):
    """The scalar `jax.grad` differentiates: the reference gradient."""
    return pool([sequence_stats(params, row, hp) for row in batch],
                hp)["loss"]


def batch_stats(params, batch, hp) -> list[dict]:
    """`sequence_stats` of every row of a (B, S) batch, one sequence at
    a time, as numpy arrays on the host."""
    import numpy as np
    frozen = tuple(sorted(hp.items()))
    fn = jax.jit(lambda p, row: sequence_stats(p, row, dict(frozen)))
    with jax.default_matmul_precision("highest"):
        return [jax.tree.map(np.asarray, fn(params, jnp.asarray(
            row, jnp.int32))) for row in batch]


def from_program(tree: dict) -> dict:
    """The program's flax parameter tree under this file's names."""
    blocks = []
    for i in range(sum(name.startswith("block") for name in tree)):
        b = tree[f"block{i}"]
        a, m = b["attn"], b["moe_mlp"]
        d = a["query"]["kernel"].shape[0]
        blocks.append({
            "norm_1": b["ln_attn"]["scale"], "norm_2": b["ln_mlp"]["scale"],
            "attn": {"q": a["query"]["kernel"].reshape(d, -1),
                     "k": a["key"]["kernel"].reshape(d, -1),
                     "v": a["value"]["kernel"].reshape(d, -1),
                     "o": a["out"]["kernel"].reshape(-1, d),
                     "q_norm": a["q_norm"]["scale"],
                     "k_norm": a["k_norm"]["scale"]},
            "router": m["router"],
            "experts": {"gate": m["w_gate"], "up": m["w_up"],
                        "down": m["w_down"]}})
    return {"embed": tree["tok_embed"]["embedding"], "blocks": blocks,
            "norm_f": tree["ln_final"]["scale"],
            "lm_head": tree["lm_head"]["kernel"]}
