"""Trinity-Mini (arcee-ai, `AfmoeForCausalLM`, `model_type: afmoe`) in
plain float32 `jax.numpy`: no kernels, no sort, no cache, no batching,
no sharding. One sequence at a time; attention as a masked softmax a
query head with the key/value heads indexed, the experts by a plain
loop over the ones this chip holds. It follows the block as the
configuration file's issue wrote it down (d the hidden size, D the head size, eps 1e-5, no bias anywhere):

    h = E[tokens] * sqrt(d)                      mup_enabled
    block: a = h + rms(attn(rms(h; n1)); n2)     four norms a block
           h = a + rms(mlp(rms(a; n3)); n4)
    attn:  q (H heads), k, v (KV heads), g = x Wq, x Wk, x Wv, x Wg
           q, k = rms(q; wq), rms(k; wk)         over the D of each head
           a sliding layer: q, k = rope(q), rope(k); a full layer: none
           query i sees key j iff j <= i and, sliding, i - j < window
           head n of q reads key/value head n // (H / KV)
           (softmax(q k^T / sqrt(D)) v, all heads * sigmoid(g)) Wo
    mlp, a leading dense layer: (silu(x Wgate) * (x Wup)) Wdown
    mlp, an expert layer:
           s = sigmoid(x Wr)                     (S, E), all E experts
           idx = top_k(s + b)                    b: the balancing bias
           w = s[idx] / (sum(s[idx]) + 1e-20) * route_scale
           y = shared(x) + sum over the e in idx that are held here of
               w_e expert_e(x)                   all SwiGLU
    logits = rms(h; nf) Whead                    untied head

The chip's share: `experts` holds the tables of the held experts only,
`first` says which of the E the first of them is; what the absent ones
would have added is left out, as in the program. The loss is the mean
next-token cross-entropy and nothing else. The bias gets no gradient;
`bias_after` is the rule that moves it after a step.

Routing is discrete: `forward(..., chosen=...)` takes the experts of
every token from the caller (the program's own), so that gradients are
compared on the same assignments, and the share of assignments that
differ is reported apart.

On a TPU a float32 matrix product runs in lower precision unless asked
otherwise: callers run these functions under
`jax.default_matmul_precision("highest")` (`batch_losses` and
`batch_grads` set it).
"""

from __future__ import annotations

import functools
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


def attention(x, p, hp, sliding: bool):
    """x: (S, d). q, g: (d, H*D); k, v: (d, KV*D); o: (H*D, d). One
    query head at a time, against the key/value head it reads."""
    s, heads = x.shape[0], hp["n_head"]
    group = heads // hp["n_kv_head"]
    q = rms((x @ p["q"]).reshape(s, heads, -1), p["q_norm"], hp["eps"])
    k = rms((x @ p["k"]).reshape(s, hp["n_kv_head"], -1), p["k_norm"],
            hp["eps"])
    v = (x @ p["v"]).reshape(s, hp["n_kv_head"], -1)
    if sliding:
        q, k = rope(q, hp["theta"]), rope(k, hp["theta"])
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = back >= 0
    if sliding:
        seen = seen & (back < hp["window"])

    @jax.checkpoint  # the gradient keeps no (S, S) of another head
    def one(n):
        scores = q[:, n] @ k[:, n // group].T / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return probs @ v[:, n // group]                      # (S, D)
    out = jax.lax.map(one, jnp.arange(heads))                # (H, S, D)
    out = out.transpose(1, 0, 2).reshape(s, -1)
    return (out * jax.nn.sigmoid(x @ p["g"])) @ p["o"]


def swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def route(x, p, hp):
    """(weights (S, k), experts (S, k), scores (S, E)): sigmoid scores,
    the k largest of score + bias, weighed by the scores alone."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(scores + p["bias"], hp["top_k"])
    return gates(scores, idx, hp), idx, scores


def gates(scores, idx, hp):
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * hp["route_scale"]


def experts(x, w, idx, p, first: int):
    """shared(x) + sum_j w_j * expert[idx_j](x) over the held experts:
    each runs on every token, and a token keeps the output of the ones
    it chose. Expert `first + e` is row e of the tables. (One expert
    after the other through `lax.scan`: the loop's body compiles once.)"""
    def one(y, at):
        e, tables = at
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)  # (S,)
        return y + weight[:, None] * swiglu(x, tables), None
    held = p["experts"]["gate"].shape[0]
    return jax.lax.scan(one, swiglu(x, p["shared"]),
                        (jnp.arange(held), p["experts"]))[0]


def block(h, p, hp, sliding: bool, chosen=None):
    """(the block's output, the experts its tokens chose (S, k) or
    None for a dense layer)."""
    eps = hp["eps"]
    a = h + rms(attention(rms(h, p["norm_1"], eps), p["attn"], hp, sliding),
                p["norm_2"], eps)
    x = rms(a, p["norm_3"], eps)
    if "mlp" in p:
        return a + rms(swiglu(x, p["mlp"]), p["norm_4"], eps), None
    if chosen is None:
        w, idx, _ = route(x, p, hp)
    else:
        idx = chosen
        w = gates(jax.nn.sigmoid(x @ p["router"]), idx, hp)
    y = experts(x, w, idx, p, hp["first_expert"])
    return a + rms(y, p["norm_4"], eps), idx


def forward(params, tokens, hp, chosen=None):
    """tokens: (S,) int32 -> (logits (S, V), the experts chosen in each
    expert layer). ``chosen``: one (S, k) an expert layer, given instead
    of routed. A gradient keeps a block's input and runs the block
    again (`jax.checkpoint`)."""
    h = params["embed"][tokens] * math.sqrt(params["embed"].shape[1])
    routed, taken = [], iter(chosen or ())
    for p, kind in zip(params["blocks"], hp["layer_types"]):
        given = next(taken) if chosen is not None and "mlp" not in p \
            else None
        h, idx = jax.checkpoint(
            lambda h, p, given, sliding=kind == "sliding": block(
                h, p, hp, sliding, given))(h, p, given)
        if idx is not None:
            routed.append(idx)
    return rms(h, params["norm_f"], hp["eps"]) @ params["lm_head"], routed


def token_losses(params, tokens, hp, chosen=None):
    """((S-1,) next-token cross-entropies of one sequence, the experts
    chosen in each expert layer)."""
    logits, routed = forward(params, tokens, hp, chosen)
    logp = jax.nn.log_softmax(logits[:-1])
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0], routed


def train_loss(params, batch, hp, chosen=None):
    """The scalar `jax.grad` differentiates: the reference gradient.
    ``chosen``: one list of (S, k) a row."""
    return jnp.mean(jnp.stack([
        token_losses(params, row, hp,
                     None if chosen is None else chosen[i])[0]
        for i, row in enumerate(batch)]))


def bias_after(bias, counts, rate: float):
    """The balancing bias after a step in which the layer's experts got
    ``counts`` assignments (E,): towards the mean load, by the sign
    alone, centred."""
    delta = rate * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


def _frozen(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in hp.items()))


@functools.lru_cache(maxsize=None)
def _losses_of(frozen):
    """One compiled `token_losses` a set of sizes: a second call with
    other biases or rows runs the program the first one built."""
    return jax.jit(lambda p, row: token_losses(p, row, dict(frozen)))


@functools.lru_cache(maxsize=None)
def _grads_of(frozen):
    return jax.jit(jax.grad(lambda p, row, given: jnp.mean(
        token_losses(p, row, dict(frozen), given)[0])))


def batch_losses(params, batch, hp) -> tuple[list, list]:
    """(`token_losses` of every row of a (B, S) batch, the experts each
    row chose a layer), one sequence at a time, numpy on the host."""
    import numpy as np
    fn = _losses_of(_frozen(hp))
    losses, routed = [], []
    with jax.default_matmul_precision("highest"):
        for row in batch:
            one, idx = fn(params, jnp.asarray(row, jnp.int32))
            losses.append(np.asarray(one))
            routed.append([np.asarray(i) for i in idx])
    return losses, routed


def batch_grads(params, batch, hp, chosen=None) -> dict:
    """The gradient of `train_loss` on a (B, S) batch of rows of one
    length, as numpy arrays on the host under ``params``' names: the
    mean of the rows' gradients, one sequence at a time. The bias gets
    none (it enters a top-k alone) and is left out."""
    import numpy as np
    fn = _grads_of(_frozen(hp))
    total = None
    with jax.default_matmul_precision("highest"):
        for i, row in enumerate(batch):
            given = None if chosen is None else [
                jnp.asarray(c, jnp.int32) for c in chosen[i]]
            one = jax.tree.map(np.asarray, fn(
                params, jnp.asarray(row, jnp.int32), given))
            total = one if total is None else jax.tree.map(
                np.add, total, one)
    grads = jax.tree.map(lambda g: g / np.float32(len(batch)), total)
    for b in grads["blocks"]:
        b.pop("bias", None)
    return grads


def from_program(tree: dict, stats: dict | None = None) -> dict:
    """The program's flax parameter tree, and its `batch_stats` (the
    balancing bias; zeros where not given), under this file's names."""
    blocks = []
    for i in range(sum(name.startswith("block") for name in tree)):
        b = tree[f"block{i}"]
        a = b["attn"]
        d = a["query"]["kernel"].shape[0]
        block = {
            "norm_1": b["ln_attn"]["scale"],
            "norm_2": b["ln_attn_out"]["scale"],
            "norm_3": b["ln_mlp"]["scale"],
            "norm_4": b["ln_mlp_out"]["scale"],
            "attn": {"q": a["query"]["kernel"].reshape(d, -1),
                     "k": a["key"]["kernel"].reshape(d, -1),
                     "v": a["value"]["kernel"].reshape(d, -1),
                     "g": a["gate"]["kernel"].reshape(d, -1),
                     "o": a["out"]["kernel"].reshape(-1, d),
                     "q_norm": a["q_norm"]["scale"],
                     "k_norm": a["k_norm"]["scale"]}}
        if "moe_mlp" in b:
            m = b["moe_mlp"]
            block["router"] = m["router"]
            block["bias"] = jnp.zeros((m["router"].shape[1],), jnp.float32) \
                if stats is None \
                else stats[f"block{i}"]["moe_mlp"]["expert_bias"]
            block["shared"] = {"gate": m["shared_gate"]["kernel"],
                               "up": m["shared_up"]["kernel"],
                               "down": m["shared_down"]["kernel"]}
            block["experts"] = {"gate": m["w_gate"], "up": m["w_up"],
                                "down": m["w_down"]}
        else:
            block["mlp"] = {"gate": b["mlp_gate"]["kernel"],
                            "up": b["mlp_up"]["kernel"],
                            "down": b["mlp_out"]["kernel"]}
        blocks.append(block)
    return {"embed": tree["tok_embed"]["embedding"], "blocks": blocks,
            "norm_f": tree["ln_final"]["scale"],
            "lm_head": tree["lm_head"]["kernel"]}
