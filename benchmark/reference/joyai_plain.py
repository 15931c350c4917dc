"""JoyAI-LLM-Flash (jdopensource, `model_type: joyai_llm_flash`; the
block of DeepSeek-V3, arXiv:2412.19437 §2.1-2.2) in plain float32
`jax.numpy`: no kernels, no sort, no cache, no batching, no sharding.
One sequence at a time; attention as a masked softmax a head and a block
of queries with the shared rotary key indexed, never copied; the experts
by a plain loop over the ones this chip holds; the multi-token-prediction
module and both losses. It follows the equations as the configuration
file's issue wrote them down (d the hidden size, eps 1e-6, no bias
anywhere, H heads, a head's sizes n | r for q and k and v for values):

    h = E[tokens]
    block: a = h + mla(rms(h; n1));  y = a + F(rms(a; n2))
    mla:   c_q = rms(x W_qa; nq)                          (q_lora_rank)
           [q_nope | q_pe] = c_q W_qb                     a head: n | r
           [c_kv | k_pe] = x W_kva                        kv_lora_rank | r
           [k_nope | v] = rms(c_kv; nkv) W_kvb            a head: n | v
           k_pe: not normed, one vector for all heads
           R_p: the pairs (x_2j, x_2j+1) turned by p * theta^(-2j/r)
           q = [q_nope | R_p(q_pe)], k = [k_nope | R_p(k_pe)]
           query i sees key j iff j <= i
           (softmax(q k^T / sqrt(n + r)) v, all heads) W_o
    F, a leading dense layer: (silu(x Wgate) * (x Wup)) Wdown
    F, an expert layer:
           s = sigmoid(x Wr)                     (S, E), all E experts
           idx = top_k(s + b)                    b: the balancing bias
           w = s[idx] / (sum(s[idx]) + 1e-20) * route_scale
           y = shared(x) + sum over the e in idx that are held here of
               w_e expert_e(x)                   all SwiGLU
    o = rms(h; nf);  logits = o Whead            untied head
    mtp:   u_i = [rms(E[t_i+1]; ne) ; rms(o_i; nh)] W_eh   (2d -> d)
           z = rms(block_mtp(u); nz);  logits' = z Whead   E, Whead: the
                                                           main model's
    L = mean_i CE(logits_i, t_i+1) + lambda * mean_i CE(logits'_i, t_i+2)

On fixed shapes the module's embedding input is the row rolled left by
one: the last place is fed the row's first token, which no earlier place
sees and whose own output meets no target; it is among the tokens the
module's experts count.

The chip's share: `experts` holds the tables of the held experts only,
`first` says which of the E the first of them is; what the absent ones
would have added is left out, as in the program. The bias gets no
gradient; `bias_after` is the rule that moves it after a step.

Routing is discrete: `token_losses(..., chosen=...)` takes the experts
of every token from the caller (the program's own), so that gradients
are compared on the same assignments.

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

Q_BLOCK = 1024  # queries a piece of one head's scores


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, ..., D). Position s turns the pair (x[2j], x[2j + 1]) by
    the angle s * theta^(-2j/D): the complex product
    (x[2j] + i x[2j+1]) * exp(i angle)."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape(s, *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def latents(x, p, hp):
    """x: (S, d) -> q_nope (S, H, n), q_pe (S, H, r), k_nope (S, H, n),
    k_pe (S, r), v (S, H, v), before any position. q_a: (d,
    q_lora_rank); q_b: (q_lora_rank, H*(n+r)); kv_a: (d, kv_lora_rank +
    r); kv_b: (kv_lora_rank, H*(n+v))."""
    s, heads, eps = x.shape[0], hp["n_head"], hp["eps"]
    n, rank = hp["nope"], hp["kv_rank"]
    q = (rms(x @ p["q_a"], p["q_a_norm"], eps) @ p["q_b"]
         ).reshape(s, heads, -1)
    c_kv = x @ p["kv_a"]
    kv = (rms(c_kv[:, :rank], p["kv_a_norm"], eps) @ p["kv_b"]
          ).reshape(s, heads, -1)
    return q[..., :n], q[..., n:], kv[..., :n], c_kv[:, rank:], kv[..., n:]


def turned(q_nope, q_pe, k_nope, k_pe, hp):
    """Rotary positions: on the second part of q and on the shared key,
    as they are (the key not normed), and on nothing else."""
    return q_nope, rope(q_pe, hp["theta"]), k_nope, rope(k_pe, hp["theta"])


def softmax_scale(hp) -> float:
    return 1.0 / math.sqrt(hp["nope"] + hp["rope"])


def mla(x, p, hp):
    """x: (S, d); o: (H*v, d). One head and one block of queries at a
    time; the shared rotary key indexed by every head, never copied."""
    s, heads = x.shape[0], hp["n_head"]
    *qk, v = latents(x, p, hp)
    q_nope, q_pe, k_nope, k_pe = turned(*qk, hp)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint  # the gradient keeps no scores of another piece
    def one(at):
        head, start = at
        rows = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=start, slice_size=blk, axis=0)
        k_n = jax.lax.dynamic_index_in_dim(k_nope, head, 1, False)
        scores = (rows(jax.lax.dynamic_index_in_dim(q_nope, head, 1, False))
                  @ k_n.T
                  + rows(jax.lax.dynamic_index_in_dim(q_pe, head, 1, False))
                  @ k_pe.T) * softmax_scale(hp)
        seen = keys <= start + jnp.arange(blk)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return probs @ jax.lax.dynamic_index_in_dim(v, head, 1, False)
    grid = jnp.stack(jnp.meshgrid(jnp.arange(heads),
                                  jnp.arange(0, s, blk), indexing="ij"),
                     -1).reshape(-1, 2)
    out = jax.lax.map(one, (grid[:, 0], grid[:, 1]))    # (H*S/blk, blk, v)
    out = out.reshape(heads, s, -1).transpose(1, 0, 2).reshape(s, -1)
    return out @ p["o"]


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
    after the other through `lax.scan`, each under `jax.checkpoint`: the
    gradient holds one expert's products at a time.)"""
    def one(y, at):
        e, tables = at
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)  # (S,)
        return y + weight[:, None] * jax.checkpoint(swiglu)(x, tables), None
    held = p["experts"]["gate"].shape[0]
    return jax.lax.scan(one, swiglu(x, p["shared"]),
                        (jnp.arange(held), p["experts"]))[0]


def feed_forward(a, p, hp, chosen=None):
    """(F(rms(a; n2)), the experts chosen (S, k) or None for a dense
    layer)."""
    x = rms(a, p["norm_2"], hp["eps"])
    if "mlp" in p:
        return swiglu(x, p["mlp"]), None
    if chosen is None:
        w, idx, _ = route(x, p, hp)
    else:
        idx = chosen
        w = gates(jax.nn.sigmoid(x @ p["router"]), idx, hp)
    return experts(x, w, idx, p, hp["first_expert"]), idx


def block(h, p, hp, chosen=None):
    """(the block's output, the experts its tokens chose or None). A
    gradient keeps a half's input and runs the half again."""
    a = h + jax.checkpoint(lambda h, p: mla(
        rms(h, p["norm_1"], hp["eps"]), p["attn"], hp))(h, p)
    y, idx = jax.checkpoint(
        lambda a, p, chosen: feed_forward(a, p, hp, chosen))(a, p, chosen)
    return a + y, idx


def forward(params, tokens, hp, chosen=None):
    """tokens: (S,) int32 -> (logits (S, V) for the next token, logits
    (S, V) of the module for the token after the next, the experts
    chosen in each expert layer, the module's last). ``chosen``: one
    (S, k) an expert layer in that order, given instead of routed."""
    h = params["embed"][tokens]
    routed, taken = [], iter(chosen or ())

    def run(h, p):
        given = next(taken) if chosen is not None and "mlp" not in p \
            else None
        h, idx = block(h, p, hp, given)
        if idx is not None:
            routed.append(idx)
        return h
    for p in params["blocks"]:
        h = run(h, p)
    o = rms(h, params["norm_f"], hp["eps"])
    m = params["mtp"]
    u = jnp.concatenate(
        [rms(params["embed"][next_tokens(tokens)], m["norm_e"], hp["eps"]),
         rms(o, m["norm_h"], hp["eps"])], -1) @ m["eh_proj"]
    z = rms(run(u, m["block"]), m["norm_out"], hp["eps"])
    return o @ params["lm_head"], z @ params["lm_head"], routed


def next_tokens(tokens):
    """Place i's next token, on a fixed shape: the row rolled left."""
    return jnp.roll(tokens, -1)


def ahead_pairs(ahead, tokens):
    """(the module's logits, their targets): place i against token
    i + 2, for the places that have one."""
    return ahead[:-2], tokens[2:]


def token_losses(params, tokens, hp, chosen=None):
    """((S-1,) next-token cross-entropies of one sequence, (S-2,) of the
    module against the token after the next, the experts chosen)."""
    logits, ahead, routed = forward(params, tokens, hp, chosen)

    def ce(lg, targets):
        return -jnp.take_along_axis(jax.nn.log_softmax(lg),
                                    targets[:, None], axis=-1)[:, 0]
    return (ce(logits[:-1], tokens[1:]), ce(*ahead_pairs(ahead, tokens)),
            routed)


def row_loss(params, tokens, hp, chosen=None):
    main, ahead, _ = token_losses(params, tokens, hp, chosen)
    return jnp.mean(main) + hp["mtp_weight"] * jnp.mean(ahead)


def train_loss(params, batch, hp, chosen=None):
    """The scalar `jax.grad` differentiates: L_main + lambda * L_mtp
    over rows of one length. ``chosen``: one list of (S, k) a row."""
    return jnp.mean(jnp.stack([
        row_loss(params, row, hp, None if chosen is None else chosen[i])
        for i, row in enumerate(batch)]))


def bias_after(bias, counts, rate: float):
    """The balancing bias after a step in which the layer's experts got
    ``counts`` assignments (E,): DeepSeek-V3's rule, towards the mean
    load by the sign alone."""
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def _frozen(hp):
    return tuple(sorted(hp.items()))


@functools.lru_cache(maxsize=None)
def _losses_of(frozen):
    """One compiled `token_losses` a set of sizes: a second call with
    other biases or rows runs the program the first one built."""
    return jax.jit(lambda p, row: token_losses(p, row, dict(frozen)))


@functools.lru_cache(maxsize=None)
def _grads_of(frozen):
    return jax.jit(jax.grad(lambda p, row, given: row_loss(
        p, row, dict(frozen), given)))


def batch_losses(params, batch, hp) -> tuple[list, list, list]:
    """(`token_losses` of every row of a (B, S) batch: the main losses,
    the module's, the experts each row chose a layer), one sequence at
    a time, numpy on the host."""
    import numpy as np
    fn = _losses_of(_frozen(hp))
    main, ahead, routed = [], [], []
    with jax.default_matmul_precision("highest"):
        for row in batch:
            one, two, idx = fn(params, jnp.asarray(row, jnp.int32))
            main.append(np.asarray(one))
            ahead.append(np.asarray(two))
            routed.append([np.asarray(i) for i in idx])
    return main, ahead, routed


def batch_grads(params, batch, hp, chosen=None) -> dict:
    """The gradient of `train_loss` on a (B, S) batch of rows of one
    length, as numpy arrays on the host under ``params``' names: the
    mean of the rows' gradients, one sequence at a time. The bias gets
    none (it enters a top-k alone) and is left out. The host holds one
    tree: a row's gradient is added to it leaf by leaf, each leaf let
    go as soon as it is read (a tree is 2.7 GB at the cell's size, a
    device array keeps the host copy that was read from it, and the
    checker holds the program's gradient beside this one)."""
    import numpy as np
    fn = _grads_of(_frozen(hp))
    total = names = None
    with jax.default_matmul_precision("highest"):
        for i, row in enumerate(batch):
            given = None if chosen is None else [
                jnp.asarray(c, jnp.int32) for c in chosen[i]]
            leaves, names = jax.tree.flatten(fn(
                params, jnp.asarray(row, jnp.int32), given))
            if total is None:
                total = [np.zeros(g.shape, g.dtype) for g in leaves]
            while leaves:
                into = total[len(leaves) - 1]
                np.add(into, np.asarray(leaves.pop()), out=into)
    for leaf in total:
        np.divide(leaf, np.float32(len(batch)), out=leaf)
    grads = jax.tree.unflatten(names, total)
    for b in (*grads["blocks"], grads["mtp"]["block"]):
        b.pop("bias", None)
    return grads


def from_program(tree: dict, stats: dict | None = None) -> dict:
    """The program's flax parameter tree, and its `batch_stats` (the
    balancing bias; zeros where not given), under this file's names."""
    def one(b, bias):
        a = b["attn"]
        d = a["q_a"]["kernel"].shape[0]
        out = {
            "norm_1": b["ln_attn"]["scale"], "norm_2": b["ln_mlp"]["scale"],
            "attn": {"q_a": a["q_a"]["kernel"],
                     "q_a_norm": a["q_a_norm"]["scale"],
                     "q_b": a["q_b"]["kernel"].reshape(
                         a["q_b"]["kernel"].shape[0], -1),
                     "kv_a": a["kv_a"]["kernel"],
                     "kv_a_norm": a["kv_a_norm"]["scale"],
                     "kv_b": a["kv_b"]["kernel"].reshape(
                         a["kv_b"]["kernel"].shape[0], -1),
                     "o": a["out"]["kernel"].reshape(-1, d)}}
        if "moe_mlp" in b:
            m = b["moe_mlp"]
            out["router"] = m["router"]
            out["bias"] = jnp.zeros((m["router"].shape[1],), jnp.float32) \
                if bias is None else bias["moe_mlp"]["expert_bias"]
            out["shared"] = {"gate": m["shared_gate"]["kernel"],
                             "up": m["shared_up"]["kernel"],
                             "down": m["shared_down"]["kernel"]}
            out["experts"] = {"gate": m["w_gate"], "up": m["w_up"],
                              "down": m["w_down"]}
        else:
            out["mlp"] = {"gate": b["mlp_gate"]["kernel"],
                          "up": b["mlp_up"]["kernel"],
                          "down": b["mlp_out"]["kernel"]}
        return out
    stats = stats or {}
    blocks = [one(tree[f"block{i}"], stats.get(f"block{i}"))
              for i in range(sum(name.startswith("block") for name in tree))]
    m = tree["mtp"]
    return {"embed": tree["tok_embed"]["embedding"], "blocks": blocks,
            "norm_f": tree["ln_final"]["scale"],
            "lm_head": tree["lm_head"]["kernel"],
            "mtp": {"norm_e": m["enorm"]["scale"],
                    "norm_h": m["hnorm"]["scale"],
                    "eh_proj": m["eh_proj"]["kernel"],
                    "block": one(m["block"],
                                 stats.get("mtp", {}).get("block")),
                    "norm_out": m["norm"]["scale"]}}
