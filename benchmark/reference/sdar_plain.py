"""SDAR-30B-A3B-Chat (JetLM, `SDARMoeForCausalLM`, `model_type:
sdar_moe`; arXiv:2510.06303) and the objective it is trained by
(BD3-LMs, arXiv:2503.09573) in plain float32 `jax.numpy`: no kernels, no
sort, no cache, no batching, no sharding. One row at a time; the mask
built outright on the 2L x 2L grid from the three published terms;
attention as a masked softmax a query head with the key/value heads
indexed, in blocks of queries so that a head's scores over 16,384
positions fit; the experts by a plain loop over the ones this chip
holds. It follows the model as the configuration file's issue wrote it
down (d the hidden size, D the head size, eps 1e-6, no bias anywhere):

    x = [noised ; clean]                         2L token ids
    h = E[x]                                     no multiplier
    block: a = h + attn(rms(h; n1))
           h = a + moe(rms(a; n2))
    attn:  q (H heads), k, v (KV heads) = x Wq, x Wk, x Wv
           q, k = rms(q; wq), rms(k; wk)         over the D of each head
           q, k = rope(q), rope(k)               at the place within the
                                                 row: both copies 0..L-1
           query i sees key j iff `visible`      below
           head n of q reads key/value head n // (H / KV)
           softmax(q k^T / sqrt(D)) v, all heads, Wo
    moe:   p = softmax(x Wr)                     (2L, E), all E experts
           idx = top_k(p);  w = p[idx] / sum(p[idx])
           y = sum over the e in idx that are held here of
               w_e expert_e(x)                   SwiGLU, no shared expert
    logits = rms(h[:L]; nf) Whead                the noised copy's alone

    loss = 1 / (rows x L) x sum over the masked i of
           (1 / t_b(i)) x CE(logits_i, clean_i)  no shift, no router term

`visible`, with b(i) = (i mod L) // B and a place i clean iff i >= L:

    block_diagonal       b(i) == b(j) and i, j in the same copy
    offset_block_causal  b(i) >  b(j) and i noised and j clean
    block_causal         b(i) >= b(j) and i clean  and j clean

The chip's share: `experts` holds the tables of the held experts only,
`first` says which of the E the first of them is; what the absent ones
would have added is left out, as in the program.

Routing is discrete: `states(..., chosen=...)` takes the experts of
every position from the caller (the program's own), so that gradients
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

QUERY_BLOCK = 2048  # positions a piece of a head's masked softmax, or of the logits


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta, positions):
    """x: (S, H, D). A place at position s turns the pair
    (x[i], x[i + D/2]) by the angle s * theta^(-2i/D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def visible(length: int, block: int):
    """(2L, 2L) bool: may query i (rows) see key j (columns)? The three
    terms of BD3-LMs' training mask, [noised ; clean]."""
    place = jnp.arange(2 * length)
    clean = place >= length
    b = (place % length) // block
    qb, kb, qc, kc = b[:, None], b[None, :], clean[:, None], clean[None, :]
    block_diagonal = (qb == kb) & (qc == kc)
    offset_block_causal = (qb > kb) & kc & ~qc
    block_causal = (qb >= kb) & kc & qc
    return block_diagonal | offset_block_causal | block_causal


def attention(x, p, hp, seen):
    """x: (2L, d). q: (d, H*D); k, v: (d, KV*D); o: (H*D, d). One query
    head at a time against the key/value head it reads, a block of
    queries at a time."""
    s, heads = x.shape[0], hp["n_head"]
    group = heads // hp["n_kv_head"]
    at = jnp.arange(s) % (s // 2)
    q = rms((x @ p["q"]).reshape(s, heads, -1), p["q_norm"], hp["eps"])
    k = rms((x @ p["k"]).reshape(s, hp["n_kv_head"], -1), p["k_norm"],
            hp["eps"])
    v = (x @ p["v"]).reshape(s, hp["n_kv_head"], -1)
    q, k = rope(q, hp["theta"], at), rope(k, hp["theta"], at)
    step = min(QUERY_BLOCK, s)
    pieces = s // step

    @jax.checkpoint  # the gradient keeps no scores of another piece
    def one(at):
        n, piece = at // pieces, at % pieces
        rows = jax.lax.dynamic_slice_in_dim(q[:, n], piece * step, step)
        mask = jax.lax.dynamic_slice_in_dim(seen, piece * step, step)
        scores = rows @ k[:, n // group].T / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return probs @ v[:, n // group]                      # (step, D)
    out = jax.lax.map(one, jnp.arange(heads * pieces))
    out = out.reshape(heads, s, -1).transpose(1, 0, 2).reshape(s, -1)
    return out @ p["o"]


def swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def gates(probs, idx):
    w = jnp.take_along_axis(probs, idx, axis=-1)
    return w / jnp.sum(w, -1, keepdims=True)


def route(x, p, hp):
    """(weights (S, k), experts (S, k)): softmax over all E, the k
    largest, renormalised to 1 (`norm_topk_prob`)."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    _, idx = jax.lax.top_k(probs, hp["top_k"])
    return gates(probs, idx), idx


def experts(x, w, idx, p, first: int):
    """sum_j w_j * expert[idx_j](x) over the held experts: each runs on
    every position, and a position keeps the output of the ones it
    chose. Expert `first + e` is row e of the tables."""
    @jax.checkpoint  # the gradient keeps no expert's hidden products
    def part(x, tables, weight):
        return weight[:, None] * swiglu(x, tables)

    def one(y, at):
        e, tables = at
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)  # (S,)
        return y + part(x, tables, weight), None
    held = p["experts"]["gate"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), p["experts"]))[0]


def block(h, p, hp, seen, chosen=None):
    """(the block's output, the experts its positions chose (2L, k))."""
    eps = hp["eps"]

    # each half under a checkpoint of its own: a gradient holds one
    # half's activations at a time, which is what lets 16,384 positions
    # in float32 fit beside the parameters and their gradient
    @jax.checkpoint
    def first_half(h, p):
        return h + attention(rms(h, p["norm_1"], eps), p["attn"], hp, seen)

    @jax.checkpoint
    def second_half(a, p, chosen):
        x = rms(a, p["norm_2"], eps)
        if chosen is None:
            w, idx = route(x, p, hp)
        else:
            idx = chosen
            w = gates(jax.nn.softmax(x @ p["router"], -1), idx)
        return a + experts(x, w, idx, p, hp["first_expert"]), idx
    return second_half(first_half(h, p), p, chosen)


def states(params, tokens, noised, hp, chosen=None):
    """tokens, noised: (L,) int32 -> (the noised copy's final states
    (L, d), normed, the experts chosen in each layer, (2L, k) each).
    ``chosen``: one (2L, k) a layer, given instead of routed. A gradient
    keeps a block's input and runs the block again (`jax.checkpoint`)."""
    length = tokens.shape[0]
    seen = visible(length, hp["block_length"])
    h = params["embed"][jnp.concatenate([noised, tokens])]
    routed = []
    for i, p in enumerate(params["blocks"]):
        given = None if chosen is None else chosen[i]
        h, idx = jax.checkpoint(
            lambda h, p, given: block(h, p, hp, seen, given))(h, p, given)
        routed.append(idx)
    return rms(h[:length], params["norm_f"], hp["eps"]), routed


def token_losses(params, tokens, noised, hp, chosen=None):
    """((L,) cross-entropies of the clean tokens under the noised copy's
    logits at the same places, the experts chosen in each layer). The
    logits a block of positions at a time: a gradient keeps none."""
    h, routed = states(params, tokens, noised, hp, chosen)
    step = min(QUERY_BLOCK, h.shape[0])

    @jax.checkpoint
    def piece(at):
        rows, targets = at
        logp = jax.nn.log_softmax(rows @ params["lm_head"])
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    ce = jax.lax.map(piece, (h.reshape(-1, step, h.shape[1]),
                             tokens.reshape(-1, step)))
    return ce.reshape(-1), routed


def noised_copy(tokens, masked, mask_id: int):
    return jnp.where(masked, mask_id, tokens)


def weights_of(masked, t):
    """masked / t / (rows x L) for a (rows, L) batch."""
    return masked.astype(jnp.float32) / (t * masked.size)


def train_loss(params, batch, hp, chosen=None):
    """The scalar `jax.grad` differentiates: the objective on a batch of
    `tokens`, `masked` (rows, L) and `t` (rows, L). ``chosen``: one list
    of (2L, k) a row."""
    w = weights_of(batch["masked"], batch["t"])
    total = 0.0
    for i, row in enumerate(batch["tokens"]):
        ce, _ = token_losses(
            params, row, noised_copy(row, batch["masked"][i], hp["mask_id"]),
            hp, None if chosen is None else chosen[i])
        total = total + jnp.sum(w[i] * ce)
    return total


def _frozen(hp):
    return tuple(sorted(hp.items()))


@functools.lru_cache(maxsize=None)
def _losses_of(frozen):
    return jax.jit(lambda p, row, noised: token_losses(
        p, row, noised, dict(frozen)))


@functools.lru_cache(maxsize=None)
def _grads_of(frozen):
    return jax.jit(jax.grad(lambda p, row, noised, w, given: jnp.sum(
        w * token_losses(p, row, noised, dict(frozen), given)[0])))


def batch_losses(params, batch, hp) -> tuple[list, list]:
    """(`token_losses` of every row of the batch, the experts each row
    chose a layer), one row at a time, numpy on the host."""
    import numpy as np
    fn = _losses_of(_frozen(hp))
    losses, routed = [], []
    with jax.default_matmul_precision("highest"):
        for row, masked in zip(batch["tokens"], batch["masked"]):
            row = jnp.asarray(row, jnp.int32)
            one, idx = fn(params, row, noised_copy(
                row, jnp.asarray(masked), hp["mask_id"]))
            losses.append(np.asarray(one))
            routed.append([np.asarray(i) for i in idx])
    return losses, routed


def batch_loss(losses: list, batch) -> float:
    """The objective from `batch_losses`' per-token values."""
    import numpy as np
    w = np.asarray(weights_of(jnp.asarray(batch["masked"]),
                              jnp.asarray(batch["t"], jnp.float32)))
    return float(np.sum(w * np.stack(losses).astype(np.float64)))


def batch_grads(params, batch, hp, chosen=None) -> dict:
    """The gradient of `train_loss`, as numpy arrays on the host under
    ``params``' names: the sum of the rows' gradients, one row at a
    time."""
    import numpy as np
    fn = _grads_of(_frozen(hp))
    w = weights_of(jnp.asarray(batch["masked"]),
                   jnp.asarray(batch["t"], jnp.float32))
    total = None
    with jax.default_matmul_precision("highest"):
        for i, row in enumerate(batch["tokens"]):
            row = jnp.asarray(row, jnp.int32)
            given = None if chosen is None else [
                jnp.asarray(c, jnp.int32) for c in chosen[i]]
            one = jax.tree.map(np.asarray, fn(
                params, row, noised_copy(row, jnp.asarray(batch["masked"][i]),
                                         hp["mask_id"]), w[i], given))
            total = one if total is None else jax.tree.map(
                np.add, total, one)
    return total


def from_program(tree: dict) -> dict:
    """The program's flax parameter tree under this file's names."""
    blocks = []
    for i in range(sum(name.startswith("block") for name in tree)):
        b = tree[f"block{i}"]
        a, m = b["attn"], b["moe_mlp"]
        d = a["query"]["kernel"].shape[0]
        blocks.append({
            "norm_1": b["ln_attn"]["scale"],
            "norm_2": b["ln_mlp"]["scale"],
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
