"""`flops.py` for a mixture of experts with sliding-window and global
attention layers, a shared expert and a chip's share of the routed
experts (`model_type: afmoe`): the operations a trained token requires,
and the operations and bytes of its attention, from shapes alone.
Recomputed operations (remat's replay, the flash backward's second pass
over QK^T) do not count, and neither do the experts a token did not
choose or this chip does not hold.
"""

from __future__ import annotations


def _kinds(cfg: dict) -> tuple[int, int]:
    """(sliding layers, full layers) of the configuration."""
    kinds = cfg["layer_types"]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def matmul_params(cfg: dict, held_share: float) -> float:
    """Parameters that multiply one token's activation. Every layer's
    attention: q, the gate and out (d x H·D each), k and v (d x KV·D
    each). A leading dense layer: the SwiGLU MLP, 3·d·d_ff. An expert
    layer: the router over all the experts it routes over (d x E,
    `router_experts`), the shared experts, and of the token's
    `num_experts_per_tok` experts the ``held_share`` that fall on this
    chip (3·d·d_expert each). The head d x V once; the embedding is a
    gather and adds none."""
    d = cfg["n_embd"]
    wide = cfg["n_head"] * cfg["head_dim"]
    narrow = cfg["num_key_value_heads"] * cfg["head_dim"]
    attention = 3 * d * wide + 2 * d * narrow
    expert = 3 * d * cfg["moe_intermediate_size"]
    dense_layers = cfg["num_dense_layers"]
    experts = d * cfg["router_experts"] + expert * (
        cfg["num_shared_experts"]
        + held_share * cfg["num_experts_per_tok"])
    return cfg["n_layer"] * attention \
        + dense_layers * 3 * d * cfg["n_inner"] \
        + (cfg["n_layer"] - dense_layers) * experts \
        + d * cfg["vocab_size"]


def visible_keys(seq_len: int, window: int | None = None) -> int:
    """Keys the queries of one sequence see, summed: query i sees its
    own and those before, i + 1, and under a window at most `window`."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def score_products(cfg: dict, seq_len: int) -> float:
    """Multiply-adds of one q·k^T (or p·v) over all layers for one
    sequence: visible keys x query heads x the head size."""
    sliding, full = _kinds(cfg)
    keys = sliding * visible_keys(seq_len, cfg["sliding_window"]) \
        + full * visible_keys(seq_len)
    return float(keys) * cfg["n_head"] * cfg["head_dim"]


def train_flops_per_token(cfg: dict, seq_len: int, held_share: float
                          ) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per
    matrix-multiplied parameter, and 3 x the forward's two products
    (QK^T, PV) over the keys a query sees, 2 FLOPs a multiply-add."""
    scores = 3.0 * 2 * 2.0 * score_products(cfg, seq_len) / seq_len
    return 6.0 * matmul_params(cfg, held_share) + scores


def attention_train(batch: int, cfg: dict, seq_len: int, itemsize: int = 2
                    ) -> tuple[float, float]:
    """(FLOPs, bytes) the attention of every layer requires in one step,
    forward and backward, remat's second forward not counted.
    Operations: seven matrix products (forward QK^T, PV; backward QK^T
    again, dV, dP, dQ, dK) over the visible keys, 2 FLOPs a
    multiply-add. Bytes, the least any kernel can move: q, o (forward),
    q, o, do, dq (backward) are six passes over a (B, S, H, D) array; k,
    v (forward), k, v, dk, dv (backward) six over (B, S, KV, D): a
    key/value head is read once for its group of query heads."""
    layers = cfg["n_layer"]
    heads, kv = cfg["n_head"], cfg["num_key_value_heads"]
    flops = 7.0 * 2.0 * batch * score_products(cfg, seq_len)
    passes = 6.0 * batch * seq_len * (heads + kv) * cfg["head_dim"] * itemsize
    return flops, layers * passes
