"""`flops.py` for a share of JoyAI-LLM-Flash (`model_type:
joyai_llm_flash`; DeepSeek-V3's block): latent attention with keys of
nope + rope and values of their own size, a leading dense layer, sigmoid-
routed experts with a shared one and a chip's share of the routed ones,
and one multi-token-prediction module that is a whole expert block
behind a merge projection and meets the head a second time. The
parameters the file's sizes make, the operations a trained token
requires, and the operations and bytes of its attention, from shapes
alone. Recomputed operations (remat's replay, the flash backward's
second pass over QK^T) do not count, and neither do the experts a token
did not choose or this chip does not hold.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {
        "d": d, "heads": h, "qk": qk, "v": cfg["v_head_dim"],
        # q_a, q_b, kv_a, kv_b, o: the mixer's five matrices
        "mla": d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
        + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                     + cfg["v_head_dim"])
        + h * cfg["v_head_dim"] * d,
        "mla_norms": cfg["q_lora_rank"] + cfg["kv_lora_rank"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "router": d * cfg["router_experts"],
        "dense_layers": cfg["first_k_dense_replace"],
        "expert_layers": cfg["num_hidden_layers"]
        - cfg["first_k_dense_replace"],
        "mtp": cfg["num_nextn_predict_layers"],
        "table": cfg["vocab_size"] * d}


def parameters(cfg: dict) -> int:
    """Every parameter the trainer holds: what its start line logs as
    `params=`. A block: the mixer with its two latent norms, two norms
    of d, and a dense SwiGLU MLP or the router, the shared experts and
    the held routed ones (`n_routed_experts` of the file). The module:
    an expert block, the merge projection 2d x d and three norms of d.
    Embedding, head and the final norm."""
    s = _sizes(cfg)
    block = s["mla"] + s["mla_norms"] + 2 * s["d"]
    expert_layer = block + s["router"] + s["expert"] * (
        cfg["n_shared_experts"] + cfg["n_routed_experts"])
    return s["dense_layers"] * (block + s["dense_mlp"]) \
        + s["expert_layers"] * expert_layer \
        + s["mtp"] * (expert_layer + 2 * s["d"] * s["d"] + 3 * s["d"]) \
        + 2 * s["table"] + s["d"]


def matmul_params(cfg: dict, held_share: float) -> float:
    """Parameters that multiply one token's activation. Every block's
    mixer (five matrices). The leading dense layer's MLP. An expert
    layer, the module's too: the router over all the experts it routes
    over (`router_experts`), the shared experts, and of the token's
    `num_experts_per_tok` experts the ``held_share`` that fall on this
    chip. The module's merge projection. The head d x V once for the
    main model and once for the module; the embedding is a gather and
    adds none."""
    s = _sizes(cfg)
    experts = s["router"] + s["expert"] * (
        cfg["n_shared_experts"] + held_share * cfg["num_experts_per_tok"])
    return (s["dense_layers"] + s["expert_layers"] + s["mtp"]) * s["mla"] \
        + s["dense_layers"] * s["dense_mlp"] \
        + (s["expert_layers"] + s["mtp"]) * experts \
        + s["mtp"] * 2 * s["d"] * s["d"] \
        + (1 + s["mtp"]) * s["table"]


def attention_layers(cfg: dict) -> int:
    """Layers that run causal attention: every block, the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def train_flops_per_token(cfg: dict, seq_len: int, held_share: float
                          ) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per
    matrix-multiplied parameter, and 3 x the forward's two products over
    the S / 2 keys a query sees on average: QK^T at the key size, PV at
    the value size, 2 FLOPs a multiply-add."""
    s = _sizes(cfg)
    scores = 3.0 * 2.0 * (seq_len / 2.0) * s["heads"] * (s["qk"] + s["v"])
    return 6.0 * matmul_params(cfg, held_share) \
        + attention_layers(cfg) * scores


def attention_train_layer(batch: int, cfg: dict, seq_len: int,
                          itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's causal latent attention requires,
    forward and backward. Seven products over the S^2 / 2 causal pairs a
    head: four contract or produce the key size (QK^T, QK^T again in the
    backward, dQ, dK) and three the value size (PV, dV, dP), 2 FLOPs a
    multiply-add; counted at the published sizes whatever the kernel
    issues. Bytes, the least any kernel can move: q read forward and
    backward and dq written (three passes at the key size), k the same
    with its rotary part one vector for all heads, and v, o (forward),
    v, o, dO, dV (backward) six passes at the value size."""
    s = _sizes(cfg)
    pairs = batch * s["heads"] * seq_len * seq_len / 2.0
    flops = pairs * 2.0 * (4 * s["qk"] + 3 * s["v"])
    k = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] / s["heads"]
    nbytes = batch * seq_len * s["heads"] * itemsize * (
        3 * s["qk"] + 3 * k + 6 * s["v"])
    return flops, nbytes


def attention_train(batch: int, cfg: dict, seq_len: int, itemsize: int = 2
                    ) -> tuple[float, float]:
    """`attention_train_layer` over every layer of one step."""
    flops, nbytes = attention_train_layer(batch, cfg, seq_len, itemsize)
    return attention_layers(cfg) * flops, attention_layers(cfg) * nbytes
