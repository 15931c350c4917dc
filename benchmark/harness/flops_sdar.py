"""`flops.py` for a mixture of experts trained by diffusion over blocks
(`model_type: sdar_moe`): a noised and a clean copy of every row in one
pass, attention by block index, the loss on the noised copy. The
operations a trained token requires, and the operations and bytes of its
attention, from shapes alone and from the mask as the objective defines
it, whichever kernel or XLA path computes it. A token is a token of the
data: a row of L tokens is 2L positions through the blocks and L through
the head. Recomputed operations (remat's replay, the flash backward's
second pass over QK^T) do not count, and neither do the experts a
position did not choose or this chip does not hold.
"""

from __future__ import annotations


def visible_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs the mask lets through for one row, both
    copies. With N = L / B blocks: a clean query of block n sees the
    (n + 1)·B clean keys of the blocks up to its own; a noised query of
    block n its own block's B noised keys and the n·B clean keys before
    it: (n + 1)·B as well. B queries a block, two copies:
    2 · B² · N(N + 1) / 2."""
    n = seq_len // block
    return block * block * n * (n + 1)


def own_block_pairs(seq_len: int, block: int) -> int:
    """Of those, the noised queries against their own block: L·B."""
    return seq_len * block


def matmul_params(cfg: dict, held_share: float) -> tuple[float, float]:
    """(parameters that multiply one position's activation in the
    blocks, parameters that multiply one token's in the head). A layer's
    attention: q and out (d x H·D each), k and v (d x KV·D each); the
    router over all the experts it routes over (d x E,
    `router_experts`); of the position's `num_experts_per_tok` experts
    the ``held_share`` that fall on this chip (3·d·d_expert each). The
    head d x V; the embedding is a gather and adds none."""
    d = cfg["n_embd"]
    wide = cfg["n_head"] * cfg["head_dim"]
    narrow = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = 2 * d * wide + 2 * d * narrow + d * cfg["router_experts"] \
        + held_share * cfg["num_experts_per_tok"] \
        * 3 * d * cfg["moe_intermediate_size"]
    return cfg["n_layer"] * layer, float(d * cfg["vocab_size"])


def score_products(cfg: dict, seq_len: int, pairs: int | None = None
                   ) -> float:
    """Multiply-adds of one q·k^T (or p·v) over all layers for one row:
    visible pairs x query heads x the head size."""
    if pairs is None:
        pairs = visible_pairs(seq_len, cfg["block_length"])
    return float(cfg["n_layer"]) * pairs * cfg["n_head"] * cfg["head_dim"]


def train_flops_per_token(cfg: dict, seq_len: int, held_share: float
                          ) -> float:
    """Forward + backward FLOPs one trained token of the data requires:
    6 per matrix-multiplied parameter, the blocks' twice (its noised and
    its clean position) and the head's once, and 3 x the forward's two
    products (QK^T, PV) over the visible pairs, 2 FLOPs a
    multiply-add."""
    blocks, head = matmul_params(cfg, held_share)
    scores = 3.0 * 2 * 2.0 * score_products(cfg, seq_len) / seq_len
    return 6.0 * (2 * blocks + head) + scores


def kernels_train(batch: int, cfg: dict, seq_len: int, itemsize: int = 2
                  ) -> tuple[float, float]:
    """(FLOPs, bytes) the two masked attentions of every layer require
    in one step, forward and backward: the clean copy's, and the noised
    queries' against the clean copy's earlier blocks (the own-block
    pairs are not theirs). Operations: seven matrix products (forward
    QK^T, PV; backward QK^T again, dV, dP, dQ, dK) over those pairs, 2
    FLOPs a multiply-add. Bytes, the least any kernel can move, for each
    of the two: q, o (forward), q, o, do, dq (backward) six passes over
    (B, L, H, D); k, v (forward), k, v, dk, dv (backward) six over
    (B, L, KV, D)."""
    block = cfg["block_length"]
    pairs = visible_pairs(seq_len, block) - own_block_pairs(seq_len, block)
    flops = 7.0 * 2.0 * batch * score_products(cfg, seq_len, pairs)
    heads, kv = cfg["n_head"], cfg["num_key_value_heads"]
    passes = 2 * 6.0 * batch * seq_len * (heads + kv) * cfg["head_dim"] \
        * itemsize
    return flops, cfg["n_layer"] * passes
