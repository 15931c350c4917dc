"""`flops.py` for a mixture-of-experts language model: the operations a
trained token requires when only its chosen experts multiply it, and
the operations and bytes of the grouped expert matmuls, from shapes
alone. Recomputed operations do not count, and neither do the experts a
token did not choose.
"""

from __future__ import annotations


def active_matmul_params(cfg: dict) -> int:
    """Parameters that multiply one token's activation: per block q, k,
    v, out (4·d²), the router (d·E) and the token's `num_experts_per_tok`
    SwiGLU experts (3·d·d_expert each), plus the output head (d·V).
    Embedding tables are gathers and add none."""
    d = cfg["n_embd"]
    block = 4 * d * d + d * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * 3 * d * cfg["n_inner"]
    return cfg["n_layer"] * block + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per active
    matrix-multiplied parameter, plus causal attention scores and values
    at half of 12·L·S·d (`flops.lm_train_flops_per_token`'s accounting)."""
    attention = 0.5 * 12 * cfg["n_layer"] * seq_len * cfg["n_embd"]
    return 6.0 * active_matmul_params(cfg) + attention


def grouped_matmuls_train(tokens: int, cfg: dict, itemsize: int = 2
                          ) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's three grouped expert matmuls require,
    forward and backward, for ``tokens`` tokens. Every token is a row
    once per chosen expert: A = tokens·k rows. A grouped product
    (A, m) x (E, m, n) -> (A, n) costs 2·A·m·n whatever the groups'
    sizes, and so do its two transposes (d-lhs, d-rhs): 3 matmuls x 3
    passes x 2·A·d·d_expert. Bytes: each of the nine reads its two
    operands and writes its result once, A·m + A·n + E·m·n elements, the
    least any kernel can move."""
    rows = tokens * cfg["num_experts_per_tok"]
    d, width = cfg["n_embd"], cfg["n_inner"]
    flops = 9 * 2.0 * rows * d * width
    nbytes = 9.0 * itemsize * (rows * (d + width)
                               + cfg["num_experts"] * d * width)
    return flops, nbytes
