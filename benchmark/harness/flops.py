"""Operations and bytes the algorithm needs, from shapes alone.

The accounting of `bench.py:_measure_lm` (PaLM's): 6 FLOPs per token and
matrix-multiplied parameter for forward and backward, plus causal
attention scores and values at half of 12·L·S·d per token. Recomputed
operations (remat, the flash backward's second pass over QK^T) do not
count: these are the operations the result requires.
"""

from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that multiply an activation: per block q, k, v, out
    (4·d²) and the two feed-forward matrices (2·d·d_ff), plus the output
    head (d·V). Embedding tables are gathers and add none."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff) \
        + d * cfg["vocab_size"]


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires."""
    attention = 0.5 * 12 * cfg["n_layer"] * seq_len * cfg["n_embd"]
    return 6.0 * lm_matmul_params(cfg) + attention


def causal_attention_train(batch: int, heads: int, seq_len: int,
                           head_dim: int, itemsize: int = 2
                           ) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's causal attention requires, forward and
    backward. A matrix product over the causal half costs
    2·(S²/2)·D per head; forward needs two (QK^T, PV), backward five
    (QK^T again, dV, dP, dQ, dK). Bytes: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv — twelve
    passes over a (B, S, H, D) array, the least any kernel can move."""
    product = 2.0 * 0.5 * seq_len * seq_len * head_dim * batch * heads
    return 7.0 * product, 12.0 * batch * seq_len * heads * head_dim * itemsize


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     dtype: str = "bf16") -> tuple[float, str]:
    """Least time the chip could take, and which bound applies."""
    t_compute = flops / peak[f"{dtype}_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
