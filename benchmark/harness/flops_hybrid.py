"""`flops.py` for a hybrid state-space language model (Mamba-2 mixers
with attention layers among them, a dense SwiGLU MLP, a tied head): the
operations a trained token requires, and the operations and bytes of the
state-space scan in its chunked form, from shapes alone. Recomputed
operations (remat's replay, the scan backward's second pass over its
chunk-local products) do not count.
"""

from __future__ import annotations


def _kinds(cfg: dict) -> tuple[int, int]:
    """(mamba layers, attention layers) of the configuration."""
    kinds = cfg["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply one token's activation. A mamba layer:
    in_proj d x (2·HP + 2·N + H) and out_proj HP x d; an attention
    layer: q and o (d x d each), k and v (d x KV·D each); both kinds the
    SwiGLU MLP, 3·d·d_ff; and the head d x V once: it is the embedding
    table, whose other use is a gather. The conv's four taps a channel,
    the norms and the skip are not matrix products and add none."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    mamba = d * (2 * inner + 2 * cfg["mamba_d_state"]
                 + cfg["mamba_n_heads"]) + inner * d
    kv = cfg["num_key_value_heads"] * (d // cfg["n_head"])
    attention = 2 * d * d + 2 * d * kv
    n_mamba, n_attention = _kinds(cfg)
    return n_mamba * (mamba + 3 * d * ff) \
        + n_attention * (attention + 3 * d * ff) + d * cfg["vocab_size"]


def scan_flops_per_token(cfg: dict) -> int:
    """Forward operations of one mamba layer's scan for one token, in
    the chunked form at chunk Q. The masked products count their causal
    half, as `flops.causal_attention_train` does: position i of a chunk
    needs the i + 1 positions up to itself, (Q + 1) / 2 on average, so
    the scores C·B^T shared by the heads are (Q + 1)·N and a head's
    masked product with the inputs (Q + 1)·P. (A kernel that forms the
    whole (Q, Q) product issues 2·Q·N and 2·Q·P, nearly twice that; what
    it issues above the half is not required work.) A head's own state
    of the chunk and the read of the entering state are 2·N·P each. The
    carry of the states across chunks is 2·H·P·N a chunk, 1/Q of a term
    above, and is left out."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return (q + 1) * n + h * ((q + 1) * p + 4 * n * p)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per
    matrix-multiplied parameter, 3 x the scan's forward count per mamba
    layer, and causal attention scores and values at half of 12·S·d per
    attention layer (`flops.lm_train_flops_per_token`'s accounting)."""
    n_mamba, n_attention = _kinds(cfg)
    attention = 0.5 * 12 * n_attention * seq_len * cfg["n_embd"]
    return 6.0 * matmul_params(cfg) \
        + 3.0 * n_mamba * scan_flops_per_token(cfg) + attention


def scan_train(tokens: int, cfg: dict, itemsize: int = 2
               ) -> tuple[float, float]:
    """(FLOPs, bytes) one mamba layer's scan requires, forward and
    backward, for ``tokens`` tokens. Every product of the forward has
    two in the backward (one a operand), hence 3 x. Bytes, the least any
    kernel can move: the forward reads x (H·P), B and C (N each) and the
    float32 step sizes (H) and writes y (H·P); the backward reads those
    and dy and writes dx, dB, dC and d(dt). States that stay on the chip
    between chunks are not counted."""
    n = cfg["mamba_d_state"]
    h = cfg["mamba_n_heads"]
    hp = h * cfg["mamba_d_head"]
    inputs = itemsize * (hp + 2 * n) + 4 * h
    forward = inputs + itemsize * hp
    backward = inputs + itemsize * hp + inputs
    return (3.0 * tokens * scan_flops_per_token(cfg),
            float(tokens * (forward + backward)))


def attention_train(batch: int, cfg: dict, seq_len: int, itemsize: int = 2
                    ) -> tuple[float, float]:
    """(FLOPs, bytes) the grouped-query causal attention of the
    configuration's attention layers requires in one step, forward and
    backward, remat's second forward not counted. Operations as
    `flops.causal_attention_train`: seven matrix products over the
    causal half, 2·(S²/2)·D a query head. Bytes, the least any kernel
    can move: q, o (forward), q, o, do, dq (backward) are six passes
    over a (B, S, H, D) array; k, v (forward), k, v, dk, dv (backward)
    six over (B, S, KV, D): a key/value head is read once for its group
    of query heads, not once a query head."""
    heads, kv = cfg["n_head"], cfg["num_key_value_heads"]
    head_dim = cfg["n_embd"] // heads
    layers = _kinds(cfg)[1]
    product = 2.0 * 0.5 * seq_len * seq_len * head_dim * batch * heads
    passes = 6.0 * batch * seq_len * (heads + kv) * head_dim * itemsize
    return layers * 7.0 * product, layers * passes
