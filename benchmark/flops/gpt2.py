"""Operations and bytes of GPT-2 training, from shapes.

Model FLOPs per token follow PaLM's convention (Chowdhery et al. 2022,
appendix B): 6 N for the matmul parameters N (the tied embedding counts
once, as the output head; positions and norm scales are not matmuls),
plus 12 L d s for attention, counted in full and not halved for the
causal mask. Recomputed operations are not counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f, n_layer = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    per_layer = d * 3 * d + d * d + 2 * d * f
    return n_layer * per_layer + cfg["vocab_size"] * d


def flops_per_token(cfg: dict, seq_len: int) -> float:
    return (6.0 * matmul_params(cfg)
            + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq_len)


def flash_attention(cfg: dict, rows: int, seq_len: int) -> dict:
    """What the flash kernels of one training step need on one chip, for
    `rows` sequences: the causal half of the score matrix, forward (two
    matmuls: QK^T and PV) and fused backward (five: the recomputed scores,
    dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q), 2 FLOPs a
    multiply-add; and the bytes each kernel must move once: q, k, v, o
    and dO in bf16, the f32 row statistics (lse; delta in the backward),
    and dQ, dK, dV written in f32."""
    h, d, n_layer = cfg["n_head"], cfg["n_embd"], cfg["n_layer"]
    hd = d // h
    t = seq_len
    pairs = rows * h * t * (t + 1) / 2          # causal score entries
    one_matmul = 2.0 * pairs * hd
    elems = rows * h * t * hd                   # one of q, k, v, o, dO
    stats = rows * h * t * 4                    # one f32 row statistic
    fwd_bytes = 4 * elems * 2 + stats           # q, k, v in; o, lse out
    bwd_bytes = 4 * elems * 2 + 2 * stats + 3 * elems * 4
    return {"flops": n_layer * 7 * one_matmul,
            "bytes": n_layer * (fwd_bytes + bwd_bytes)}
