"""Operations and bytes of DeepSeek-V2 training, from shapes.

Model FLOPs per token follow PaLM's convention, as `flops/gpt2.py`
counts them: 6 N for the matmul parameters a token passes through (the
embedding is a lookup; the untied head counts; a routed expert counts at
the expected share of tokens it sees, k x held / G), plus attention's
score and value matmuls, 3 x 2 s (heads x qk width + heads x v width) a
layer, in full and not halved for the causal mask. Recomputed operations
are not counted.
"""

from __future__ import annotations


def _expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU: gate, up and down."""
    return 3 * cfg["n_embd"] * cfg["moe_intermediate_size"]


def _moe_layers(cfg: dict) -> int:
    return cfg["n_layer"] - cfg["first_dense_layers"]


def matmul_params(cfg: dict) -> float:
    """Matmul parameters a token passes through, routed experts at their
    expected share."""
    d, h = cfg["n_embd"], cfg["n_head"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    mla = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    dense = 3 * d * cfg["n_inner"]
    shared = cfg["n_shared_experts"] * _expert_params(cfg)
    router = d * cfg["router_experts"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_experts"] * _expert_params(cfg))
    return (cfg["n_layer"] * mla + cfg["first_dense_layers"] * dense
            + _moe_layers(cfg) * (shared + router + routed)
            + d * cfg["vocab_size"])


def flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["n_head"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attention = 3 * 2 * seq_len * (h * qk + h * cfg["v_head_dim"])
    return 6.0 * matmul_params(cfg) + cfg["n_layer"] * attention


def flash_attention(cfg: dict, rows: int, seq_len: int) -> dict:
    """What the flash kernels of one training step need on one chip, for
    `rows` sequences, at MLA's true widths (q and k 192, v 128; the kernel
    now carries v padded to 192, which this does not count): the causal
    half of the scores, forward (QK^T, PV) and fused backward (the
    recomputed scores, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q),
    2 FLOPs a multiply-add; and the bytes each kernel must move once: q,
    k, v, o and dO in bf16, the f32 row statistics, dQ, dK, dV in f32."""
    h, n_layer = cfg["n_head"], cfg["n_layer"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    t = seq_len
    pairs = rows * h * t * (t + 1) / 2
    fwd = 2.0 * pairs * (qk + dv)
    bwd = 2.0 * pairs * (qk + dv + dv + qk + qk)
    per_row = rows * h * t
    stats = per_row * 4
    fwd_bytes = per_row * (2 * qk + 2 * dv) * 2 + stats
    bwd_bytes = (per_row * (2 * qk + 3 * dv) * 2 + 2 * stats
                 + per_row * (2 * qk + dv) * 4)
    return {"flops": n_layer * (fwd + bwd),
            "bytes": n_layer * (fwd_bytes + bwd_bytes)}


def expert_ffn(cfg: dict, tokens: int, chips: int = 1) -> dict:
    """The grouped SwiGLU of one training step on one chip, over the rows
    its experts are expected to receive, `tokens` x k x held / G (each of
    the `chips` sends its tokens' rows for this chip's held / chips
    experts): forward (gate, up, down) and backward (each one's input and
    weight gradient), 2 FLOPs a multiply-add; bytes: the rows in and out
    of each matmul in bf16, and the chip's expert weights in bf16 read
    once each way."""
    d, f = cfg["n_embd"], cfg["moe_intermediate_size"]
    rows = (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])
    weights = cfg["n_routed_experts"] // chips * _expert_params(cfg) * 2
    fwd_rows = rows * (d + 2 * f + f + d) * 2       # x in, gate/up out,
    bwd_rows = 2 * fwd_rows                         # h in, y out; twice back
    return {"flops": _moe_layers(cfg) * 18.0 * rows * d * f,
            "bytes": _moe_layers(cfg) * (2 * weights + fwd_rows + bwd_rows)}
