"""expert_ffn_roofline: the routed experts' grouped SwiGLU's share of its
roofline on chip 0. The least time is the larger of its FLOPs over the
bf16 peak and its bytes over the HBM bandwidth for the rows the chip's
experts are expected to receive (`benchmark/flops/<family>.py`'s
`expert_ffn`: forward and backward, the rematerialized forward not
counted); the time is expert_ffn_ms. A grouped matmul that costs its
worst-case buffer, not the rows that came, reads low. Moves
tokens_per_s. Nothing when the step carries no such scope."""

from benchmark import ep_scopes


def read(run):
    ms = ep_scopes.part_ms(run, "experts")
    if ms is None:
        return None
    need = run.flops.expert_ffn(
        run.config, run.traffic["batch_per_chip"] * run.traffic["seq_len"],
        run.chips)
    least = max(need["flops"] / run.peaks["bf16_flops"],
                need["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
