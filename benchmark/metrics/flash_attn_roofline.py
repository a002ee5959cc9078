"""flash_attn_roofline: the flash kernels' (forward and fused backward)
share of their roofline on chip 0. The least time is the larger of their
FLOPs over the bf16 peak and their bytes over the HBM bandwidth, from the
shapes (`benchmark/flops/<family>.py`); the time is the summed device
time of the kernels' events. Moves tokens_per_s. Nothing when the trace
holds no flash kernel."""

from benchmark.trace import is_flash


def read(run):
    t = run.trace
    ns, steps = t.op_ns(t.chips[0], is_flash)
    if ns == 0:
        return None
    need = run.flops.flash_attention(run.config,
                                     run.traffic["batch_per_chip"],
                                     run.traffic["seq_len"])
    least = max(need["flops"] / run.peaks["bf16_flops"],
                need["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9 / steps)
