"""mfu_pct: the whole step's share of the chips' bf16 peak, from the
device trace. Model FLOPs of the steps in the traced window (PaLM's
convention, `benchmark/flops/<family>.py`) over the window's length on
chip 0, over chips x peak. Moves tokens_per_s."""


def read(run):
    t = run.trace
    lo, hi, steps = t.window(t.chips[0])
    flops = (run.flops.flops_per_token(run.config, run.traffic["seq_len"])
             * run.tokens_per_step * steps)
    return 100.0 * flops / ((hi - lo) / 1e9) / (
        run.chips * run.peaks["bf16_flops"])
