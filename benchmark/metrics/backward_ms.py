"""backward_ms: device time a step, on chip 0, of the ops under
`gloo_tpu.ddp.loss` inside AD's `transpose(`: the backward pass, its
all-reduces left out (grad_sync_ms has them), and whatever the compiler
fused into its kernels (`benchmark/phases.py`). Moves tokens_per_s.
Nothing when the step carries no such scope."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "backward")
