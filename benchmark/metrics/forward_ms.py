"""forward_ms: device time a step, on chip 0, of the ops whose HLO
op_name lies under the DDP step's `gloo_tpu.ddp.loss` scope and outside
AD's `transpose(`: the forward pass and the loss (`benchmark/phases.py`).
Moves tokens_per_s. Nothing when the step carries no such scope."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "forward")
