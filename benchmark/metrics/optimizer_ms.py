"""optimizer_ms: device time a step, on chip 0, of the ops under the DDP
step's `gloo_tpu.ddp.optimizer` scope: the update and its application to
the params, where the compiler did not fuse them into a backward kernel
(`benchmark/phases.py`). Moves tokens_per_s. Nothing when the step
carries no such scope."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "optimizer")
