"""expert_ffn_ms: device time a step on chip 0 of the routed experts'
grouped SwiGLU: the ops under `gloo_tpu.ep.experts` and XLA's grouped
matmul kernels, forward, rematerialized and transposed
(`benchmark/ep_scopes.py`). Moves tokens_per_s. Nothing when the step
carries no such scope."""

from benchmark import ep_scopes


def read(run):
    return ep_scopes.part_ms(run, "experts")
