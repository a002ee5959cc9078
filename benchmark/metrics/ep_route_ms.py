"""ep_route_ms: device time a step on chip 0 of the expert layer's
permutation overhead: the non-collective ops under `gloo_tpu.ep.route`,
`.dispatch` and `.combine` (router, top-k, sort, the rows' gathers, the
offsets, the weighted sum), forward and transposed
(`benchmark/ep_scopes.py`). Moves tokens_per_s. Nothing when the step
carries no such scope."""

from benchmark import ep_scopes


def read(run):
    return ep_scopes.part_ms(run, "route", "dispatch", "combine")
