"""grad_sync_ms: device time a step of the all-reduce operations on
chip 0, found by op kind. Moves tokens_per_s. Nothing on one chip."""

from benchmark.trace import is_all_reduce


def read(run):
    t = run.trace
    ns, steps = t.op_ns(t.chips[0], is_all_reduce)
    return ns / 1e6 / steps if ns else None
