"""grad_sync_exposed_ms: the part of grad_sync_ms in which no other
operation runs on chip 0, a step. Moves tokens_per_s. Nothing on one
chip."""

from benchmark.trace import is_all_reduce


def read(run):
    t = run.trace
    ns, steps = t.op_ns(t.chips[0], is_all_reduce)
    if not ns:
        return None
    exposed, steps = t.exposed_ns(t.chips[0], is_all_reduce)
    return exposed / 1e6 / steps
