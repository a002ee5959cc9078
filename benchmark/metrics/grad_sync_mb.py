"""grad_sync_mb: the bytes a chip hands the step's all-reduces in one
step, in MB (1e6 B), counted from the compiled step's HLO by
`gloo_tpu.tpu.collective_stats` (`benchmark/phases.py`). Moves
tokens_per_s. Nothing when the step has no all-reduce or the checkout
has no such counter."""

from benchmark import phases


def read(run):
    stats = phases.step_stats(run)
    if stats is None or not any(op.startswith("all-reduce")
                                for op in stats.calls):
        return None
    return sum(b for op, b in stats.bytes.items()
               if op.startswith("all-reduce")) / 1e6
