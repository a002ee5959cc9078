"""The DDP step's phases on chip 0, from the traced window's ops and the
`op_name` metadata of the compiled step.

gloo_tpu's `make_ddp_train_step` scopes its phases: `gloo_tpu.ddp.loss`
around `jax.value_and_grad` and `gloo_tpu.ddp.optimizer` around the
update; AD puts the backward under `transpose(`. The v5e's op events
carry an instruction's name but not its `op_name`, so `step_stats`
compiles the cell's DDP step once more, for abstract shapes with the
cell's shardings (no device buffer is held; the compile cache holds the
program the window ran), and reads `gloo_tpu.tpu.collective_stats` of
it: every instruction's `op_name` and the collectives' bytes. A compile
is deterministic, so its instruction names are the ones the trace's
events bear. A checkout whose gloo_tpu has no such counter gives None,
and so does every reader of this module.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from benchmark.trace import is_all_reduce

LOSS_SCOPE = "gloo_tpu.ddp.loss/"
OPTIMIZER_SCOPE = "gloo_tpu.ddp.optimizer/"


def phase(name: str, op_name: str):
    """The phase of the op `name` (`<instruction> <opcode> ...`) with HLO
    `op_name`: "forward" (under `gloo_tpu.ddp.loss`, outside AD's
    transpose), "backward" (under it, inside `transpose(`), "optimizer"
    (under `gloo_tpu.ddp.optimizer`), "grad_sync" (any all-reduce: the
    gradient's is AD's transpose of the replicated params), else None."""
    if is_all_reduce(name):
        return "grad_sync"
    if LOSS_SCOPE in op_name:
        return ("backward" if "transpose(" in op_name.split(LOSS_SCOPE)[1]
                else "forward")
    if OPTIMIZER_SCOPE in op_name:
        return "optimizer"
    return None


def step_stats(run):
    """`collective_stats` of the cell's DDP step, or None where the
    checkout's gloo_tpu has no such counter."""
    import gloo_tpu.tpu

    if not hasattr(gloo_tpu.tpu, "collective_stats"):
        return None
    return _compiled(json.dumps(run.config, sort_keys=True),
                     json.dumps(run.traffic, sort_keys=True), run.chips)


@functools.lru_cache(maxsize=None)
def _compiled(config: str, traffic: str, chips: int):
    import jax
    from jax.sharding import Mesh

    from benchmark import generate, harness
    from gloo_tpu.tpu import collective_stats

    cfg, traffic = json.loads(config), json.loads(traffic)
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
    system = harness.module("recipes", "ddp").build(cfg, mesh)
    ref = harness.module("references", cfg["family"])
    params = jax.eval_shape(functools.partial(ref.init_params, cfg),
                            ref.seed_words(0))
    opt_state = jax.eval_shape(system.init_opt, params)
    batch = jax.ShapeDtypeStruct(
        (generate.global_rows(traffic), traffic["seq_len"]), np.int32)

    def placed(tree, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    return collective_stats(system.step.lower(
        placed(params, system.param_sharding),
        placed(opt_state, system.param_sharding),
        placed((batch, batch), system.batch_sharding)).compile())


def phase_ms(run, which: str):
    """Device time a step on chip 0 of the one-at-a-time ops of phase
    `which`, inside the traced window; None where nothing is scoped so."""
    stats = step_stats(run)
    if stats is None:
        return None
    t = run.trace
    chip = t.chips[0]
    lo, hi, steps = t.window(chip)
    ns = sum(min(e, hi) - max(s, lo) for name, s, e in t.ops[chip]
             if e > lo and s < hi and phase(
                 name, stats.op_names.get(name.split(" ")[0], "")) == which)
    return ns / 1e6 / steps if ns else None
