"""The expert layer's parts on chip 0, from the traced window's ops and the
`op_name` metadata of the compiled step, for the `ep` recipe's cells.

gloo_tpu's `moe` scopes its parts: `gloo_tpu.ep.route` (router, top-k,
sort, the rows' gather), `gloo_tpu.ep.dispatch` (the counts' and the
rows' exchange), `gloo_tpu.ep.experts` (the grouped SwiGLU),
`gloo_tpu.ep.combine` (the reverse exchange, the weighted sum). AD keeps
the scopes in the backward (`transpose(`), and the rematerialized
forward too. As `phases.py` does for the DDP recipe, `step_stats`
compiles the cell's step once more for abstract shapes with the cell's
shardings and reads `collective_stats` of it for every instruction's
`op_name`. XLA's grouped-matmul kernels (`ragged-dot-*` custom calls)
carry their own name in place of the scope, and are the experts' by
that name. A checkout whose gloo_tpu has no such counter gives None.
"""

from __future__ import annotations

import functools
import json
import re

import numpy as np

SCOPES = ("route", "dispatch", "experts", "combine")
_SCOPE = re.compile(r"gloo_tpu\.ep\.(" + "|".join(SCOPES) + r")\b")
_COLLECTIVE = ("all-", "ragged-all-to-all", "reduce-scatter",
               "collective-permute")


def part(name: str, op_name: str):
    """The expert layer's part the op `name` (`<instruction> <opcode>
    ...`) belongs to: one of SCOPES by the last `gloo_tpu.ep.<part>` in
    its `op_name` (AD writes `jvp(gloo_tpu.ep.route)/...` as well as
    `.../gloo_tpu.ep.route/...`), or "experts" for XLA's grouped matmul
    kernels; None for any collective (the rows' exchange, the counts'
    all-gather, the gradient all-reduce) and for every other op."""
    if name.split(" ")[1].startswith(_COLLECTIVE):
        return None
    if name.startswith("ragged-dot"):
        return "experts"
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def step_stats(run):
    """`collective_stats` of the cell's EP step, or None where the
    checkout's gloo_tpu has no such counter."""
    import gloo_tpu.tpu

    if not hasattr(gloo_tpu.tpu, "collective_stats"):
        return None
    return _stats(json.dumps(run.config, sort_keys=True),
                  json.dumps(run.traffic, sort_keys=True), run.chips)


@functools.lru_cache(maxsize=None)
def _stats(config: str, traffic: str, chips: int):
    import jax

    from gloo_tpu.tpu import collective_stats

    return collective_stats(compile_step(json.loads(config),
                                         json.loads(traffic),
                                         jax.devices()[:chips]))


def compile_step(cfg: dict, traffic: dict, devices):
    """The `ep` recipe's step compiled for abstract shapes on a `data`
    mesh of `devices` (no buffer is made)."""
    import jax
    from jax.sharding import Mesh

    from benchmark import generate, harness

    mesh = Mesh(np.asarray(devices, dtype=object), ("data",))
    system = harness.module("recipes", "ep").build(cfg, mesh)
    ref = harness.module("references", cfg["family"])
    params = jax.eval_shape(functools.partial(ref.init_params, cfg),
                            ref.seed_words(0))
    opt_state = jax.eval_shape(system.init_opt, params)

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    batch = jax.ShapeDtypeStruct(
        (generate.global_rows(traffic), traffic["seq_len"]), np.int32,
        sharding=system.batch_sharding)
    return system.step.lower(
        placed(params, system.param_sharding),
        placed(opt_state, system.opt_sharding), (batch, batch)).compile()


def part_ms(run, *which: str):
    """Device time a step on chip 0 of the one-at-a-time and asynchronous
    ops of the parts `which` inside the traced window; None where there
    are none."""
    stats = step_stats(run)
    if stats is None:
        return None
    t = run.trace
    chip = t.chips[0]
    ns, steps = t.op_ns(chip, lambda name: part(
        name, stats.op_names.get(name.split(" ")[0], "")) in which)
    return ns / 1e6 / steps if ns else None
