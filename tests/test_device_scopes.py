"""Device-plane instrumentation: the DDP step's phase scopes in the
compiled program's `op_name` metadata, and `collective_stats`, the count
of collective calls and bytes a compiled program sends per device."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gloo_tpu.models import Transformer, TransformerConfig
from gloo_tpu.parallel import make_ddp_train_step
from gloo_tpu.tpu import collective_stats
from gloo_tpu.tpu.hlo_stats import array_bytes

_ALL_REDUCE = re.compile(r"%([\w.\-]+) = .*? all-reduce(?:-start)?\(")


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.asarray(jax.devices()[:4]), ("data",))


def _placed(tree, mesh, spec):
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def ddp_compiled(mesh4):
    """The DDP step of a tiny Transformer with the flash kernel, compiled
    for 4 CPU devices, as the benchmark's recipe builds it."""
    model = Transformer(TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=256,
        max_seq_len=64, use_flash_attention=True))
    opt = optax.adamw(1e-3)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.ShapeDtypeStruct((8, 64), jnp.int32)
    step = make_ddp_train_step(model.loss, opt, mesh4)
    return step.lower(_placed(params, mesh4, P()),
                      _placed(opt_state, mesh4, P()),
                      _placed((batch, batch), mesh4, P("data"))).compile()


def test_ddp_phase_scopes(ddp_compiled):
    text = ddp_compiled.as_text()
    names = collective_stats(ddp_compiled).op_names
    reduces = _ALL_REDUCE.findall(text)
    assert reduces
    # The gradient all-reduce is AD's transpose of the replicated params.
    for instr in reduces:
        assert "gloo_tpu.ddp.loss/transpose(" in names[instr], names[instr]
    assert any("gloo_tpu.ddp.loss/jvp(" in n for n in names.values())
    # Outside the shard_map region the step only updates: every op there
    # is the optimizer's.
    update = [n for n in names.values() if n.startswith("jit(step)/")
              and not n.startswith("jit(step)/shard_map")]
    assert update
    assert all("gloo_tpu.ddp.optimizer/" in n for n in update), update
    assert not any("grad_sync" in n for n in names.values())


def test_collective_stats_counts_exact_bytes(mesh4):
    """A psum of an f32 and a bf16 array: each device hands the
    all-reduce its own shard of both, whether or not the combiner merges
    them into one call, in the dtype the compiled all-reduce carries (the
    CPU backend reduces bf16 in f32)."""

    def body(a, b):
        return jax.lax.psum(a, "data"), jax.lax.psum(b, "data")

    f = jax.jit(jax.shard_map(body, mesh=mesh4,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P(), P())))
    a = jax.ShapeDtypeStruct((4 * 16, 8), jnp.float32,
                             sharding=NamedSharding(mesh4, P("data")))
    b = jax.ShapeDtypeStruct((4 * 32, 4), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, P("data")))
    compiled = f.lower(a, b).compile()
    stats = collective_stats(compiled)
    calls = sum(n for op, n in stats.calls.items()
                if op.startswith("all-reduce"))
    assert calls >= 1
    moved = {}
    for op, split in stats.dtypes.items():
        assert op.startswith("all-reduce")
        for dtype, n in split.items():
            moved[dtype] = moved.get(dtype, 0) + n
    in_bf16 = any("bf16[" in line.split(" = ")[1].split("all-reduce")[0]
                  for line in compiled.as_text().splitlines()
                  if _ALL_REDUCE.search(line))
    expected = ({"f32": 16 * 8 * 4, "bf16": 32 * 4 * 2} if in_bf16
                else {"f32": 16 * 8 * 4 + 32 * 4 * 4})
    assert moved == expected
    assert sum(stats.bytes.values()) == sum(expected.values())


def test_collective_stats_on_text():
    """Async pairs count at their start; operands are found by name in
    any computation; a tuple operand counts every element."""
    text = """HloModule m

%wrapped (p: f32[8]) -> f32[32] {
  %p = f32[8]{0} parameter(0)
  ROOT %ag = f32[32]{0} all-gather(%p), dimensions={0}, metadata={op_name="jit(f)/gather"}
}

ENTRY %main (x: f32[8], y: bf16[2,4]) -> f32[8] {
  %x = f32[8]{0:T(256)} parameter(0), metadata={op_name="x"}
  %y = bf16[2,4]{1,0} parameter(1)
  %t = (f32[8]{0}, bf16[2,4]{1,0}) tuple(%x, %y)
  %ars = (f32[8]{0}, bf16[2,4]{1,0}) all-reduce-start(%x, %y), to_apply=%add, metadata={op_name="jit(f)/psum"}
  %ard = (f32[8]{0}, bf16[2,4]{1,0}) all-reduce-done(%ars)
  %cp = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1}}
  ROOT %r = f32[8]{0} add(%x, %x)
}
"""
    stats = collective_stats(text)
    assert stats.calls == {"all-gather": 1, "all-reduce-start": 1,
                           "collective-permute": 1}
    assert stats.bytes == {"all-gather": 32, "all-reduce-start": 32 + 16,
                           "collective-permute": 32}
    assert stats.dtypes["all-reduce-start"] == {"f32": 32, "bf16": 16}
    assert stats.op_names == {"ag": "jit(f)/gather", "x": "x",
                              "ars": "jit(f)/psum"}


@pytest.mark.parametrize("text,expected", [
    ("(bf16[768,2304]{1,0:T(8,128)(2,1)}, f32[]{:T(128)})",
     {"bf16": 768 * 2304 * 2, "f32": 4}),
    ("/*index=5*/f32[50304,768]{1,0:T(8,128)}", {"f32": 50304 * 768 * 4}),
    ("pred[3]", {"pred": 3}),
    ("s4[4]", {"s4": 2}),
    ("token[]", {"token": 0}),
])
def test_array_bytes(text, expected):
    assert array_bytes(text) == expected
