"""Compile the main path's kernels and step for a described TPU v5e.

Nothing runs: the TPU compiler installed here compiles for a v5e:2x2 that
is described, not attached, and refuses what the chip would refuse
(misaligned tiles, too much VMEM, a program larger than HBM). Sizes are
chip_smoke.py's. The topology is described inside a fixture, never while a
module is imported: only one process at a time may load libtpu, and every
xdist worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import chip_smoke

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4], dtype=object), ("data",))


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB per chip"


def test_flash_attention_fwd_bwd(one_chip):
    from gloo_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    x = jax.ShapeDtypeStruct((1, 12, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)
    _check(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
           .compile())


@pytest.mark.parametrize("name", ["ring_allreduce", "ring_allreduce_hbm",
                                  "ring_reduce_scatter", "ring_allgather",
                                  "ring_allreduce_q8", "pallas_alltoall"])
def test_ring_four_chips(mesh4, name):
    n = mesh4.size
    cases = {c[0]: c for c in chip_smoke._ring_cases(n, interpret=False)}
    _, pallas, _, rows, _ = cases[name]
    fn = jax.jit(jax.shard_map(pallas, mesh=mesh4, in_specs=P("data"),
                               out_specs=P("data"), check_vma=False))
    x = jax.ShapeDtypeStruct((n * rows, chip_smoke.RING_COLS), jnp.float32,
                             sharding=NamedSharding(mesh4, P("data")))
    _check(fn.lower(x).compile())


@pytest.fixture(scope="module")
def gpt2_small_step(topo):
    """chip_smoke's training step, compiled for one described chip. The
    model picks the Pallas interpreter when jax.default_backend() is
    "cpu", which it is here: steer it to the compiled kernel while the
    step compiles."""
    import optax

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step

    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), ("data",))
    cfg = TransformerConfig(**chip_smoke.GPT2_SMALL,
                            use_flash_attention=True)
    model = Transformer(cfg)
    opt = optax.adamw(chip_smoke.LR)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    batch = jax.ShapeDtypeStruct((8, cfg.max_seq_len), jnp.int32)
    step = make_ddp_train_step(model.loss, opt, mesh)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return step.lower(placed(params, P()), placed(opt_state, P()),
                          placed((batch, batch), P("data"))).compile()


def test_gpt2_small_ddp_step(gpt2_small_step):
    _check(gpt2_small_step)


def test_gpt2_small_loss_writes_logits_once(gpt2_small_step):
    """The loss reads the f32 logits [8, 1024, vocab] and writes no second
    tensor of that size: of the top-level fusions, only the logits matmul
    outputs one. A loss taken from a materialized log_softmax adds a
    second, and a backward pass that reduces over it."""
    text = gpt2_small_step.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    vocab = chip_smoke.GPT2_SMALL["vocab_size"]
    wide = [m[1] for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%(\S+) = (.*?) fusion\(", entry, re.MULTILINE)
        if f"f32[8,1024,{vocab}]" in m[2]]
    assert len(wide) == 1, wide


def test_gpt2_small_ddp_step_sends(topo, monkeypatch):
    """What the four-chip GPT-2-small DDP step hands its all-reduces on
    each chip, counted by `collective_stats` from the compiled program:
    the gradient of every param once, the embedding's and the learned
    positions' in f32, every other param's in bf16 (the layers cast them
    before use, and AD's transpose of the cast is where the psum lands),
    and the loss's f32 scalar."""
    import optax

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step
    from gloo_tpu.tpu import collective_stats

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4], dtype=object), ("data",))
    model = Transformer(TransformerConfig(**chip_smoke.GPT2_SMALL,
                                          use_flash_attention=True))
    opt = optax.adamw(chip_smoke.LR)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    batch = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    step = make_ddp_train_step(model.loss, opt, mesh)
    stats = collective_stats(step.lower(
        placed(params, P()), placed(jax.eval_shape(opt.init, params), P()),
        placed((batch, batch), P("data"))).compile())
    f32 = params["embed"].size + params["pos"].size
    bf16 = sum(x.size for x in jax.tree.leaves(params)) - f32
    assert stats.dtypes == {"all-reduce": {"f32": 4 * f32 + 4,
                                           "bf16": 2 * bf16}}
    assert stats.bytes["all-reduce"] == 327_587_332
    assert stats.calls["all-reduce"] == 4


def test_gpt2_small_step_unchanged_by_explicit_replicated_specs(
        topo, monkeypatch):
    """`make_ddp_train_step` with `param_specs` all `P()` compiles the
    four-chip GPT-2-small step to the same optimized HLO as without it."""
    import optax

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4], dtype=object), ("data",))
    model = Transformer(TransformerConfig(**chip_smoke.GPT2_SMALL,
                                          use_flash_attention=True))
    opt = optax.adamw(chip_smoke.LR)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    batch = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    args = (placed(params, P()), placed(jax.eval_shape(opt.init, params),
                                        P()),
            placed((batch, batch), P("data")))

    def hlo(**kw):
        """The optimized HLO without source locations: the metadata and
        the stack-frame tables name the lines that built the step."""
        step = make_ddp_train_step(model.loss, opt, mesh, **kw)
        text = step.lower(*args).compile().as_text()
        return [re.sub(r",? metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if line[:1] in "H%E }"]

    specs = jax.tree.map(lambda _: P(), params)
    assert hlo() == hlo(param_specs=specs)


DSV2_CHIPS = (1, 4)


@pytest.fixture(scope="module")
def dsv2_steps(topo):
    """DeepSeek-V2-Lite's step at full size (8192 tokens, 2 rows a chip),
    through the benchmark's `ep` recipe with the `dsv2l-ep1-s8k` cell's
    configuration, compiled for the described chips: one chip, and four
    with experts split 2 a chip."""
    from benchmark import ep_scopes, harness

    cell = harness.load_cell("dsv2l-ep1-s8k")
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        for chips in DSV2_CHIPS:
            out[chips] = ep_scopes.compile_step(
                cell.config, dict(cell.traffic, data_parallel=chips),
                topo.devices[:chips])
    return out


@pytest.mark.parametrize("chips", DSV2_CHIPS)
def test_dsv2_lite_step_fits(dsv2_steps, chips):
    """The flash kernel is in; inputs, temporaries and the outputs that do
    not reuse a donated input fit one chip's memory, and the params and
    AdamW state are donated (a step in flight holds one copy of them)."""
    compiled = dsv2_steps[chips]
    _check(compiled)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB per chip"


def test_dsv2_lite_exchange_is_ragged_and_only_on_four_chips(dsv2_steps):
    """One chip exchanges nothing. Four chips move the routed rows with
    `ragged-all-to-all`: 4 MoE layers x dispatch and combine x forward,
    rematerialized forward and transpose; no all-reduce lies under the
    expert layer's scopes (a receive buffer typed invariant would make
    AD sum its cotangent across chips)."""
    from gloo_tpu.tpu import collective_stats

    assert collective_stats(dsv2_steps[1]).calls == {}
    compiled = dsv2_steps[4]
    stats = collective_stats(compiled)
    assert stats.calls["ragged-all-to-all"] == 24
    assert stats.dtypes["ragged-all-to-all"]["bf16"] > 0
    reduces = re.findall(r"all-reduce(?:-start)?\(.*op_name=\"([^\"]*)\"",
                         compiled.as_text())
    assert reduces and not [n for n in reduces if "gloo_tpu.ep." in n]


def test_dsv2_lite_permutation_stays_in_token_space(dsv2_steps):
    """On one chip, the top-level instructions under `gloo_tpu.ep.route`
    and `.combine` (what `ep_route_ms` reads) write no (T, k, D) tensor
    and at most 16 of the worst-case (T k, D) row buffers: per MoE layer
    the rows' sort, its rematerialization, dy's rows in sorted order and
    their cotangent. Validity and weights live in token space, so no mask
    runs over a buffer."""
    from benchmark import ep_scopes

    text = dsv2_steps[1].as_text()
    entry = text[text.index("\nENTRY "):]
    shapes = []
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%(\S+) = (\S+) ([a-z][a-z0-9-]*)"
                         r"\(.*op_name=\"([^\"]*)\"", entry[:entry.index("\n}")],
                         re.MULTILINE):
        if ep_scopes.part(f"{m[1]} {m[3]}", m[4]) in ("route", "combine"):
            shapes.append(re.sub(r"\{[^{}]*\}", "", m[2]))
    assert shapes
    assert "bf16[16384,6,2048]" not in shapes
    assert shapes.count("bf16[98304,2048]") <= 16
