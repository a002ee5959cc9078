"""Parallelism strategies (DDP / TP / ring attention) on the CPU mesh,
plus host-plane DDP gradient sync through the C++ transport."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
optax = pytest.importorskip("optax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from gloo_tpu.models import MLP, Transformer, TransformerConfig  # noqa: E402
from gloo_tpu.parallel import (HostGradSync, make_ddp_train_step,  # noqa: E402
                               ring_attention, tp_mlp_block)
from gloo_tpu.tpu import make_mesh  # noqa: E402
from tests.harness import spawn  # noqa: E402


def test_ddp_mlp_converges():
    mesh = make_mesh({"data": -1})
    model = MLP([8, 32, 1])
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optax.adam(1e-2)
    opt_state = optimizer.init(params)
    step = make_ddp_train_step(model.loss, optimizer, mesh)

    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) * 0.5).astype(np.float32)

    losses = []
    for _ in range(60):
        params, opt_state, loss = step(params, opt_state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, losses[::20]


def test_ddp_matches_single_device():
    """DDP gradients over the mesh must equal full-batch gradients."""
    mesh = make_mesh({"data": -1})
    model = MLP([4, 8, 2])
    params = model.init(jax.random.PRNGKey(1))
    optimizer = optax.sgd(0.1)
    opt_state = optimizer.init(params)
    step = make_ddp_train_step(model.loss, optimizer, mesh)

    rng = np.random.RandomState(1)
    x = rng.randn(16, 4).astype(np.float32)
    y = rng.randn(16, 2).astype(np.float32)

    p_ddp, _, loss_ddp = step(params, opt_state, (x, y))

    loss_ref, grads_ref = jax.value_and_grad(model.loss)(params, (x, y))
    updates, _ = optimizer.update(grads_ref, optimizer.init(params), params)
    p_ref = optax.apply_updates(params, updates)

    assert abs(float(loss_ddp) - float(loss_ref)) < 1e-5
    for a, b in zip(jax.tree.leaves(p_ddp), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_tp_mlp_block_matches_dense():
    mesh = make_mesh({"model": -1})
    p = mesh.shape["model"]
    d, ff = 16, 32 * p
    rng = np.random.RandomState(2)
    x = rng.randn(4, d).astype(np.float32)
    w_up = rng.randn(d, ff).astype(np.float32) * 0.1
    w_down = rng.randn(ff, d).astype(np.float32) * 0.1

    def shard_fn(x, w_up_s, w_down_s):
        return tp_mlp_block(x, w_up_s, w_down_s, "model")

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, "model"), P("model", None)),
        out_specs=P()))
    got = np.asarray(f(x, w_up, w_down))
    expected = np.asarray(jax.nn.gelu(x @ w_up) @ w_down)
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 2, 2, 8 * p, 4
    rng = np.random.RandomState(3)
    q = rng.randn(b, h, t, d).astype(np.float32)
    k = rng.randn(b, h, t, d).astype(np.float32)
    v = rng.randn(b, h, t, d).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"),
                  P(None, None, "seq")),
        out_specs=P(None, None, "seq")))
    got = np.asarray(f(q, k, v))

    scores = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((t, t), bool))
        scores = np.where(mask, scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expected = np.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_transformer_trains():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=16)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optax.adam(1e-2)
    opt_state = optimizer.init(params)
    mesh = make_mesh({"data": -1})
    step = make_ddp_train_step(model.loss, optimizer, mesh)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, (8, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, (tokens, targets))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_host_grad_sync_matches_mean():
    """DDP over the host plane: per-process grads averaged via C++ allreduce."""
    size = 4

    def fn(ctx, rank):
        grads = {
            "w": np.full((5, 3), float(rank), dtype=np.float32),
            "b": np.arange(3, dtype=np.float32) * (rank + 1),
        }
        sync = HostGradSync(ctx)
        avg = sync.average(grads)
        return {k: np.asarray(v) for k, v in avg.items()}

    results = spawn(size, fn)
    w_expect = np.full((5, 3), np.mean(range(size)), np.float32)
    b_expect = np.arange(3, dtype=np.float32) * np.mean(
        [r + 1 for r in range(size)])
    for res in results:
        np.testing.assert_allclose(res["w"], w_expect, rtol=1e-6)
        np.testing.assert_allclose(res["b"], b_expect, rtol=1e-6)


def test_pipeline_parallel_matches_sequential():
    """GPipe schedule over the mesh == applying all stages sequentially."""
    from gloo_tpu.parallel import pipeline_apply

    mesh = make_mesh({"pipe": -1})
    stages = mesh.shape["pipe"]
    d, m = 8, 5  # feature width, microbatches
    rng = np.random.RandomState(7)
    ws = rng.randn(stages, d, d).astype(np.float32) * 0.3
    x = rng.randn(m, 4, d).astype(np.float32)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def shard_fn(w_stage, xs):
        return pipeline_apply(stage_fn, w_stage[0], xs, "pipe")

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("pipe"), P()), out_specs=P("pipe")))
    # Output lives on the last stage: take its block.
    out = np.asarray(f(ws, x))
    got = out.reshape(stages, m, 4, d)[stages - 1]

    expected = x
    for s in range(stages):
        expected = np.tanh(expected @ ws[s])
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held", [True, False],
                         ids=["routed", "unheld_expert"])
def test_expert_parallel_one_expert_a_device(held):
    """One expert a device, top-1: each token comes back from the expert
    its router picked, weighted by the router's score. A token whose
    expert no device on the axis holds (the router scores twice the
    experts held) gets zeros, not another expert's output."""
    from gloo_tpu.parallel import moe

    mesh = make_mesh({"expert": -1})
    n = mesh.shape["expert"]
    g = 2 * n
    t_local, f = 8, 4
    rng = np.random.RandomState(9)
    choice = rng.randint(0, n, n * t_local) + (0 if held else n)
    x = (np.eye(g, dtype=np.float32)[choice]
         + 0.01 * rng.randn(n * t_local, g).astype(np.float32))
    router = (20.0 * np.eye(g)).astype(np.float32)
    wg, wu = (rng.randn(n, g, f).astype(np.float32) for _ in range(2))
    wd = rng.randn(n, f, g).astype(np.float32)

    def shard_fn(x, router, wg, wu, wd):
        return moe(x, router, wg, wu, wd, first_expert=jax.lax.axis_index(
            "expert"), top_k=1, axis="expert")[0]

    out = np.asarray(jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("expert"), P(), P("expert"), P("expert"), P("expert")),
        out_specs=P("expert")))(x, router, wg, wu, wd))
    if not held:
        np.testing.assert_array_equal(out, np.zeros_like(out))
        return
    logits = x @ router
    score = np.exp(logits - logits.max(1, keepdims=True))
    score = (score / score.sum(1, keepdims=True))[np.arange(len(x)), choice]
    gate = np.einsum("td,tdf->tf", x, wg[choice])
    up = np.einsum("td,tdf->tf", x, wu[choice])
    expected = score[:, None] * np.einsum(
        "tf,tfd->td", gate / (1 + np.exp(-gate)) * up, wd[choice])
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_attention_matches_full(causal):
    """Ring rotation x flash inner kernel == full attention."""
    from gloo_tpu.parallel import ring_flash_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 1, 2, 16 * p, 128
    rng = np.random.RandomState(3)
    q = rng.randn(b, h, t, d).astype(np.float32)
    k = rng.randn(b, h, t, d).astype(np.float32)
    v = rng.randn(b, h, t, d).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, "seq", causal=causal,
                                             block_q=8, block_k=8,
                                             interpret=True),
        mesh=mesh,
        in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq"), check_vma=False))
    got = np.asarray(f(q, k, v))

    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    expected = np.einsum("bhqk,bhkd->bhqd", pr, v)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_attention_grads(causal):
    """VJP of the ring-flash path == grads of full attention."""
    from gloo_tpu.parallel import ring_flash_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 1, 1, 16 * p, 32
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss_ring(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, "seq",
                                                 causal=causal, block_q=8,
                                                 block_k=8, interpret=True),
            mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"), check_vma=False)
        return jnp.sum(jnp.sin(f(q, k, v)))

    def loss_full(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", pr, v)))

    got = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_full(causal):
    """all-to-all head/seq exchange == full attention (needs h % n == 0)."""
    from gloo_tpu.parallel import ulysses_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 1, p, 8 * p, 16
    rng = np.random.RandomState(7)
    q = rng.randn(b, h, t, d).astype(np.float32)
    k = rng.randn(b, h, t, d).astype(np.float32)
    v = rng.randn(b, h, t, d).astype(np.float32)

    # Default attn path = the Pallas flash kernel (interpreted on the CPU
    # mesh, which requires check_vma=False on the enclosing shard_map).
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq", causal=causal),
        mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq"), check_vma=False))
    got = np.asarray(f(q, k, v))

    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    expected = np.einsum("bhqk,bhkd->bhqd", pr, v)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_ulysses_attention_vma_checked():
    """The all_to_all vma bookkeeping must hold under default
    check_vma=True (the flash default needs the interpreter on CPU and
    so can't run checked here; the reference oracle path can)."""
    from gloo_tpu.ops.attention import _reference_attention
    from gloo_tpu.parallel import ulysses_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 1, p, 8 * p, 16
    rng = np.random.RandomState(11)
    q = rng.randn(b, h, t, d).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq",
                                          attn_fn=_reference_attention),
        mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq")))
    got = np.asarray(f(q, q, q))

    s = np.einsum("bhqd,bhkd->bhqk", q, q) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    expected = np.einsum("bhqk,bhkd->bhqd", pr, q)
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_ulysses_attention_grads():
    """Ulysses is pure XLA ops — differentiable by construction."""
    from gloo_tpu.parallel import ulysses_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, t, d = 1, p, 8 * p, 16
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq"), check_vma=False)

    def loss_full(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", pr, v)))

    got = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_ulysses_attention_bad_heads():
    from gloo_tpu.parallel import ulysses_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    if p == 1:
        pytest.skip("needs >1 device")
    q = np.zeros((1, p + 1, 8 * p, 16), np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "seq"),
            mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"))(q, q, q)


def test_fsdp_matches_single_device_sgd():
    """Sharded params + autodiff-recovered reduce-scatter == plain SGD."""
    from gloo_tpu.parallel import (make_fsdp_train_step, shard_params,
                                   unshard_params)
    from gloo_tpu.models.mlp import MLP

    mesh = make_mesh({"data": -1})
    n = mesh.shape["data"]
    model = MLP([8, 17, 4])  # odd hidden width exercises the pad path
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(2)
    xs = jnp.asarray(rng.randn(4 * n, 8), jnp.float32)
    ys = jnp.asarray(rng.randn(4 * n, 4), jnp.float32)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2)

    lr = 0.1
    step = make_fsdp_train_step(loss_fn, params, "data", lr=lr)

    def run(params, xs, ys):
        sharded = shard_params(params, "data")
        losses = []
        for _ in range(3):
            sharded, loss = step(sharded, (xs, ys))
            losses.append(loss)
        return unshard_params(sharded, params, "data"), jnp.stack(losses)

    # unshard_params output is replicated in value but vma-varying (there
    # is no varying->invariant cast), so disable the replication check.
    final, losses = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))(params, xs, ys)

    # Oracle: plain full-batch SGD on one device.
    ref = params
    ref_losses = []
    for _ in range(3):
        l, g = jax.value_and_grad(loss_fn)(ref, (xs, ys))
        ref_losses.append(l)
        ref = jax.tree.map(lambda p, gr: p - lr * gr, ref, g)

    np.testing.assert_allclose(np.asarray(losses),
                               np.asarray(jnp.stack(ref_losses)),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    assert float(losses[2]) < float(losses[0])


def test_ring_flash_attention_gqa():
    """GQA through the ring: smaller kv blocks rotate; grads group-sum."""
    from gloo_tpu.parallel import ring_flash_attention

    mesh = make_mesh({"seq": -1})
    p = mesh.shape["seq"]
    b, h, h_kv, t, d = 1, 2 * p, p, 16 * p, 32
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h_kv, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h_kv, t, d), jnp.float32)

    def loss_ring(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_flash_attention(q, k, v, "seq", block_q=8,
                                                 block_k=8, interpret=True),
            mesh=mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"), check_vma=False)
        return jnp.sum(jnp.sin(f(q, k, v)))

    def loss_full(q, k, v):
        kx = jnp.repeat(k, h // h_kv, axis=1)
        vx = jnp.repeat(v, h // h_kv, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kx) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd",
                                          jax.nn.softmax(s, -1), vx)))

    got = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("m", [5, 8, 3])
def test_pipeline_1f1b_matches_sequential_grads(m):
    """1F1B training schedule == jax.grad of the sequentially composed
    model, per stage, summed over microbatches (the GPipe/direct
    oracle). Covers M > S, M == S, and the M < S corner."""
    from gloo_tpu.parallel import pipeline_train_1f1b

    mesh = make_mesh({"pipe": -1})
    stages = mesh.shape["pipe"]
    d = 6
    rng = np.random.RandomState(11)
    ws = rng.randn(stages, d, d).astype(np.float32) * 0.4
    x = rng.randn(m, 4, d).astype(np.float32)
    y = rng.randn(m, 4, d).astype(np.float32)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def loss_fn(out, target):
        return jnp.mean((out - target) ** 2)

    def shard_fn(w_stage, xs, ys):
        grads, loss = pipeline_train_1f1b(
            stage_fn, loss_fn, w_stage[0], xs, ys, "pipe")
        return grads[None], loss[None]

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("pipe"), P(), P()),
        out_specs=(P("pipe"), P("pipe"))))
    grads, losses = f(ws, x, y)
    grads = np.asarray(grads)          # (stages, d, d)
    loss_sum = float(np.asarray(losses)[-1])  # last stage accumulates

    # Oracle: compose all stages, sum the per-microbatch loss, jax.grad.
    def full_loss(all_ws):
        total = 0.0
        for i in range(m):
            h = x[i]
            for s in range(stages):
                h = stage_fn(all_ws[s], h)
            total = total + loss_fn(h, y[i])
        return total

    ref_loss = float(full_loss(ws))
    ref_grads = np.asarray(jax.grad(full_loss)(ws))
    np.testing.assert_allclose(loss_sum, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(grads, ref_grads, rtol=2e-4, atol=1e-6)


def test_1f1b_tables_shape_and_memory_bound():
    """The timetable is the classic 2(M+S-1) ticks for M >= S, every
    microbatch is forwarded and backwarded exactly once per stage, and
    the in-flight window (forwarded, not yet backwarded) never exceeds
    the stage's 1F1B bound — the invariant that lets every runtime
    buffer be sized S instead of M."""
    from gloo_tpu.parallel.pp import _build_1f1b_tables

    for stages, m in [(2, 3), (4, 8), (4, 4), (8, 8), (3, 12)]:
        fwd, bwd = _build_1f1b_tables(stages, m)
        if m >= stages:
            assert fwd.shape[0] == 2 * (m + stages - 1), (stages, m)
        for s in range(stages):
            fs = [i for i in fwd[:, s] if i >= 0]
            bs = [i for i in bwd[:, s] if i >= 0]
            assert fs == list(range(m)) and bs == list(range(m))
            inflight = 0
            peak = 0
            for t in range(fwd.shape[0]):
                inflight += fwd[t, s] >= 0
                inflight -= bwd[t, s] >= 0
                peak = max(peak, inflight)
            assert peak <= min(stages - s, m), (stages, m, s, peak)
