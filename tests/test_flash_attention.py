"""Pallas flash attention: correctness vs materialized attention (CPU
interpret mode; real-chip validation rides the graft/TPU checks)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from gloo_tpu.ops import flash_attention  # noqa: E402
from gloo_tpu.ops.attention import _reference_attention  # noqa: E402


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 2, 128, 128  # asymmetric blocks below cover t != block
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    out = np.asarray(flash_attention(q, k, v, causal=causal, block_q=64,
                                     block_k=32, interpret=True))
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k))
    s /= np.sqrt(d)
    if causal:
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_transformer_with_flash_attention():
    """Transformer forward with the flash path matches the default path
    (same weights) within matmul-precision tolerance."""
    from gloo_tpu.models import Transformer, TransformerConfig

    base = TransformerConfig(vocab_size=64, d_model=64, n_heads=2,
                             n_layers=1, d_ff=128, max_seq_len=64,
                             dtype=jnp.float32)
    flash = TransformerConfig(vocab_size=64, d_model=64, n_heads=2,
                              n_layers=1, d_ff=128, max_seq_len=64,
                              dtype=jnp.float32, use_flash_attention=True)
    m0, m1 = Transformer(base), Transformer(flash)
    params = m0.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 64)), jnp.int32)
    # Flash path in interpret mode isn't reachable through the model flag;
    # on CPU, pallas needs interpret — monkeypatch for the comparison.

    orig_platform = jax.devices()[0].platform
    if orig_platform != "tpu":
        import sys

        # The package re-export shadows the submodule attribute; fetch the
        # real module from sys.modules.
        fmod = sys.modules["gloo_tpu.ops.attention"]
        real = fmod.flash_attention

        def interp(*a, **kw):
            kw["interpret"] = True
            return real(*a, **kw)

        fmod.flash_attention = interp
        try:
            y0 = np.asarray(m0.apply(params, tokens))
            y1 = np.asarray(m1.apply(params, tokens))
        finally:
            fmod.flash_attention = real
    else:
        y0 = np.asarray(m0.apply(params, tokens))
        y1 = np.asarray(m1.apply(params, tokens))
    np.testing.assert_allclose(y0, y1, rtol=2e-3, atol=2e-3)


def test_flash_rejects_indivisible_seq():
    import jax.numpy as jnp
    import pytest as _pytest

    q = jnp.zeros((1, 1, 192, 128), jnp.float32)
    with _pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=128, block_k=128, interpret=True)


def test_largest_block_helper():
    from gloo_tpu.ops import largest_block

    assert largest_block(192) == 96
    assert largest_block(128) == 128
    assert largest_block(256) == 128
    assert largest_block(40) == 40


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 32), (32, 16)])
def test_flash_attention_trainable(causal, block_q, block_k):
    """Gradients through the dedicated backward kernels match the
    materialized path across causal modes and asymmetric blocks."""
    import sys

    import jax.numpy as jnp

    fmod = sys.modules["gloo_tpu.ops.attention"]
    rng = np.random.RandomState(0)
    b, h, t, d = 1, 2, 64, 128
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss_flash(q, k, v):
        return (fmod.flash_attention(q, k, v, causal=causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (fmod._reference_attention(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("h,h_kv", [(8, 2), (4, 1), (6, 3)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(h, h_kv, causal):
    """Grouped-query/multi-query: kv heads shared via index map; grads
    group-summed. Oracle: full attention on repeated kv heads."""
    b, t, d = 2, 32, 32
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h_kv, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h_kv, t, d), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)))

    def loss_ref(q, k, v):
        kx = jnp.repeat(k, h // h_kv, axis=1)
        vx = jnp.repeat(v, h // h_kv, axis=1)
        return jnp.sum(jnp.sin(_reference_attention(q, kx, vx, causal)))

    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    ref = _reference_attention(q, jnp.repeat(k, h // h_kv, axis=1),
                               jnp.repeat(v, h // h_kv, axis=1), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_flash_attention_gqa_bad_heads():
    q = jnp.zeros((1, 5, 32, 16), jnp.float32)
    k = jnp.zeros((1, 2, 32, 16), jnp.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, k, block_q=8, block_k=8, interpret=True)


def test_transformer_gqa_config():
    """GQA transformer (einsum path on CPU): trains, and the kv projection
    really shrinks."""
    from gloo_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq_len=32,
                            n_kv_heads=2, dtype=jnp.float32)
    m = Transformer(cfg)
    params = m.init(jax.random.PRNGKey(0))
    # wqkv: d_model query + 2 * (d_model/4 * 2) shared kv columns
    assert params["layers"][0]["wqkv"].shape == (64, 64 + 2 * 32)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    loss, grads = jax.value_and_grad(m.loss)(params, (toks, toks))
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))
    # 3 SGD steps reduce the loss
    p = params
    for _ in range(3):
        _, g = jax.value_and_grad(m.loss)(p, (toks, toks))
        p = jax.tree.map(lambda a, b: a - 0.5 * b, p, g)
    assert float(m.loss(p, (toks, toks))) < float(loss)


def test_transformer_gqa_flash_matches_einsum():
    """Same weights through the GQA flash path and the repeat-based
    einsum fallback: the two head-grouping conventions must agree."""
    import sys

    from gloo_tpu.models import Transformer, TransformerConfig

    kw = dict(vocab_size=64, d_model=64, n_heads=4, n_layers=1, d_ff=128,
              max_seq_len=64, n_kv_heads=2, dtype=jnp.float32)
    m0 = Transformer(TransformerConfig(**kw))
    m1 = Transformer(TransformerConfig(**kw, use_flash_attention=True))
    params = m0.init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 64, (2, 64)), jnp.int32)

    fmod = sys.modules["gloo_tpu.ops.attention"]
    real = fmod.flash_attention

    def interp(*a, **kwargs):
        kwargs["interpret"] = True
        return real(*a, **kwargs)

    if jax.devices()[0].platform != "tpu":
        fmod.flash_attention = interp
    try:
        y0 = np.asarray(m0.apply(params, tokens))
        y1 = np.asarray(m1.apply(params, tokens))
    finally:
        fmod.flash_attention = real
    np.testing.assert_allclose(y0, y1, rtol=2e-3, atol=2e-3)


def test_transformer_gqa_bad_config():
    from gloo_tpu.models import Transformer, TransformerConfig

    for bad in (0, 3):
        cfg = TransformerConfig(n_heads=4, n_kv_heads=bad)
        with pytest.raises(ValueError, match="positive multiple"):
            Transformer(cfg).init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("t,bq", [(512, 512), (1024, 512)])
def test_flash_large_square_tiles_match(t, bq):
    """Causal parity at the production tile shapes (square 512+ tiles,
    including t == bq: the whole sequence in one diagonal tile — the
    short-sequence serving configuration). Guards the diagonal-tile
    masked path at realistic tile sizes; r4 note: a strip-mined
    diagonal-tile variant was measured 2.1x SLOWER on v5e (thin strip
    matmuls + serialized online-softmax chains) and reverted."""
    rng = np.random.RandomState(5)
    b, h, d = 1, 2, 128
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    out = np.asarray(flash_attention(q, k, v, causal=True, block_q=bq,
                                     block_k=bq, interpret=True))
    ref = np.asarray(_reference_attention(q, k, v, causal=True))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
