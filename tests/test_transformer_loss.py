"""Transformer.loss computes each token's NLL as logsumexp(logits) minus
the target's logit. It must equal the textbook form, -log_softmax(logits)
at the target, in value and in every gradient, and stay finite where the
logits are far too large for exp."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from gloo_tpu.models import Transformer, TransformerConfig  # noqa: E402

CONFIGS = {
    "default": dict(),
    "gqa_rope_flash": dict(n_kv_heads=2, use_rope=True,
                           use_flash_attention=True),
}


def _reference_loss(model, params, batch):
    tokens, targets = batch
    logp = jax.nn.log_softmax(model.apply(params, tokens), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def _setup(name, seed=0):
    cfg = TransformerConfig(dtype=jnp.float32, **CONFIGS[name])
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rs = np.random.RandomState(seed)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 16)), jnp.int32)
    targets = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 16)), jnp.int32)
    return model, params, (tokens, targets)


def _assert_trees_close(got, want, rtol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        scale = max(float(np.max(np.abs(w))), 1e-30)
        np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol * scale)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_loss_and_grads_match_log_softmax(name):
    model, params, batch = _setup(name)
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    ref, ref_grads = jax.value_and_grad(
        lambda p: _reference_loss(model, p, batch))(params)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    _assert_trees_close(grads, ref_grads, rtol=1e-6)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_loss_with_huge_logits_stays_finite(name):
    """Logits near 1e4: exp overflows f32, so the max shift inside
    logsumexp is what keeps the loss finite."""
    model, params, batch = _setup(name, seed=1)
    peak = float(jnp.max(jnp.abs(model.apply(params, batch[0]))))
    params = dict(params, embed=params["embed"] * (1e4 / peak))
    logits = model.apply(params, batch[0])
    assert float(jnp.max(jnp.abs(logits))) > 1e3
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(logits))))
    loss = float(model.loss(params, batch))
    assert np.isfinite(loss)
    np.testing.assert_allclose(loss, float(_reference_loss(model, params,
                                                            batch)),
                               rtol=1e-6)
    grads = jax.grad(model.loss)(params, batch)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
