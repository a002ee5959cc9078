"""chip_smoke.py at tiny sizes on the virtual CPU mesh, and its refusal to
run anywhere but on a TPU. The real sizes run only on the chip; their
compile for a described v5e is in test_tpu_compile.py."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq_len=64)


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_fails_without_a_tpu(args):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("ranks", [1, 4])
def test_collectives(ranks):
    chip_smoke.phase_collectives(jax.devices()[:ranks], rows=64, cols=128)


def test_host_hop():
    chip_smoke.phase_host_hop(jax.devices()[0], nbytes=1 << 16)


def test_train_steps():
    out = chip_smoke.phase_train(jax.devices()[:1], model_kw=TINY, batch=2,
                                 steps=3)
    assert out["losses"][-1] < out["losses"][0]
    assert out["first_loss_rel_diff"] < 1e-3
    # The interpreter lowers no TPU kernel; on the chip main() requires it.
    assert not out["tpu_custom_call"]


def test_rings_against_xla(capsys, monkeypatch):
    # The per-ring watchdog would outlive the test in this process.
    monkeypatch.setattr(chip_smoke, "deadline", lambda seconds: None)
    failed = chip_smoke.phase_rings(jax.devices()[:4],
                                    vmem_bytes=128 * 128 * 4,
                                    hbm_bytes=256 * 128 * 4, cols=128)
    assert failed == []
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert sorted(l["op"] for l in lines if l["ok"]) == sorted(
        c[0] for c in chip_smoke._ring_cases(4, interpret=True))


def test_ddp_against_accumulation():
    chip_smoke.phase_ddp_vs_accum(jax.devices()[:4], model_kw=TINY,
                                  per_chip=2)


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    from gloo_tpu.tpu import enable_compile_cache

    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = os.path.join(_REPO, ".jax_cache")
        assert enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_entries_only_where_env_says(tmp_path):
    """A compile in a fresh process lands in JAX_COMPILATION_CACHE_DIR and
    nowhere in the checkout."""
    repo_cache = os.path.join(_REPO, ".jax_cache")
    before = (sorted(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
              else None)
    prog = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {_REPO!r})
        import jax, jax.numpy as jnp
        from gloo_tpu.tpu import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        f = jax.jit(lambda x: jnp.sin(x) @ x.T)
        f(jnp.ones((64, 64))).block_until_ready()
    """)
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, "-c", prog],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert cache.is_dir() and any(cache.iterdir())
    after = (sorted(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
             else None)
    assert after == before
