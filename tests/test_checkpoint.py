"""Checkpoint/resume: durable step store + elastic-training integration.

Beyond-reference coverage (the reference has no checkpoint story): state
survives process death, restores onto DIFFERENT mesh shardings, and
composes with resilience.rebuild_after_failure so a shrunken group
resumes from the last committed step instead of from scratch.
"""

import os
import signal
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("orbax.checkpoint")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from gloo_tpu.checkpoint import StepCheckpointer  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_roundtrip_and_gc(tmp_path):
    ckpt = StepCheckpointer(str(tmp_path), keep=2)
    assert ckpt.load_latest() == (None, None)
    for step in (1, 5, 9):
        ckpt.save(step, {"w": jnp.arange(8.0) * step,
                         "step": np.int64(step)})
    assert ckpt.steps() == [5, 9]  # keep=2 garbage-collected step 1
    step, state = ckpt.load_latest()
    assert step == 9
    np.testing.assert_array_equal(state["w"], np.arange(8.0) * 9)
    assert int(state["step"]) == 9


def test_restore_onto_different_sharding(tmp_path):
    """The post-failure story: state saved on an 8-way mesh restores onto
    a 4-way mesh via the template's shardings."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh8 = Mesh(np.asarray(devs[:8], dtype=object), ("x",))
    mesh4 = Mesh(np.asarray(devs[:4], dtype=object), ("x",))
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh8, P("x")))

    ckpt = StepCheckpointer(str(tmp_path))
    ckpt.save(3, {"x": x})

    template = {"x": jax.ShapeDtypeStruct(
        (8, 8), jnp.float32, sharding=NamedSharding(mesh4, P("x")))}
    step, state = ckpt.load_latest(template)
    assert step == 3
    assert state["x"].sharding.mesh.shape["x"] == 4
    np.testing.assert_array_equal(np.asarray(state["x"]),
                                  np.arange(64.0).reshape(8, 8))


def test_elastic_resume_from_checkpoint():
    """SIGKILL a rank mid-training; survivors rebuild the group AND
    resume from the last committed checkpoint — the step counter and the
    weights both come back, and training keeps converging."""
    store = tempfile.mkdtemp()
    ckdir = tempfile.mkdtemp()

    body = """
import os, signal, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import gloo_tpu
from gloo_tpu.checkpoint import StepCheckpointer
from gloo_tpu.resilience import rebuild_after_failure

rank, size = {rank}, 3
store = gloo_tpu.FileStore({store!r})
ctx = gloo_tpu.Context(rank, size, timeout=10.0)
ctx.connect_full_mesh(store, gloo_tpu.Device())
ckpt = StepCheckpointer({ckdir!r}, keep=2)

rng = np.random.RandomState(0)
X = rng.randn(240, 6).astype(np.float32)
y = X @ np.arange(6, dtype=np.float32)
w = np.zeros(6, dtype=np.float32)
step = 0
gen = 1

while step < 80:
    lo = rank * (240 // size); hi = lo + 240 // size
    err = X[lo:hi] @ w - y[lo:hi]
    grad = 2.0 * X[lo:hi].T @ err / len(err)
    if rank == 2 and step == 20:
        os.kill(os.getpid(), signal.SIGKILL)
    try:
        # Timeout sized above rank 0's worst-case synchronous orbax
        # save (its peers sit in this allreduce while it commits).
        ctx.allreduce(grad, timeout=8.0)
    except gloo_tpu.IoError:
        # settle must exceed the op timeout above: the slowest survivor
        # only detects the death when ITS allreduce times out, and the
        # membership roll call has to wait for it (resilience.py
        # docstring invariant).
        ctx, rank, size = rebuild_after_failure(
            store, gloo_tpu.Device(), old_rank=rank, old_size=size,
            generation=gen, settle=10.0, timeout=60.0)
        assert ctx is not None
        gen += 1
        # Elastic resume: everyone reloads the last committed state so
        # the shrunken group restarts from a CONSISTENT (step, w), not
        # from whatever divergent point each survivor reached.
        got_step, state = ckpt.load_latest()
        assert got_step is not None, "no checkpoint to resume from"
        step = int(state["step"])
        w = np.asarray(state["w"])
        continue
    w -= 0.02 * grad / size
    step += 1
    if rank == 0 and step % 10 == 0:
        ckpt.save(step, {{"w": w, "step": np.int64(step)}})

final_loss = float(np.mean((X @ w - y) ** 2))
assert final_loss < 1.0, final_loss
print(f"RESUMED final={{final_loss:.4f}}")
"""

    # Host-plane workers: orbax imports jax, and a worker that needs no
    # accelerator runs on the CPU, as any host-side trainer process would.
    def worker(rank):
        prog = textwrap.dedent(body).format(repo=_REPO, rank=rank,
                                            store=store, ckdir=ckdir)
        return subprocess.Popen([sys.executable, "-c", prog],
                                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)

    procs = [worker(r) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    codes = [p.returncode for p in procs]
    assert codes[2] == -signal.SIGKILL
    for r in (0, 1):
        assert codes[r] == 0, (codes, outs[r])
        assert "RESUMED" in outs[r][0], outs[r]
