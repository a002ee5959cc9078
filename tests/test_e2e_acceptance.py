"""Flagship end-to-end acceptance run: the whole product in ONE job.

8 worker processes, each a simulated host with a 2-device local CPU
mesh (16 devices total), composing every subsystem in sequence:

  1. `gloo_tpu.init_from_env()` bootstrap from torchrun-style env vars
     (rank 0 serves the TcpStore; everyone full-meshes through it);
  2. hierarchical DDP training (`make_hierarchical_ddp`): gradients
     mean over the local device mesh inside the jitted step, then
     across hosts through the C++ transport;
  3. rank 7 SIGKILLs itself mid-training;
  4. survivors hit IoError, re-rendezvous with
     `gloo_tpu.resilience.rebuild_after_failure` through the SAME
     TcpStore, and come back as a contiguous 7-host group;
  5. `gloo_tpu.checkpoint.StepCheckpointer.load_latest` restores the
     last committed step and training resumes to completion in the
     shrunken world, with end-state parameters asserted identical
     across every surviving rank.

This is the single-run composition of SURVEY.md §7 M2's "ONE model
end-to-end" story — each piece has its own test elsewhere; this proves
they compose. Referenced from README ("The acceptance run").
"""

import os
import signal
import subprocess
import sys
import tempfile
import textwrap

import pytest

pytest.importorskip("jax")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZE = 8
KILL_RANK = 7          # never rank 0: it owns the TcpStore server
KILL_STEP = 6
TOTAL_STEPS = 12
CKPT_EVERY = 2

WORKER = textwrap.dedent("""
    import os, signal, sys, time
    sys.path.insert(0, {repo!r})

    import numpy as np
    import jax, jax.numpy as jnp, optax
    # The hierarchical layer must actually be hierarchical: without the
    # 2-device local mesh, make_hierarchical_ddp silently degrades to
    # plain value_and_grad and this test stops covering the device-mesh
    # stage it advertises.
    assert jax.local_device_count() == 2, jax.devices()
    import gloo_tpu
    from gloo_tpu.checkpoint import StepCheckpointer
    from gloo_tpu.resilience import rebuild_after_failure
    from gloo_tpu.tpu import HierarchicalGroup, make_hierarchical_ddp

    KILL_RANK, KILL_STEP = {kill_rank}, {kill_step}
    TOTAL_STEPS, CKPT_EVERY = {total_steps}, {ckpt_every}
    ckpt_dir = sys.argv[1]

    # 1. launcher-env bootstrap (torchrun-style vars set by the parent)
    ctx, server = gloo_tpu.init_from_env(timeout=60.0)
    rank, size = ctx.rank, ctx.size
    print(f"rank {{rank}}: bootstrapped {{rank}}/{{size}}", flush=True)

    # tiny least-squares model so loss strictly decreases under SGD
    w_true = np.linspace(-1.0, 1.0, 8).astype(np.float32)
    rng = np.random.RandomState(1234 + rank)

    def make_batch():
        x = rng.randn(4, 8).astype(np.float32)
        y = x @ w_true
        return {{"x": x, "y": y}}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean(jnp.square(pred - batch["y"]))

    params = {{"w": jnp.zeros(8, jnp.float32)}}
    optimizer = optax.sgd(0.1)
    opt_state = optimizer.init(params)

    def make_step(c):
        group = HierarchicalGroup(c)
        return make_hierarchical_ddp(loss_fn, optimizer, group)

    step_fn = make_step(ctx)
    ckpt = StepCheckpointer(ckpt_dir, keep=3)

    step = 0
    rebuilt = False
    first_loss = None
    while step < TOTAL_STEPS:
        if rank == KILL_RANK and step == KILL_STEP:
            os.kill(os.getpid(), signal.SIGKILL)   # 3. hard failure
        try:
            params, opt_state, loss = step_fn(params, opt_state,
                                              make_batch())
        except gloo_tpu.IoError as exc:
            assert not rebuilt, "second failure not part of this script"
            print(f"rank {{rank}}: step {{step}} failed "
                  f"({{str(exc)[:40]}}); rebuilding", flush=True)
            # 4. survivors re-rendezvous through the SAME store
            store = gloo_tpu.TcpStore(
                os.environ["MASTER_ADDR"], int(os.environ["MASTER_PORT"]))
            ctx.close()
            ctx, rank, size = rebuild_after_failure(
                store, gloo_tpu.Device(), old_rank=rank, old_size=size,
                generation=1, settle=3.0, timeout=60.0)
            assert ctx is not None and size == {size} - 1, (rank, size)
            step_fn = make_step(ctx)
            # 5. resume from the last committed checkpoint
            ck_step, state = ckpt.load_latest()
            assert ck_step is not None, "no committed checkpoint found"
            params = {{"w": jnp.asarray(state["w"])}}
            opt_state = optimizer.init(params)
            step = int(state["step"])
            rebuilt = True
            print(f"rank {{rank}}: resumed from step {{ck_step}} "
                  f"(train step {{step}}) in world of {{size}}",
                  flush=True)
            continue
        loss = float(loss)
        if first_loss is None:
            first_loss = loss
        if rank == 0 and step % CKPT_EVERY == 0:
            # force=True: post-resume replay re-saves steps that already
            # have committed directories from before the failure.
            ckpt.save(step, {{"w": np.asarray(params["w"]),
                              "step": step}}, force=True)
        step += 1

    assert rebuilt, "the failure/rebuild path never ran"
    assert loss < first_loss, (first_loss, loss)
    # end-state params bitwise-identical across the surviving world
    final = np.asarray(params["w"], dtype=np.float32)
    gathered = ctx.allgather(final)
    for row in gathered:
        assert np.array_equal(np.asarray(row), final), "params diverged"
    ctx.barrier()
    print(f"rank {{rank}}: DONE loss {{first_loss:.4f}} -> {{loss:.4f}}",
          flush=True)
""").format(repo=_REPO, kill_rank=KILL_RANK, kill_step=KILL_STEP,
            total_steps=TOTAL_STEPS, ckpt_every=CKPT_EVERY, size=SIZE)


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_flagship_acceptance_run():
    ckpt_dir = tempfile.mkdtemp()
    port = _free_port()
    procs = []
    for r in range(SIZE):
        env = dict(os.environ, RANK=str(r), WORLD_SIZE=str(SIZE),
                   MASTER_ADDR="127.0.0.1", MASTER_PORT=str(port),
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER, ckpt_dir], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    try:
        outs = [p.communicate(timeout=900)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    codes = [p.returncode for p in procs]
    assert codes[KILL_RANK] == -signal.SIGKILL, (codes, outs[KILL_RANK])
    for r in range(SIZE):
        if r == KILL_RANK:
            continue
        assert codes[r] == 0, (r, codes, outs[r][-2000:])
        assert "resumed from step" in outs[r], (r, outs[r][-2000:])
        assert "DONE" in outs[r], (r, outs[r][-2000:])


if __name__ == "__main__":
    test_flagship_acceptance_run()
    print("acceptance run OK")
