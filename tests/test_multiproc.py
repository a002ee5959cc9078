"""Mode-2 tests: real child processes rendezvousing over a FileStore, with
real failure injection (reference analog: gloo/test/multiproc_test.h:29-133
and transport_test.cc IoErrors/IoTimeouts — kill a rank, assert peers fail
fast with an IoError instead of hanging)."""

import os
import signal
import subprocess
import sys
import tempfile
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_worker(body: str, rank: int, size: int, store: str):
    """Launch a child process running `body` with ctx/rank/size bound."""
    prog = textwrap.dedent("""
        import os, signal, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = {rank}; size = {size}
        store = gloo_tpu.FileStore({store!r})
        ctx = gloo_tpu.Context(rank, size, timeout=10.0)
        ctx.connect_full_mesh(store, gloo_tpu.Device())
    """).format(repo=_REPO, rank=rank, size=size, store=store) + \
        textwrap.dedent(body)
    return subprocess.Popen([sys.executable, "-c", prog],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


KILL_BODY = """
if rank == 1:
    os.kill(os.getpid(), signal.SIGKILL)
x = np.ones(1 << 20, dtype=np.float32)
t0 = time.monotonic()
try:
    ctx.allreduce(x)
    print("UNEXPECTED-SUCCESS")
    sys.exit(3)
except gloo_tpu.IoError:
    elapsed = time.monotonic() - t0
    print(f"IOERROR {elapsed:.3f}")
    sys.exit(10)
"""


def test_peer_killed_mid_collective():
    """SIGKILL one rank; survivors must exit with IoError well inside the
    context timeout (fast failure detection, not timeout expiry)."""
    store = tempfile.mkdtemp()
    procs = [_spawn_worker(KILL_BODY, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=60) for p in procs]
    codes = [p.returncode for p in procs]
    assert codes[1] == -signal.SIGKILL
    for r in (0, 2):
        assert codes[r] == 10, (r, codes[r], outs[r])
        line = [l for l in outs[r][0].splitlines() if l.startswith("IOERROR")]
        assert line, outs[r]
        elapsed = float(line[0].split()[1])
        assert elapsed < 5.0, f"rank {r} took {elapsed}s to detect failure"


TIMEOUT_BODY = """
if rank == 1:
    time.sleep(6)     # miss the collective entirely, then exit cleanly
    sys.exit(0)
x = np.ones(4, dtype=np.float32)
t0 = time.monotonic()
try:
    ctx.allreduce(x, timeout=2.0)
    print("UNEXPECTED-SUCCESS"); sys.exit(3)
except gloo_tpu.TimeoutError:
    print(f"TIMEOUT {time.monotonic()-t0:.3f}"); sys.exit(11)
except gloo_tpu.IoError:
    print(f"IOERROR {time.monotonic()-t0:.3f}"); sys.exit(12)
"""


def test_slow_peer_hits_op_timeout():
    """A peer that never enters the collective must trip the per-op timeout
    (reference analog: allreduce_test.cc timeout tests)."""
    store = tempfile.mkdtemp()
    procs = [_spawn_worker(TIMEOUT_BODY, r, 2, store) for r in range(2)]
    outs = [p.communicate(timeout=60) for p in procs]
    assert procs[1].returncode == 0, outs[1]
    assert procs[0].returncode == 11, outs[0]
    line = outs[0][0].splitlines()[0]
    elapsed = float(line.split()[1])
    assert 1.5 < elapsed < 4.0, f"timeout fired at {elapsed}s, wanted ~2s"


CLEAN_EXIT_BODY = """
x = np.full(1000, float(rank + 1), dtype=np.float32)
ctx.allreduce(x)
expected = size * (size + 1) / 2
assert x[0] == expected, x[0]
ctx.close()
print("OK")
"""


def test_multiproc_clean_run():
    store = tempfile.mkdtemp()
    procs = [_spawn_worker(CLEAN_EXIT_BODY, r, 4, store) for r in range(4)]
    outs = [p.communicate(timeout=60) for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "OK" in out[0]


def test_peer_killed_during_bootstrap():
    """Death before rendezvous (rank 1 never starts): survivors must fail
    connect_full_mesh with a timeout."""
    store = tempfile.mkdtemp()
    prog = textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, {repo!r})
        import gloo_tpu
        store = gloo_tpu.FileStore({store!r})
        ctx = gloo_tpu.Context(0, 2, timeout=2.0)
        try:
            ctx.connect_full_mesh(store, gloo_tpu.Device())
            print("UNEXPECTED-CONNECT"); sys.exit(3)
        except gloo_tpu.TimeoutError:
            print("BOOTSTRAP-TIMEOUT"); sys.exit(10)
    """).format(repo=_REPO, store=store)
    p = subprocess.Popen([sys.executable, "-c", prog], stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    out, err = p.communicate(timeout=60)
    assert p.returncode == 10, (out, err)


RECOVERY_BODY = """
from gloo_tpu.resilience import rebuild_after_failure
if rank == 2:
    os.kill(os.getpid(), signal.SIGKILL)
x = np.full(1 << 18, float(rank + 1), dtype=np.float32)
try:
    ctx.allreduce(x, timeout=2.0)
    print("UNEXPECTED-SUCCESS"); sys.exit(3)
except gloo_tpu.IoError:
    pass
# Survivors regroup into a fresh, smaller world and keep training. The
# settle window must cover detection skew (bounded by the 2s op timeout).
new_ctx, new_rank, new_size = rebuild_after_failure(
    store, gloo_tpu.Device(), old_rank=rank, old_size=size, generation=1,
    settle=3.0, timeout=30.0)
assert new_ctx is not None, "rebuild failed"
assert new_size == 2, new_size
y = np.full(100, float(new_rank + 1), dtype=np.float32)
new_ctx.allreduce(y)
assert y[0] == 3.0, y[0]
new_ctx.close()
print(f"RECOVERED {rank}->{new_rank}/{new_size}")
sys.exit(0)
"""


def test_survivors_rebuild_after_rank_death():
    """The documented recovery contract as working code: a SIGKILL'd rank
    poisons the group; survivors re-rendezvous into a smaller world over
    the same store and run collectives again."""
    store = tempfile.mkdtemp()
    procs = [_spawn_worker(RECOVERY_BODY, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=90) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        assert "RECOVERED" in outs[r][0], outs[r]


TRAINING_RECOVERY_BODY = """
from gloo_tpu.resilience import rebuild_after_failure

rng = np.random.RandomState(0)
X = rng.randn(256, 8).astype(np.float32)
true_w = np.arange(8, dtype=np.float32)
y = X @ true_w
w = np.zeros(8, dtype=np.float32)
gen = 1

def loss_and_grad(w, lo, hi):
    xb, yb = X[lo:hi], y[lo:hi]
    err = xb @ w - yb
    return float(np.mean(err ** 2)), 2.0 * xb.T @ err / len(yb)

loss_at_failure = None
for step in range(120):
    lo = rank * (256 // size)
    hi = lo + 256 // size
    loss, grad = loss_and_grad(w, lo, hi)
    if rank == 2 and step == 5:
        os.kill(os.getpid(), signal.SIGKILL)
    try:
        ctx.allreduce(grad, timeout=2.0)
    except gloo_tpu.IoError:
        loss_at_failure = loss
        ctx, rank, size = rebuild_after_failure(
            store, gloo_tpu.Device(), old_rank=rank, old_size=size,
            generation=gen, settle=3.0, timeout=30.0)
        assert ctx is not None, "rebuild returned no context"
        gen += 1
        # Post-rebuild correctness at the new size: allreduce of rank+1
        # must equal the closed form over the new group.
        probe = np.full(100, float(rank + 1), dtype=np.float32)
        ctx.allreduce(probe)
        expected = size * (size + 1) / 2.0
        assert abs(probe[0] - expected) < 1e-6, (probe[0], expected)
        continue  # redo the step in the new world
    w -= 0.01 * grad / size

final_loss, _ = loss_and_grad(w, 0, 256)
assert loss_at_failure is not None, "this rank never saw the failure"
assert final_loss < loss_at_failure / 10, (final_loss, loss_at_failure)
print(f"RECOVERED final={final_loss:.6f} at_failure={loss_at_failure:.6f}")
sys.exit(0)
"""


def test_recovery_after_sigkill():
    """SIGKILL a rank mid-allreduce; the
    survivors rebuild through gloo_tpu.resilience, post-rebuild
    collectives produce correct values at the new size, and training
    keeps converging (final loss well below the loss at failure)."""
    store = tempfile.mkdtemp()
    procs = [_spawn_worker(TRAINING_RECOVERY_BODY, r, 3, store)
             for r in range(3)]
    outs = [p.communicate(timeout=120) for p in procs]
    codes = [p.returncode for p in procs]
    assert codes[2] == -signal.SIGKILL
    for r in (0, 1):
        assert codes[r] == 0, (codes, outs[r])
        assert "RECOVERED" in outs[r][0], outs[r]
