"""Scalable bootstrap plane (docs/bootstrap.md): lazy pair
establishment with the LRU-capped broker, leader-relayed rendezvous
over the host topology, and per-host lease aggregation for the elastic
coordinator — the P>=512 bring-up story, exercised here at CI scale
with simulated hosts (TPUCOLL_HOST_ID / set_host_id).

These tests pin the *semantics*: every algorithm
family (and the PR 17 schedule interpreter) runs unchanged over a
broker-dialed mesh, the steady-state broker pair count respects
TPUCOLL_MAX_PAIRS, first-use dial failures surface as typed errors
naming the peer, and a 4x4 simulated grid rebuilds through a SIGKILL
with aggregated leases on."""

import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import numpy as np
import pytest

import gloo_tpu
from gloo_tpu import schedule

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Process-global env drives the boot plane (read once per
# connect_full_mesh), so lazy-mode spawns serialize behind this lock.
_ENV_MU = threading.Lock()


def _spawn_lazy(size, rph, fn, cap=None, timeout=90.0,
                context_timeout=30.0, extra_env=None):
    """Threaded lazy-mode grid: rank r presents host lazyhost<r//rph>,
    connects with TPUCOLL_BOOT_MODE=lazy (plus TPUCOLL_MAX_PAIRS=cap
    when given), runs fn(ctx, rank), restores the environment."""
    store = gloo_tpu.HashStore()
    results = [None] * size
    errors = []
    lock = threading.Lock()

    def worker(rank):
        ctx = None
        try:
            ctx = gloo_tpu.Context(rank, size, timeout=context_timeout)
            ctx.set_host_id(f"lazyhost{rank // rph}")
            ctx.connect_full_mesh(store, gloo_tpu.Device())
            results[rank] = fn(ctx, rank)
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            with lock:
                errors.append((rank, exc))
        finally:
            if ctx is not None:
                try:
                    ctx.close()
                except Exception:
                    pass

    env = {"TPUCOLL_BOOT_MODE": "lazy"}
    if cap is not None:
        env["TPUCOLL_MAX_PAIRS"] = str(cap)
    if extra_env:
        env.update(extra_env)
    with _ENV_MU:
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            threads = [threading.Thread(target=worker, args=(r,),
                                        daemon=True)
                       for r in range(size)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout)
                if t.is_alive():
                    raise TimeoutError(f"lazy rank hung past {timeout}s")
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    if errors:
        rank, exc = errors[0]
        raise AssertionError(f"rank {rank} failed: {exc!r}") from exc
    return results


# ---- lazy mesh is transparent to every algorithm family --------------------


def test_lazy_bootstrap_all_families():
    """8 ranks / 2 simulated hosts come up lazy; every collective
    family computes its closed form over broker-dialed pairs, and the
    boot metrics carry the relayed-rendezvous story (lazy flag on,
    store traffic far under the full-mesh O(N^2) exchange)."""
    size, rph = 8, 4

    def fn(ctx, rank):
        topo = ctx.topology()
        assert topo["n_hosts"] == 2, topo
        x = np.full(512, float(rank + 1), dtype=np.float32)
        for algo in ("auto", "ring", "hd", "bcube", "hier"):
            x[:] = float(rank + 1)
            ctx.allreduce(x, algorithm=algo)
            assert x[0] == size * (size + 1) / 2, (algo, x[0])
        g = np.full(32, float(rank), dtype=np.float64)
        out = ctx.allgather(g, tag=1)
        assert [int(out[r][0]) for r in range(size)] == list(range(size))
        b = np.full(64, float(rank == 3), dtype=np.float32)
        ctx.broadcast(b, root=3, tag=2)
        assert b[0] == 1.0, b[0]
        r = np.full(128, 1.0, dtype=np.float32)
        red = ctx.reduce(r, root=5, tag=3)
        if rank == 5:
            assert red[0] == size, red[0]
        else:
            assert red is None
        rs = np.arange(size * 16, dtype=np.float32)
        block = ctx.reduce_scatter_inplace(rs, tag=4)
        assert block[0] == size * (rank * 16), block[0]
        a2a = np.full((size, 4), float(rank), dtype=np.float32)
        a2a_out = ctx.alltoall(a2a, tag=5)
        assert [int(a2a_out[s][0]) for s in range(size)] == \
            list(range(size))
        ctx.barrier(tag=6)
        boot = ctx.metrics()["boot"]
        assert boot["lazy"] is True, boot
        # Relayed rendezvous: per-rank store traffic stays O(1)-ish
        # (publish + topo + leader relay) vs the 2(N-1) gets every rank
        # performs in the seed's full-mesh exchange.
        assert boot["store_ops"] < 2 * size * (size - 1), boot
        assert boot["lazy_dials"] > 0, boot
        return boot["pairs_connected"]

    connected = _spawn_lazy(size, rph, fn)
    # Nobody needed a full mesh to run all of the above.
    assert all(c <= size - 1 for c in connected), connected


def test_lazy_bootstrap_schedule_interpreter():
    """The PR 17 interpreter replays a generated schedule over a lazy
    mesh byte-identically to the native dispatch: broker-dialed pairs
    are indistinguishable from eager ones to the schedule plane."""
    size, rph = 4, 2

    def fn(ctx, rank):
        base = (np.random.RandomState(7 + rank)
                .randint(0, 50, size=1536).astype(np.float32))
        native = base.copy()
        ctx.allreduce(native)
        t = schedule.generate("ring", size, {"depth": 2})
        t = json.loads(json.dumps(t))
        t["elections"] = [{
            "collective": "allreduce", "world_size": size, "dtype": "",
            "bucket": (1536 * 4).bit_length() - 1,
            "schedule": t["schedules"][0]["name"],
        }]
        schedule.install(ctx, t)
        got = base.copy()
        ctx.allreduce(got)
        schedule.clear(ctx)
        assert np.array_equal(native, got)
        return got.tobytes()

    results = _spawn_lazy(size, rph, fn)
    assert len(set(results)) == 1  # consensus across ranks


# ---- LRU broker cap --------------------------------------------------------


def test_lazy_broker_cap_and_lru_eviction():
    """TPUCOLL_MAX_PAIRS=1 under a mixed soak: in-flight pairs may pin
    past the cap, but a dial with the mesh quiesced trims the broker
    back to <= cap — and the evicted-then-redialed peers still compute
    correct results (the LRU churn is invisible to callers)."""
    size, rph, cap = 8, 4, 1

    def fn(ctx, rank):
        eager = ctx.metrics()["boot"]["pairs_connected"]
        for i in range(6):
            a2a = np.full((size, 4), float(rank), dtype=np.float32)
            out = ctx.alltoall(a2a, tag=1)
            assert out[rank][0] == float(rank), out[rank][0]
            y = np.ones(128, dtype=np.float32)
            ctx.allreduce(y)
            assert y[0] == size, y[0]
        ctx.barrier(tag=2)
        # Quiesced single dial: the cap is enforced at dial time.
        z = np.full(8, float(rank), dtype=np.float32)
        ctx.send(z, (rank + 3) % size, slot=9)
        w = np.empty(8, dtype=np.float32)
        ctx.recv(w, (rank - 3) % size, slot=9)
        assert w[0] == float((rank - 3) % size), w[0]
        boot = ctx.metrics()["boot"]
        broker = boot["pairs_connected"] - eager
        assert broker <= cap, (rank, broker, boot)
        return boot["pairs_evicted"]

    evictions = _spawn_lazy(size, rph, fn, cap=cap)
    assert sum(evictions) > 0, evictions


# ---- typed first-use dial failure ------------------------------------------


def test_lazy_first_use_dial_failure_names_peer():
    """A peer that died between rendezvous and first use: the broker's
    on-demand dial fails with a typed IoError naming the peer rank —
    not a hang, not an anonymous socket error."""
    size = 3
    store_dir = tempfile.mkdtemp()
    body = textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        store = gloo_tpu.FileStore({store!r})
        ctx = gloo_tpu.Context(rank, {size}, timeout=8.0)
        ctx.set_host_id("deadhost%d" % rank)  # one rank per host
        # TPUCOLL_BOOT_EAGER=none: nothing is dialed at connect, so the
        # dial below is genuinely first-use.
        ctx.connect_full_mesh(store, gloo_tpu.Device())
        if rank == 2:
            # Vanish before anyone broker-dials us. os._exit skips the
            # orderly goodbye: the listener socket just disappears.
            store.set("rank2_gone", b"1")
            os._exit(0)
        store.get("rank2_gone", timeout=10.0)
        time.sleep(0.3)
        if rank == 0:
            err = None
            try:
                z = np.ones(8, dtype=np.float32)
                ctx.send(z, 2, slot=5)
            except gloo_tpu.IoError as exc:
                err = str(exc)
            assert err is not None, "dial to a dead rank succeeded?"
            assert "rank 2" in err, err
            print("TYPED-ERR-OK")
        ctx.close()
    """).format(repo=_REPO, store=store_dir, size=size)
    env = dict(os.environ, TPUCOLL_BOOT_MODE="lazy",
               TPUCOLL_BOOT_EAGER="none")
    procs = [subprocess.Popen([sys.executable, "-c", body, str(r)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for r in range(size)]
    outs = [p.communicate(timeout=60) for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (r, p.returncode, out)
    assert "TYPED-ERR-OK" in outs[0][0], outs[0]


# ---- native rendezvous choreography ----------------------------------------


def test_relayed_rendezvous_store_op_scaling():
    """tc_boot_rendezvous_bench, 32 thread-ranks over a shared
    FileStore: the full-mesh arm performs exactly its closed-form
    2N + 2N(N-1) store ops; the relayed arm stays an order of magnitude
    under it (O(hosts^2 + N)) while moving the same address bytes."""
    from gloo_tpu import _lib

    n, rph = 32, 8
    ops = {}
    for arm, lazy in (("lazy", 1), ("full", 0)):
        d = tempfile.mkdtemp()
        raw = _lib.copy_out(_lib.lib.tc_boot_rendezvous_bench,
                            d.encode(), n, rph, 8, lazy, 64, 60000)
        ops[arm] = json.loads(raw)
    assert ops["full"]["store_ops"] == 2 * n + 2 * n * (n - 1)
    assert ops["lazy"]["store_ops"] * 10 <= ops["full"]["store_ops"], ops
    assert ops["lazy"]["nranks"] == n


def test_rendezvous_bench_validates_arguments():
    from gloo_tpu import _lib

    d = tempfile.mkdtemp()
    with pytest.raises(gloo_tpu.Error):
        _lib.copy_out(_lib.lib.tc_boot_rendezvous_bench, d.encode(),
                      0, 8, 8, 1, 64, 1000)
    with pytest.raises(gloo_tpu.Error):
        _lib.copy_out(_lib.lib.tc_boot_rendezvous_bench, d.encode(),
                      8, 8, 8, 1, 1 << 21, 1000)


# ---- boot env validation ---------------------------------------------------


def test_boot_env_validation():
    """Malformed boot knobs fail loudly at connect time (strict env
    parsing, common/env.h discipline) — never a silent fallback."""
    cases = [{"TPUCOLL_BOOT_MODE": "eager"},
             {"TPUCOLL_BOOT_MODE": "lazy", "TPUCOLL_BOOT_EAGER": "all"},
             {"TPUCOLL_BOOT_MODE": "lazy", "TPUCOLL_BOOT_SHARDS": "0"},
             {"TPUCOLL_BOOT_MODE": "lazy", "TPUCOLL_MAX_PAIRS": "-2"}]
    for env in cases:
        with _ENV_MU:
            saved = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                ctx = gloo_tpu.Context(0, 1)
                with pytest.raises(gloo_tpu.Error):
                    ctx.connect_full_mesh(gloo_tpu.HashStore(),
                                          gloo_tpu.Device())
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v


def test_lazy_single_rank_world():
    """The degenerate world still bootstraps lazily (leader of its own
    one-host topology, zero pairs)."""

    def fn(ctx, rank):
        x = np.full(16, 3.0, dtype=np.float32)
        ctx.allreduce(x)
        assert x[0] == 3.0
        return ctx.metrics()["boot"]["pairs_connected"]

    assert _spawn_lazy(1, 1, fn) == [0]


# ---- elastic: SIGKILL -> rebuild on a 4x4 grid with aggregated leases ------


def test_elastic_sigkill_4x4_grid_agg_leases():
    """16 workers across 4 simulated hosts, lazy bootstrap AND
    per-host lease aggregation on: SIGKILL one member mid-step; the
    survivors detect via the aggregate scan (O(hosts) per coordinator
    pass), agree the next epoch, and rebuild at size 15 within the
    lease-grace-bounded window. Every worker's final agent status must
    show the aggregation plane actually ran."""
    hosts, rph = 4, 4
    size = hosts * rph
    store_dir = tempfile.mkdtemp()
    body = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu
        from gloo_tpu import elastic

        rank = int(sys.argv[1])
        store = gloo_tpu.FileStore({store!r})

        def step_fn(ectx, step, state):
            flag = np.zeros(1, dtype=np.float32)
            if ectx.rank == 0:
                try:
                    store.get("grid_stop", timeout=0.001)
                    flag[0] = 1.0
                except gloo_tpu.Error:
                    pass
            ectx.allreduce(flag, tag=0)
            if flag[0] > 0:
                raise StopIteration
            n = ectx.size
            x = np.full(1024, float(ectx.rank + 1), dtype=np.float32)
            ectx.allreduce(x, tag=1)
            assert x[0] == n * (n + 1) / 2, (step, x[0], n)
            state["i"] += 1
            return state

        res = elastic.run_elastic(
            step_fn, store=store, device=gloo_tpu.Device(), rank=rank,
            world_size={size}, min_size={min_size},
            host_id="gridhost%d" % (rank // {rph}),
            state={{"i": 0}}, timeout=120.0)
        res.pop("state")
        print("OK", json.dumps(res))
    """).format(repo=_REPO, store=store_dir, size=size, rph=rph,
                min_size=size - 1)
    env = dict(os.environ, TPUCOLL_LEASE_AGG="1",
               TPUCOLL_BOOT_MODE="lazy",
               TPUCOLL_LEASE_MS="200", TPUCOLL_LEASE_GRACE="1200")
    procs = [subprocess.Popen([sys.executable, "-c", body, str(r)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for r in range(size)]
    victim = 5
    try:
        time.sleep(6.0)  # founders up + a few steps
        t_kill = time.monotonic()
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(timeout=30)
        # Bounded recovery: detect (lease grace) + agree + rebuild.
        deadline = time.monotonic() + 30.0
        time.sleep(4.0)
    finally:
        gloo_tpu.FileStore(store_dir).set("grid_stop", b"1")
    summaries = []
    for r, p in enumerate(procs):
        out, err = p.communicate(timeout=90)
        if r == victim:
            assert p.returncode == -signal.SIGKILL
            continue
        assert p.returncode == 0, (r, p.returncode, err[-800:])
        line = [ln for ln in out.splitlines() if ln.startswith("OK ")]
        assert line, (r, out, err[-500:])
        summaries.append(json.loads(line[0][3:]))
    assert len(summaries) == size - 1
    for s in summaries:
        final = s["epochs"][-1]
        assert final["size"] == size - 1, s["epochs"]
        assert final["epoch"] >= 2
        assert s["elastic"]["lease_agg"] is True, s["elastic"]
        assert s["rebuilds"] >= 1
        # The rebuild itself stays in the small-N regime: the grace
        # window owns detection, the rebuild must not add seconds.
        assert min(s["rebuild_ms"]) < 10000, s["rebuild_ms"]
    # At least the four host leaders published aggregates.
    agg_pubs = sum(s["elastic"]["agg_publishes"] for s in summaries)
    assert agg_pubs >= hosts, agg_pubs
    assert time.monotonic() <= deadline
