"""Elastic membership plane (docs/elastic.md): the contract is that the
SYSTEM detects membership changes — no application-level rebuild call
appears anywhere in these worker bodies. Multiprocess over a FileStore
like test_chaos.py (real processes, real sockets, real SIGKILLs), with
fast lease knobs (TPUCOLL_LEASE_MS=200 / TPUCOLL_LEASE_GRACE=1200) so
detection latency is test-sized.

Covered transitions:
- SIGKILL mid-allreduce auto-detected by lease expiry alone (survivors
  resume in a new epoch within the grace window, epoch-tagged flight
  recorder + metrics()["elastic"] assert every transition);
- coordinator death and re-election (next-lowest wid publishes);
- replacement-rank rejoin back to the original world size;
- shrink below min_size fails loudly and typed on every survivor;
- same-seed fault-plane determinism across an epoch transition;
- graceful leave (deleted lease: immediate shrink, no grace wait).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LEASE_ENV = {"TPUCOLL_LEASE_MS": "200", "TPUCOLL_LEASE_GRACE": "1200"}


def _spawn(body, rank, size, store, extra_env=None):
    env = dict(os.environ, **_LEASE_ENV)
    env.pop("TPUCOLL_FAULT_FILE", None)
    if extra_env:
        env.update(extra_env)
    prog = textwrap.dedent("""
        import json, os, signal, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu
        from gloo_tpu import elastic, fault

        rank = {rank}; size = {size}
        store = gloo_tpu.FileStore({store!r})
        device = gloo_tpu.Device()
    """).format(repo=_REPO, rank=rank, size=size, store=store) + \
        textwrap.dedent(body)
    return subprocess.Popen([sys.executable, "-c", prog],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)


def _summary(out):
    line = [ln for ln in out[0].splitlines() if ln.startswith("OK ")]
    assert line, out
    return json.loads(line[0][3:])


# A verified elastic workload: every step allreduces a consensus stop
# flag (so ranks end at the same step even across membership changes),
# then a payload allreduce checked against the CURRENT size. `victim`
# SIGKILLs itself mid-run; survivors recover with no manual rebuild.
_STEP_BODY = """
victim = {victim}
target_steps = {target_steps}
stop_at_size = {stop_at_size}

def step_fn(ectx, step, state):
    if rank == victim and step == 3:
        os.kill(os.getpid(), signal.SIGKILL)
    flag = np.zeros(1, dtype=np.float32)
    if ectx.rank == 0 and state["done"] >= target_steps and \\
            ectx.size == stop_at_size:
        flag[0] = 1.0
    ectx.allreduce(flag, tag=0)
    if flag[0] > 0:
        raise StopIteration
    x = np.full(1 << 14, float(ectx.rank + 1), dtype=np.float32)
    ectx.allreduce(x, tag=1)
    n = ectx.size
    assert x[0] == n * (n + 1) / 2, (step, x[0], n)
    state["done"] += 1
    return state

t0 = time.time()
res = elastic.run_elastic(step_fn, store=store, device=device,
                          rank=rank, world_size=size, min_size={min_size},
                          join={join}, state={{"done": 0}}, timeout=90.0)
res["wall_s"] = round(time.time() - t0, 2)
res.pop("state")
print("OK", json.dumps(res))
"""


def test_sigkill_mid_allreduce_auto_recovery():
    """Acceptance core: SIGKILL of one rank mid-collective is detected
    by lease expiry ALONE — survivors resume collectives in a new epoch
    within the grace window, with metrics()["elastic"] counters and
    epoch-tagged contexts asserting the transition, and no manual
    rebuild call anywhere in the worker body."""
    store = tempfile.mkdtemp()
    body = _STEP_BODY.format(victim=2, target_steps=6, stop_at_size=2,
                             min_size=2, join=False)
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        res = _summary(outs[r])
        # One lease-expiry transition: epoch 1 (size 3) -> epoch 2
        # (size 2), epoch-tagged group namespaces on both sides.
        assert res["rebuilds"] == 1, res
        assert [(e["epoch"], e["size"], e["group"]) for e in
                res["epochs"]] == [(1, 3, "e1"), (2, 2, "e2")], res
        st = res["elastic"]
        assert st["epoch"] == 2 and st["size"] == 2, st
        assert st["members"] == [0, 1], st
        assert st["leases_renewed"] >= 2, st
        assert st["rebuilds"] == 2, st  # founding bind + the recovery
        # Detection + rebuild bounded by the grace window: the whole
        # run — including ~6 pre/post steps — stays far under the
        # watchdog-free hang the old world would have suffered.
        assert res["rebuild_ms"][0] < 6 * 1200, res
        assert res["wall_s"] < 60, res
    # Exactly one bump, published by the surviving coordinator (wid 0).
    assert _summary(outs[0])["elastic"]["bumps_published"] == 1
    assert _summary(outs[1])["elastic"]["bumps_published"] == 0


def test_epoch_tagged_flightrec_dumps():
    """Every epoch's context carries its epoch as the flight-recorder
    group tag: explicit dumps from both sides of a transition are
    partitionable by epoch before any cross-rank comparison (the
    merge_by_tag contract, docs/flightrec.md)."""
    store = tempfile.mkdtemp()
    dumps = tempfile.mkdtemp()
    body = """
dumps = {dumps!r}

def step_fn(ectx, step, state):
    if rank == 2 and step == 2:
        os.kill(os.getpid(), signal.SIGKILL)
    x = np.full(1024, 1.0, dtype=np.float32)
    ectx.allreduce(x, tag=1)
    assert x[0] == float(ectx.size), x[0]
    ectx.flightrec_dump(os.path.join(
        dumps, "flightrec-rank%d-%s.json" % (rank, ectx.group_tag())))
    if ectx.size == 2 and state["post"] >= 2:
        raise StopIteration
    if ectx.size == 2:
        state["post"] += 1
    return state

res = elastic.run_elastic(step_fn, store=store, device=device,
                          rank=rank, world_size=size, min_size=2,
                          state={{"post": 0}}, timeout=90.0)
print("OK", json.dumps({{"epochs": [e["group"] for e in res["epochs"]]}}))
""".format(dumps=dumps)
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        assert _summary(outs[r])["epochs"] == ["e1", "e2"], outs[r]
    # Both epochs dumped, and every dump document is stamped with its
    # epoch's group tag (the filename-safe and in-document forms).
    for r in (0, 1):
        for epoch in ("e1", "e2"):
            path = os.path.join(dumps, f"flightrec-rank{r}-{epoch}.json")
            assert os.path.exists(path), sorted(os.listdir(dumps))
            with open(path) as f:
                doc = json.load(f)
            assert doc["group"] == epoch, (path, doc.get("group"))
            assert doc["events"], path


def test_coordinator_death_reelection():
    """SIGKILL the coordinator (wid 0): the next-lowest live wid takes
    over, publishes the shrink epoch, and reports coordinator=True —
    the lowest-live-rank re-election the protocol promises."""
    store = tempfile.mkdtemp()
    body = _STEP_BODY.format(victim=0, target_steps=6, stop_at_size=2,
                             min_size=2, join=False)
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    assert procs[0].returncode == -signal.SIGKILL
    for r in (1, 2):
        assert procs[r].returncode == 0, (r, outs[r])
    st1 = _summary(outs[1])["elastic"]
    st2 = _summary(outs[2])["elastic"]
    assert st1["members"] == [1, 2] and st2["members"] == [1, 2]
    # wid 1 is the re-elected coordinator (new rank 0) and published
    # the bump; wid 2 followed.
    assert st1["coordinator"] is True and st1["rank"] == 0, st1
    assert st2["coordinator"] is False and st2["rank"] == 1, st2
    assert st1["bumps_published"] >= 1 and st2["bumps_published"] == 0


def test_replacement_rank_rejoins_to_full_size():
    """Grow path: after the SIGKILL shrink, a respawned replacement
    (join=True — fresh wid, no rank argument) enqueues on the join
    queue and is admitted at the next epoch boundary back to the
    ORIGINAL world size; all three then run verified collectives."""
    store = tempfile.mkdtemp()
    body = _STEP_BODY.format(victim=2, target_steps=6, stop_at_size=3,
                             min_size=2, join=False)
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    # Wait for the victim to die, then spawn the replacement.
    assert procs[2].wait(timeout=60) == -signal.SIGKILL
    time.sleep(0.5)
    joiner_body = _STEP_BODY.format(victim=-1, target_steps=6,
                                    stop_at_size=3, min_size=2, join=True)
    joiner = _spawn(joiner_body, 9, 3, store)
    outs = [p.communicate(timeout=240) for p in procs[:2]]
    jout = joiner.communicate(timeout=240)
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        res = _summary(outs[r])
        # epoch 1 (3) -> shrink epoch (2) -> join epoch (3 again).
        assert [e["size"] for e in res["epochs"]] == [3, 2, 3], res
        assert res["elastic"]["members"] == [0, 1, 3], res
    assert joiner.returncode == 0, jout
    jres = _summary(jout)
    st = jres["elastic"]
    assert st["wid"] == 3 and st["rank"] == 2 and st["size"] == 3, st
    assert jres["epochs"][0]["size"] == 3, jres


def test_shrink_below_min_size_fails_loudly():
    """With min_size == world_size, losing one rank cannot be recovered
    from: every survivor's run_elastic raises the typed BelowMinSize —
    loudly, not a hang, not a silent small group."""
    store = tempfile.mkdtemp()
    body = """
def step_fn(ectx, step, state):
    if rank == 2 and step == 2:
        os.kill(os.getpid(), signal.SIGKILL)
    x = np.full(1024, 1.0, dtype=np.float32)
    ectx.allreduce(x, tag=1)
    return state

try:
    elastic.run_elastic(step_fn, store=store, device=device, rank=rank,
                        world_size=size, min_size=3, steps=50,
                        timeout=90.0)
    print("UNEXPECTED-SUCCESS"); sys.exit(3)
except elastic.BelowMinSize as e:
    assert "below min_size 3" in str(e), e
    print("OK", json.dumps({"typed": True, "message": str(e)[:120]}))
"""
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        assert _summary(outs[r])["typed"] is True


def test_graceful_leave_is_immediate():
    """ElasticContext.leave() deletes the lease: peers shrink at the
    NEXT monitor poll without waiting out the grace — clean departures
    must be cheaper than crashes."""
    store = tempfile.mkdtemp()
    body = """
def step_fn(ectx, step, state):
    if rank == 2 and step == 3:
        ectx.leave()
    flag = np.zeros(1, dtype=np.float32)
    if ectx.rank == 0 and ectx.size == 2 and state["post"] >= 2:
        flag[0] = 1.0
    ectx.allreduce(flag, tag=0)
    if flag[0] > 0:
        raise StopIteration
    x = np.full(1024, float(ectx.rank + 1), dtype=np.float32)
    ectx.allreduce(x, tag=1)
    n = ectx.size
    assert x[0] == n * (n + 1) / 2, (step, x[0], n)
    if ectx.size == 2:
        state["post"] += 1
    return state

t0 = time.time()
res = elastic.run_elastic(step_fn, store=store, device=device, rank=rank,
                          world_size=size, min_size=2,
                          state={"post": 0}, timeout=90.0)
res["wall_s"] = round(time.time() - t0, 2)
res.pop("state")
print("OK", json.dumps(res))
"""
    procs = [_spawn(body, r, 3, store) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    for r in range(3):
        assert procs[r].returncode == 0, (r, outs[r])
    for r in (0, 1):
        res = _summary(outs[r])
        assert res["elastic"]["members"] == [0, 1], res
        assert res["elastic"]["epoch"] == 2, res
    assert _summary(outs[2])["left"] is True, outs[2]


def test_same_seed_fault_determinism_across_epoch_transition():
    """Same-seed fault-plane determinism ACROSS an epoch transition:
    a probabilistic delay rule fires inside both epochs' fault domains
    (hash of the "e<N>" group tag, >= 1000), and the post-transition
    epoch's per-(rank, domain) firing subsequence is byte-identical
    across two runs. (The failing epoch's own tail is timing-truncated
    — the abort cuts its schedule at a scheduling-dependent point — so
    the deterministic unit is the completed epoch's stream.)"""
    schedule = {"seed": 31, "faults": [
        {"when": {"opcode": "data"},
         "action": "delay", "ms": 1, "prob": 0.4, "seed": 77}]}
    body = """
def step_fn(ectx, step, state):
    if rank == 2 and step == 3:
        ectx.leave()   # deterministic departure point (no mid-op kill)
    flag = np.zeros(1, dtype=np.float32)
    if ectx.rank == 0 and ectx.size == 2 and state["post"] >= 4:
        flag[0] = 1.0
    ectx.allreduce(flag, tag=0)
    if flag[0] > 0:
        raise StopIteration
    x = np.full(4096, float(ectx.rank + 1), dtype=np.float32)
    ectx.allreduce(x, tag=1)
    if ectx.size == 2:
        state["post"] += 1
    return state

res = elastic.run_elastic(step_fn, store=store, device=device, rank=rank,
                          world_size=size, min_size=2,
                          state={"post": 0}, timeout=90.0)
fired = [(e["domain"], e["n"], e["action"], e["peer"], e["nbytes"])
         for e in fault.report(rank=rank)]
fired.sort()
print("OK", json.dumps({
    "fired": fired,
    "e2_domain": res["elastic"]["fault_domain"],
    "epochs": [e["group"] for e in res["epochs"]]}))
"""
    runs = []
    for attempt in range(2):
        store = tempfile.mkdtemp()
        path = os.path.join(store, "schedule.json")
        with open(path, "w") as f:
            json.dump(schedule, f)
        procs = [_spawn(body, r, 3, store,
                        extra_env={"TPUCOLL_FAULT_FILE": path})
                 for r in range(3)]
        outs = [p.communicate(timeout=240) for p in procs]
        for r in range(3):
            assert procs[r].returncode == 0, (r, outs[r])
        runs.append([_summary(outs[r]) for r in range(3)])
    for r in (0, 1):
        assert runs[0][r]["epochs"] == runs[1][r]["epochs"] == ["e1", "e2"]
        e2 = runs[0][r]["e2_domain"]
        assert e2 == runs[1][r]["e2_domain"]
        assert e2 >= 1000, e2  # a group domain, not the root's
        first = [e for e in runs[0][r]["fired"] if e[0] == e2]
        second = [e for e in runs[1][r]["fired"] if e[0] == e2]
        assert first, "no faults fired in the post-transition epoch"
        assert first == second, (r, first, second)


def test_run_elastic_restores_from_checkpointer():
    """run_elastic with a StepCheckpointer: after the shrink, every
    survivor resumes from the newest COMMITTED checkpoint's (step,
    state) — the step counter rewinds to ck_step + 1 and the restored
    accumulator is identical across survivors (the post-failure state
    agreement in-memory retry cannot give, since a failed in-place
    collective leaves buffers undefined)."""
    pytest.importorskip("orbax.checkpoint")
    store = tempfile.mkdtemp()
    ckdir = tempfile.mkdtemp()
    body = """
import jax
jax.config.update("jax_platforms", "cpu")
from gloo_tpu.checkpoint import StepCheckpointer

ckpt = StepCheckpointer({ckdir!r}, keep=3)

def step_fn(ectx, step, state):
    if rank == 2 and step == 4:
        os.kill(os.getpid(), signal.SIGKILL)
    x = np.ones(256, dtype=np.float32)
    ectx.allreduce(x, tag=1)
    state = {{"acc": float(state["acc"]) + float(x[0])}}
    if ectx.rank == 0:
        ckpt.save(step, {{"acc": np.array(state["acc"],
                                          dtype=np.float64)}})
    return state

res = elastic.run_elastic(
    step_fn, store=store, device=device, rank=rank, world_size=size,
    min_size=2, steps=8, state={{"acc": 0.0}},
    checkpointer=ckpt,
    template={{"acc": np.zeros((), dtype=np.float64)}},
    timeout=90.0)
print("OK", json.dumps({{"acc": float(res["state"]["acc"]),
                         "rebuilds": res["rebuilds"],
                         "sizes": [e["size"] for e in res["epochs"]]}}))
""".format(ckdir=ckdir)
    # Each rank imports jax and orbax before its first step; on a loaded
    # host the file's 1.2 s grace could expire rank 2's lease before its
    # planned SIGKILL. A 6 s grace still detects the kill well inside
    # the step timeout.
    env = {"JAX_PLATFORMS": "cpu", "TPUCOLL_LEASE_MS": "500",
           "TPUCOLL_LEASE_GRACE": "6000"}
    procs = [_spawn(body, r, 3, store, extra_env=env) for r in range(3)]
    outs = [p.communicate(timeout=240) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    results = []
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
        results.append(_summary(outs[r]))
    for res in results:
        assert res["rebuilds"] == 1 and res["sizes"] == [3, 2], res
    # Both survivors restored the same committed accumulator and then
    # advanced it identically through the remaining steps.
    assert results[0]["acc"] == results[1]["acc"], results
    assert results[0]["acc"] > 0, results


def test_rebuild_after_failure_reaps_store_keys():
    """Satellite: rebuild_after_failure used to leave every
    rebuild/<gen>/* key in the store forever; on success the new rank 0
    now reaps the mesh bootstrap + roll-call keys — while KEEPING the
    stall/<rank> evidence, which is the post-mortem record
    stall_reports reads after the fact."""
    import gloo_tpu
    from gloo_tpu.resilience import stall_reports

    store_dir = tempfile.mkdtemp()
    body = """
from gloo_tpu.resilience import rebuild_after_failure
x = np.full(1 << 16, float(rank + 1), dtype=np.float32)
ctx = gloo_tpu.Context(rank, size, timeout=10.0)
ctx.connect_full_mesh(store, device)
if rank == 2:
    os.kill(os.getpid(), signal.SIGKILL)
try:
    ctx.allreduce(x, tag=1, timeout=3.0)
    sys.exit(3)
except gloo_tpu.IoError:
    pass
new_ctx, new_rank, new_size = rebuild_after_failure(
    store, gloo_tpu.Device(), old_rank=rank, old_size=size, generation=1,
    settle=3.0, timeout=60.0, failed_context=ctx)
assert new_ctx is not None and new_size == 2
y = np.full(64, 1.0, dtype=np.float32)
new_ctx.allreduce(y, tag=2)
new_ctx.close()
print("OK {}")
"""
    procs = [_spawn(body, r, 3, store_dir) for r in range(3)]
    outs = [p.communicate(timeout=180) for p in procs]
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, (r, outs[r])
    store = gloo_tpu.FileStore(store_dir)
    # The O(n^2) mesh-bootstrap namespace and the roll-call keys are
    # gone; the stall evidence survives and still names the dead rank.
    assert store.list("rebuild/1/mesh") == []
    assert store.list("rebuild/1/alive/") == []
    reports = stall_reports(store, generation=1, old_size=3)
    assert reports, "stall evidence must survive the reap"
    suspects = [rep.get("suspect") for rep in reports.values()]
    assert max(set(suspects), key=suspects.count) == 2, reports


def test_lease_knobs_are_strict():
    """TPUCOLL_LEASE_MS / TPUCOLL_LEASE_GRACE take the strict env
    parsers: malformed values and a grace that cannot span two renewal
    periods fail loudly at agent construction."""
    prog = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {_REPO!r})
        import gloo_tpu
        from gloo_tpu import elastic
        try:
            elastic.ElasticAgent(gloo_tpu.HashStore(), gloo_tpu.Device(),
                                 rank=0, world_size=1)
            print("UNEXPECTED"); sys.exit(3)
        except gloo_tpu.Error as e:
            assert "TPUCOLL_LEASE" in str(e), e
            print("LOUD")
    """)
    for env_extra in ({"TPUCOLL_LEASE_MS": "fast"},
                      {"TPUCOLL_LEASE_MS": "500",
                       "TPUCOLL_LEASE_GRACE": "600"}):
        env = dict(os.environ, **env_extra)
        p = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert p.returncode == 0 and "LOUD" in p.stdout, (
            env_extra, p.stdout, p.stderr)


def test_store_delete_and_list():
    """Satellite: delete(key) + list(prefix) across every store flavor
    (the ops lease reaping and namespace hygiene ride)."""
    import gloo_tpu

    def exercise(store):
        store.set("lease/1", b"a")
        store.set("lease/2", b"b")
        store.set("doc", b"c")
        assert sorted(store.list("lease/")) == ["lease/1", "lease/2"]
        assert sorted(store.list("")) == ["doc", "lease/1", "lease/2"]
        assert store.list("nope/") == []
        assert store.delete("lease/1") is True
        assert store.delete("lease/1") is False
        assert sorted(store.list("lease/")) == ["lease/2"]
        # A counter key (different file layout on FileStore) deletes too.
        store.add("ctr", 5)
        assert store.delete("ctr") is True
        assert store.add("ctr", 1) == 1  # recreated from zero
        # Namespaced view: list is relative to the prefix and delete
        # composes with it.
        p = gloo_tpu.PrefixStore(store, "lease")
        assert sorted(p.list("")) == ["2"]
        assert p.delete("2") is True
        assert store.list("lease/") == []

    exercise(gloo_tpu.HashStore())
    exercise(gloo_tpu.FileStore(tempfile.mkdtemp()))
    server = gloo_tpu.TcpStoreServer("127.0.0.1")
    exercise(gloo_tpu.TcpStore("127.0.0.1", server.port))
