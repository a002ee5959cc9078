"""Smoke the runnable examples: they are the first code a new user
executes, and nothing else in CI runs them. Each runs as the README
documents it, on the virtual CPU mesh, asserting the script's own
success line."""

import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EX = os.path.join(_REPO, "examples")


def _run(name, timeout=420, env_extra=None):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               **(env_extra or {}))
    proc = subprocess.run([sys.executable, os.path.join(_EX, name)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, (name, proc.stdout[-1500:],
                                  proc.stderr[-1500:])
    return proc.stdout


def test_example_fused_tp():
    out = _run("example_fused_tp.py")
    assert "fused tensor-parallel example OK" in out
    assert "auto dispatcher" in out


def test_graft_entry_one_process():
    """`python __graft_entry__.py 4`: the forward, then the dry run over 4
    of the 8 virtual devices, in one process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "__graft_entry__.py"), "4"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "dryrun_multichip(4): OK on cpu" in proc.stdout


def test_example_device_plane():
    out = _run("example_device_plane.py")
    assert "done" in out


def test_example_fsdp_long_context():
    out = _run("example_fsdp_long_context.py")
    assert "fsdp + long-context example OK" in out


def test_example_observability():
    out = _run("example_observability.py", timeout=180)
    assert "observability example OK" in out
    assert "[watchdog] rank0 was blocked" in out
    assert "labeled rank rows" in out


def test_example_chaos():
    out = _run("example_chaos.py", timeout=180)
    assert "chaos example: OK" in out
    assert "fault firing sequence:" in out
    assert '"action": "stall"' in out and '"action": "kill"' in out
    assert "rebuilt OK" in out
    assert "[watchdog] rank0 was blocked" in out
    assert "merged chaos trace" in out


def test_example_flightrec():
    out = _run("example_flightrec.py", timeout=180)
    assert "flightrec example: OK" in out
    assert "reason=stall blamed_peer=1" in out
    assert "desync verdict: collective desync" in out
    assert "merged Perfetto timeline" in out


def test_bench_autotune_smoke(tmp_path):
    """bench.py --autotune smoke cell (tiny sizes, 2 ranks): the sweep
    must elect a table all ranks agree on, persist it, and the tuned
    dispatch must not lose to the better fixed ring/HD arm beyond the
    noise floor (aggregate check — per-cell timings on a shared-core
    host swing widely)."""
    import json
    import math

    table_path = os.path.join(tmp_path, "table.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--autotune",
         "--autotune-quick", "--autotune-out", table_path],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metric"] == "allreduce_autotune_2rank_host"
    assert line["ranks_agree"] is True
    assert line["cells"], "no swept sizes reported"
    # Acceptance: tuned dispatch >= the better fixed arm minus noise, at
    # every swept size in aggregate (geomean absorbs per-cell jitter).
    ratios = [c["tuned_vs_best_fixed"] for c in line["cells"]]
    geomean = math.exp(sum(math.log(r) for r in ratios) / len(ratios))
    assert geomean <= 1.5, (geomean, line["cells"])
    # The emitted table is a valid TPUCOLL_TUNING_FILE payload.
    with open(table_path) as f:
        table = json.load(f)
    assert table["version"] == 1 and table["entries"]


def test_bench_channel_sweep_smoke():
    """bench.py --channel-sweep --quick (2 ranks): every grid point must
    produce a valid JSON measurement line — the data the tuning plane's
    transport hints (tuning.set_transport_hints) are picked from. Values
    are not compared: on a shared-core CI host the multi-channel arm can
    legitimately lose; the sweep's job is producing trustworthy points."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--channel-sweep", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    assert len(lines) >= 2, proc.stdout
    seen = set()
    for line in lines:
        assert line["metric"] == "channel_sweep"
        assert line["ok"] is True, line
        assert line["value"] > 0
        seen.add((line["loops"], line["channels"], line["stripe_bytes"]))
    assert (1, 1, 1 << 20) in seen and (2, 2, 1 << 20) in seen


def test_bench_hier_sweep_smoke():
    """bench.py --hier-sweep --quick (4 ranks, 2 simulated hosts): one
    valid JSON cell comparing flat vs hierarchical allreduce over the
    mixed shm+TCP fabric. The ratio is not asserted — the committed
    HIER_r13.json records the measured grid; the smoke proves the cell
    machinery (topology simulation, consensus check, shm-grouping
    assertion inside the workers) holds together."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--hier-sweep", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    assert len(lines) == 1, proc.stdout
    line = lines[0]
    assert line["metric"] == "hier_sweep" and line["ok"] is True, line
    assert line["hosts"] == 2 and line["ranks_per_host"] == 2
    assert line["flat_gbps"] > 0 and line["hier_gbps"] > 0
    assert line["hier_vs_flat"] > 0


def test_bench_latency_smoke():
    """bench.py --latency --quick (2 ranks, TPUCOLL_SHM=0): one JSON
    line per (op, size, plans on/off) cell plus a summary line. The
    on-arm must prove the zero-registration steady state
    (ubuf_creates_steady_delta == 0); speedups are NOT asserted — a
    shared-core CI host's scheduler noise owns that margin, and the
    committed LAT_r12.json records the measured run."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--latency", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    cells = [l for l in lines if l.get("bench") == "latency"]
    summaries = [l for l in lines if l.get("bench") == "latency_summary"]
    # 4 quick sizes x 2 ops x 2 arms.
    assert len(cells) == 16, proc.stdout
    assert len(summaries) == 1, proc.stdout
    for cell in cells:
        assert cell["p50_us"] > 0 and cell["p99_us"] >= cell["p50_us"]
        if cell["plans"]:
            assert cell["ubuf_creates_steady_delta"] == 0, cell
            assert cell["plan_hits"] > 0, cell
    assert summaries[0]["geomean_p50_speedup_le_64KiB"] is not None


def test_bench_elastic_soak_smoke():
    """bench.py --elastic-soak --quick (3 workers, 1 SIGKILL + 1
    rejoin): the soak must come back at FULL size with every mixed-
    workload step verified, epochs covering the shrink + grow
    transitions, and rebuild-latency percentiles measured — the
    committed ELASTIC_r14.json records the longer run. Latency values
    are not ranked (shared-core CI host); ok=True already asserts the
    end-to-end recovery contract inside the driver."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--elastic-soak", "20", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    assert len(lines) == 1, proc.stdout
    line = lines[0]
    assert line["metric"] == "elastic_soak_3rank_host"
    assert line["ok"] is True, line
    assert line["kills"] == 1 and line["rejoins"] == 1
    # One kill forces at least shrink + grow past the founding epoch.
    assert line["value"] >= 3, line
    assert line["steps"] > 0
    assert line["rebuild_ms_p50"] > 0
    assert line["rebuild_ms_p99"] >= line["rebuild_ms_p50"]


def test_bench_profile_smoke():
    """bench.py --profile --quick (2 ranks): one per-phase breakdown
    JSON line per (size x algorithm) cell plus the profiler overhead
    A/B line (docs/profiling.md). Each cell must profile its timed ops
    and the breakdown must carry canonical phase names."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--profile", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    cells = [l for l in lines if l["metric"] == "profile_phases"]
    abs_ = [l for l in lines if l["metric"] == "profile_overhead_ab"]
    assert len(cells) == 3 and len(abs_) == 1, proc.stdout
    phase_names = {"pack", "post", "wire_wait", "reduce", "unpack",
                   "intra", "inter", "fanout"}
    for cell in cells:
        assert cell["ok"] is True, cell
        assert cell["profiled_ops"] > 0, cell
        assert cell["mean_phase_us"], cell
        assert set(cell["mean_phase_us"]) <= phase_names, cell
        assert "wire_wait" in cell["mean_phase_us"], cell
    ab = abs_[0]
    assert ab["ok"] is True, ab
    assert ab["p50_us_profile_on"] > 0 and ab["p50_us_profile_off"] > 0


def test_bench_wire_sweep_smoke():
    """bench.py --wire-sweep --quick (2 ranks): the four sections the
    committed WIRE_r20.json is built from — the wire grid (one line per
    codec arm, the crossover data auto_lossy_wire is elected from), the
    pipelined-vs-serial interleaved A/B, the codec-thread scaling curve,
    and the phase-attribution A/B with its pack+unpack cut line. Values
    are not ranked: on a shared-core CI host the codec arms' CPU cost
    can legitimately beat their wire savings; each run self-verifies
    its reduced values before timing."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--wire-sweep", "--quick"],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    by_metric = {}
    for line in lines:
        assert line["ok"] is True, line
        by_metric.setdefault(line["metric"], []).append(line)
    grid = by_metric.pop("wire_sweep")
    assert {c["algorithm"] for c in grid} == {
        "ring", "ring_bf16_wire", "ring_q8_wire", "ring_q4_wire"}
    assert all(c["value"] > 0 for c in grid)
    ab = by_metric.pop("wire_pipeline_ab")
    assert {(c["algorithm"], c["arm"]) for c in ab} == {
        (a, arm) for a in ("ring_q8_wire", "ring_q4_wire")
        for arm in ("serial", "pipelined")}
    threads = by_metric.pop("wire_codec_threads")
    assert sorted(c["codec_threads"] for c in threads) == [1, 2, 4]
    phases = by_metric.pop("wire_phase_ab")
    assert {c["arm"] for c in phases} == {"serial", "pipelined"}
    assert all(c["mean_phase_us"] for c in phases)
    (cut,) = by_metric.pop("wire_phase_cut")
    assert cut["pack_unpack_us"]["serial"] > 0
    assert cut["pack_unpack_us"]["pipelined"] > 0
    assert not by_metric, by_metric


def test_bench_bootstrap_sweep_smoke():
    """bench.py --bootstrap-sweep --quick: the choreography cells run
    both rendezvous arms at N in {8, 32}, the real 8-rank lazy vs full
    bring-up verifies its collectives and holds the broker cap under a
    mixed soak, and the aggregated-lease elastic probe rebuilds — the
    committed BOOT_r18.json records the full N<=512 curves (where the
    lazy arm's win is ranked; quick Ns sit below the crossover, so
    wall ratios are not asserted here)."""
    import json
    import tempfile

    out = os.path.join(tempfile.mkdtemp(), "boot_sweep.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--bootstrap-sweep", "--quick", "--bootstrap-out", out],
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    with open(out) as f:
        doc = json.load(f)
    assert doc["metric"] == "bootstrap_scale_sweep"
    assert doc["ok"] is True, doc
    assert [c["nranks"] for c in doc["choreography"]] == [8, 32]
    for cell in doc["choreography"]:
        # The relayed protocol's structural win holds at any N.
        assert cell["ops_ratio"] > 1.0, cell
    e2e = doc["e2e_8rank"]
    assert e2e["ok"] is True, e2e
    assert max(e2e["soak"]["broker_pairs_end"]) <= e2e["cap"]
    assert e2e["soak"]["evictions"] > 0
    assert doc["elastic_rebuild"]["ok"] is True, doc["elastic_rebuild"]
