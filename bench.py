"""Headline benchmark: allreduce algorithm bandwidth, host plane.

Config #1: allreduce, float32, 64 MiB payload, 2 ranks,
host transport on localhost — the reference's own benchmark methodology
(p50 of timed iterations after warmup, verified first iteration). "Host"
because the transport routes bulk payloads over its same-host shm plane
with TCP as the control stream (docs/transport.md) — the same stack a
user gets from Device() with no configuration, measured against the
reference's own localhost TCP number.

vs_baseline compares against pytorch/gloo's `benchmark --transport tcp
allreduce_ring_chunked` at the same config: measured live when the
reference build exists at build-ref/ (run `cmake -S /root/reference -B
build-ref -G Ninja -DBUILD_BENCHMARK=ON -DUSE_REDIS=OFF && cmake --build
build-ref`), otherwise against the value recorded on this host
(RECORDED_REFERENCE_GBPS).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"spread", "runs"} — value is the median of five full measurements taken
after one discarded warm-up run (run-to-run spread was ~9.4% at
median-of-3, BENCH_r05), spread is (max-min)/median of those runs (this
host's remaining noise floor next to the number), runs lists all five.

--channel-sweep measures allreduce algbw across the multi-channel
transport grid (TPUCOLL_LOOP_THREADS x TPUCOLL_CHANNELS x
TPUCOLL_STRIPE_BYTES), one JSON line per point, feeding the tuning
plane's transport hints; add --quick for a small smoke grid.

--wire-sweep measures allreduce algbw across the wire-codec family
(plain ring vs ring_bf16_wire vs ring_q8_wire vs ring_q4_wire) x
payload size under TPUCOLL_SHM=0 (the TCP plane, where wire bytes are
the bottleneck the codecs exist to cut), one JSON line per
(algorithm, size) point — the crossover data the tuner's lossy arms
and future rounds consume. It also runs the pipelined-engine A/B
(serial depth-1 hop vs depth-4 + codec pool, interleaved passes), the
TPUCOLL_CODEC_THREADS width axis, and a profiled 64 MiB phase
breakdown quantifying the op-thread pack+unpack cut; add --quick for
a small smoke grid.
"""

import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

ELEMENTS = 16 * 1024 * 1024  # 64 MiB float32
WARMUP = 3
ITERS = 15
RECORDED_REFERENCE_GBPS = 0.620

# --pin: sched_setaffinity each rank's thread/process (and so, by
# inheritance, its loop and lane threads) to core (rank % cpu_count),
# cutting scheduler-migration noise out of the ~9% headline spread on
# this 2-core host. Recorded in every JSON line it affects.
PIN_RANKS = False


def _maybe_pin(rank):
    if not PIN_RANKS:
        return
    ncpu = os.cpu_count() or 1
    # pid 0 = the calling thread on Linux; threads spawned afterwards
    # (event loops, async lanes) inherit the mask.
    os.sched_setaffinity(0, {rank % ncpu})


def bench_ours(metrics_out=None):
    import numpy as np

    import gloo_tpu

    store = gloo_tpu.HashStore()
    samples = [None, None]

    def worker(rank):
        _maybe_pin(rank)
        device = gloo_tpu.Device()
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(store, device)
        x = np.full(ELEMENTS, float(rank + 1), dtype=np.float32)
        ctx.allreduce(x)
        assert x[0] == 3.0, "allreduce verification failed"
        x[:] = 1.0
        for _ in range(WARMUP):
            ctx.allreduce(x)
        if metrics_out is not None and rank == 0:
            ctx.metrics(drain=True)  # measure the timed loop only
        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            ctx.allreduce(x)
            times.append(time.perf_counter() - t0)
        samples[rank] = times
        if metrics_out is not None and rank == 0:
            metrics_out.append(ctx.metrics())
        ctx.barrier()
        ctx.close()

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    import numpy as np

    p50 = float(np.median(samples[0]))
    p99 = float(np.percentile(samples[0], 99))
    algbw = ELEMENTS * 4 / p50 / 1e9
    print(f"[bench] ours: p50 {p50 * 1e6:.0f}us p99 {p99 * 1e6:.0f}us "
          f"algbw {algbw:.3f} GB/s", file=sys.stderr)
    return algbw


def bench_reference():
    """Run the reference gloo benchmark at the identical config, if built."""
    binary = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "build-ref", "gloo", "benchmark", "benchmark")
    if not os.path.exists(binary):
        return None
    store = tempfile.mkdtemp()
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [binary, "--size", "2", "--rank", str(rank),
             "--shared-path", store, "--transport", "tcp",
             "--elements", str(ELEMENTS), "--iteration-time", "2s",
             "--no-verify", "allreduce_ring_chunked"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for out in outs:
        m = re.search(r"^\s*\d+\s+\d+\s+\d+\s+(\d+)\s+\d+\s+\d+\s+"
                      r"([\d.]+)\s+\d+\s*$", out, re.M)
        if m:
            gbps = float(m.group(2))
            p50_us = int(m.group(1))
            print(f"[bench] reference gloo: p50 {p50_us}us algbw "
                  f"{gbps:.3f} GB/s", file=sys.stderr)
            return gbps
    return None


def bench_autotune(quick=False, out_path=None):
    """--autotune: run the tuner sweep on a 2-rank group, persist the
    elected table, and measure what the table buys: for every swept
    allreduce size, p50 with the tuned table installed vs the default
    (untuned) kAuto thresholds vs each fixed arm (ring, halving-
    doubling). Prints ONE JSON line:

      {"metric": "allreduce_autotune_2rank_host",
       "value": <geomean over sizes of default_us / tuned_us>,
       "unit": "x_speedup_vs_default_auto",
       "ranks_agree": <all ranks installed byte-identical tables>,
       "table": <path the table was saved to>,
       "cells": [{"bytes", "tuned_us", "default_us", "ring_us", "hd_us",
                  "tuned_vs_best_fixed"}, ...]}

    tuned_vs_best_fixed is the acceptance signal: ~<= 1 plus noise means
    the tuned dispatch never loses to the better fixed arm at any swept
    size (the hardcoded threshold CAN lose — that is the point).
    """
    import math

    import numpy as np

    import gloo_tpu
    from gloo_tpu import tuning

    if out_path is None:
        out_path = "/tmp/tuning_table.json"
    # Quick mode (CI smoke): tiny sizes, few iterations.
    min_bytes = 4 << 10
    max_bytes = (64 << 10) if quick else (4 << 20)
    tune_iters, tune_warmup = (3, 1) if quick else (8, 2)
    time_iters = 10 if quick else 30

    store = gloo_tpu.HashStore()
    rank_tables = [None, None]
    cells_out = [None]

    def time_allreduce(ctx, x, iters, **kw):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, **kw)
            times.append(time.perf_counter() - t0)
        return float(np.median(times)) * 1e6

    def worker(rank):
        device = gloo_tpu.Device()
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(store, device)
        table = tuning.tune(ctx, min_bytes=min_bytes, max_bytes=max_bytes,
                            iters=tune_iters, warmup=tune_warmup)
        rank_tables[rank] = json.dumps(table, sort_keys=True)
        if rank == 0:
            tuning.save_table(table, out_path)

        # Measured-vs-default sweep. Both ranks run the identical
        # sequence (install/clear are dispatch-relevant state and must
        # flip at the same sequence points on every rank); rank 0's
        # timings are reported.
        cells = []
        nbytes = min_bytes
        while nbytes <= max_bytes:
            x = np.zeros(nbytes // 4, dtype=np.float32)
            tuned = time_allreduce(ctx, x, time_iters)  # table installed
            ring = time_allreduce(ctx, x, time_iters, algorithm="ring")
            hd = time_allreduce(ctx, x, time_iters,
                                algorithm="halving_doubling")
            tuning.clear_table(ctx)
            default = time_allreduce(ctx, x, time_iters)  # stock kAuto
            tuning.install_table(ctx, table)
            cells.append({
                "bytes": nbytes,
                "tuned_us": round(tuned, 1),
                "default_us": round(default, 1),
                "ring_us": round(ring, 1),
                "hd_us": round(hd, 1),
                "tuned_vs_best_fixed": round(tuned / min(ring, hd), 3),
            })
            nbytes *= 2
        if rank == 0:
            cells_out[0] = cells
        ctx.barrier()
        ctx.close()

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1200)
    assert all(t is not None for t in rank_tables), "a rank failed to tune"
    cells = cells_out[0]
    assert cells, "no measurement cells"
    speedup = math.exp(
        sum(math.log(c["default_us"] / c["tuned_us"]) for c in cells)
        / len(cells))
    for c in cells:
        print(f"[autotune] {c['bytes'] >> 10}KiB tuned {c['tuned_us']:.0f}us"
              f" default {c['default_us']:.0f}us ring {c['ring_us']:.0f}us"
              f" hd {c['hd_us']:.0f}us", file=sys.stderr)
    line = {
        "metric": "allreduce_autotune_2rank_host",
        "value": round(speedup, 3),
        "unit": "x_speedup_vs_default_auto",
        "ranks_agree": rank_tables[0] == rank_tables[1],
        "table": out_path,
        "cells": cells,
    }
    print(json.dumps(line))


def bench_schedule_sweep(quick=False, out_path=None):
    """--schedule-sweep [--quick]: sweep the schedule generator grid
    against the native arms on a 4-rank group (docs/schedules.md).

    For every swept allreduce size: p50 of the native kAuto dispatch
    (schedule plane cleared) and of the fixed native ring and hd arms,
    then each generated candidate schedule installed with a single
    election for exactly that (collective, world, bucket) cell — the
    grid includes the two families the native enum cannot express (the
    chunked-pipelined ring, depth 2/4, and the 2-level hierarchy).
    Elects the fastest candidate wherever it beats the BEST native arm,
    saves the elected table (the TPUCOLL_SCHEDULE_FILE format), and
    prints ONE JSON line:

      {"metric": "allreduce_schedule_sweep_4rank_host",
       "value": <cells where a generated schedule beat best-native>,
       "unit": "cells_won", "ranks_agree": ..., "table": <path>,
       "cells": [{"bytes", "native_auto_us", "native_ring_us",
                  "native_hd_us", "arms": {name: us}, "winner",
                  "winner_vs_best_native"}, ...]}

    SCHED_r17.json in the repo root is a committed full run: the
    acceptance evidence that schedule search finds real wins (a
    pipelined ring or hierarchy cell under 1.0).
    """
    import numpy as np

    import gloo_tpu
    from gloo_tpu import schedule

    if out_path is None:
        out_path = "/tmp/schedule_table.json"
    world = 4
    min_bytes = (16 << 10) if quick else (64 << 10)
    max_bytes = (64 << 10) if quick else (4 << 20)
    iters, warmup = (6, 1) if quick else (20, 3)
    candidates = [("ring", {"depth": 1}), ("ring", {"depth": 2}),
                  ("ring", {"depth": 4}), ("hd", {}), ("bcube", {}),
                  ("hier", {"ranks_per_host": 2})]
    # The generated-only families: the acceptance signal counts wins
    # from shapes the native enum cannot dispatch.
    generated_only = {"ring_p4_k2", "ring_p4_k4", "hier_p4_h2"}

    store = gloo_tpu.HashStore()
    rank_tables = [None] * world
    cells_out = [None]

    def time_allreduce(ctx, x, **kw):
        for _ in range(warmup):
            ctx.allreduce(x, **kw)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, **kw)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2] * 1e6

    def worker(rank):
        _maybe_pin(rank)
        device = gloo_tpu.Device()
        ctx = gloo_tpu.Context(rank, world, timeout=120)
        ctx.connect_full_mesh(store, device)
        named = []
        for family, params in candidates:
            t = schedule.generate(family, world, params)
            named.append((t["schedules"][0]["name"], t))

        # Every rank runs the identical install/clear sequence (the
        # plane is dispatch-relevant state and must flip at the same
        # sequence points everywhere); rank 0's timings are reported.
        cells = []
        nbytes = min_bytes
        while nbytes <= max_bytes:
            x = np.zeros(nbytes // 4, dtype=np.float32)
            schedule.clear(ctx)
            ctx.barrier()
            native_auto = time_allreduce(ctx, x)
            native_ring = time_allreduce(ctx, x, algorithm="ring")
            native_hd = time_allreduce(ctx, x,
                                       algorithm="halving_doubling")
            arms = {}
            for name, table in named:
                one = json.loads(json.dumps(table))
                one["elections"] = [{
                    "collective": "allreduce", "world_size": world,
                    "dtype": "",
                    "bucket": nbytes.bit_length() - 1,
                    "schedule": name,
                }]
                schedule.install(ctx, one)
                ctx.barrier()
                arms[name] = time_allreduce(ctx, x)
            best_native = min(native_auto, native_ring, native_hd)
            winner = min(arms, key=arms.get)
            cells.append({
                "bytes": nbytes,
                "native_auto_us": round(native_auto, 1),
                "native_ring_us": round(native_ring, 1),
                "native_hd_us": round(native_hd, 1),
                "arms": {k: round(v, 1) for k, v in arms.items()},
                "winner": winner,
                "winner_vs_best_native": round(arms[winner] / best_native,
                                               3),
            })
            nbytes *= 2
        schedule.clear(ctx)

        # Rank 0's timings decide (each rank measured its own clock);
        # its elected table is broadcast so every rank reports the same
        # bytes — the same agreement protocol schedule.sweep() uses.
        if rank == 0:
            elected = {"version": 1, "schedules": [], "elections": []}
            used = set()
            for c in cells:
                best_native = min(c["native_auto_us"],
                                  c["native_ring_us"], c["native_hd_us"])
                if c["arms"][c["winner"]] < best_native:
                    used.add(c["winner"])
                    elected["elections"].append({
                        "collective": "allreduce", "world_size": world,
                        "dtype": "",
                        "bucket": c["bytes"].bit_length() - 1,
                        "schedule": c["winner"],
                    })
            for name, table in named:
                if name in used:
                    elected["schedules"].append(
                        json.loads(json.dumps(table))["schedules"][0])
            payload = json.dumps(elected, sort_keys=True).encode()
            cells_out[0] = cells
        else:
            payload = b""
        n = np.array([len(payload)], dtype=np.int64)
        ctx.broadcast(n, root=0)
        buf = np.zeros(int(n[0]), dtype=np.uint8)
        if rank == 0:
            buf[:] = np.frombuffer(payload, dtype=np.uint8)
        ctx.broadcast(buf, root=0)
        rank_tables[rank] = buf.tobytes().decode()
        if rank == 0:
            schedule.verify(rank_tables[rank])
            schedule.save(rank_tables[rank], out_path)
        ctx.barrier()
        ctx.close()

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1800)
    assert all(t is not None for t in rank_tables), "a rank failed"
    cells = cells_out[0]
    assert cells, "no measurement cells"
    for c in cells:
        print(f"[sched] {c['bytes'] >> 10}KiB native "
              f"{c['native_auto_us']:.0f}us winner {c['winner']} "
              f"{c['arms'][c['winner']]:.0f}us "
              f"(x{c['winner_vs_best_native']})", file=sys.stderr)
    generated_wins = sum(
        1 for c in cells
        if c["winner"] in generated_only and c["winner_vs_best_native"] < 1)
    line = {
        "metric": "allreduce_schedule_sweep_4rank_host",
        "value": generated_wins,
        "unit": "cells_won",
        "ranks_agree": len(set(rank_tables)) == 1,
        "table": out_path,
        "cells": cells,
    }
    print(json.dumps(line))


def bench_latency(quick=False):
    """Small-message latency A/B: persistent collective plans on vs off.

    The plan cache's headline is LATENCY, not bandwidth: per-op setup
    (UnboundBuffer create+destroy, scratch acquisition, schedule
    recompute) is a fixed cost that dominates small messages. This sweep
    measures allreduce and reduce_scatter p50/p99 at 64 B..256 KiB under
    TPUCOLL_SHM=0 (pure TCP loopback, the acceptance configuration),
    with the two arms interleaved in time per size (A/B/A/B passes) so
    host drift hits both equally. One JSON line per (op, size, arm).

    Arms differ ONLY by TPUCOLL_PLAN_CACHE at context construction: the
    off-arm context runs the transient path (pre-plan behavior), the
    on-arm replays cached plans. The on-arm line also records the
    steady-state ubuf_creates delta across the timed loop — the
    zero-registration proof.
    """
    import numpy as np

    import gloo_tpu

    os.environ["TPUCOLL_SHM"] = "0"
    sizes = [64, 256, 1024, 4096, 16384, 65536, 262144]
    if quick:
        sizes = [64, 1024, 16384, 65536]
    warmup = 10 if quick else 30
    passes = 2 if quick else 4
    iters = 30 if quick else 100

    store_on = gloo_tpu.HashStore()
    store_off = gloo_tpu.HashStore()
    gate = threading.Barrier(2)
    results = []
    lock = threading.Lock()

    def worker(rank):
        _maybe_pin(rank)
        # Coordinated construction: TPUCOLL_PLAN_CACHE is read at
        # Context creation, and the env is process-global, so both
        # ranks build each arm's context under the same setting.
        gate.wait()
        if rank == 0:
            os.environ["TPUCOLL_PLAN_CACHE"] = "0"
        gate.wait()
        dev = gloo_tpu.Device()
        ctx_off = gloo_tpu.Context(rank, 2, timeout=120)
        ctx_off.connect_full_mesh(store_off, dev)
        gate.wait()
        if rank == 0:
            os.environ["TPUCOLL_PLAN_CACHE"] = "1"
        gate.wait()
        ctx_on = gloo_tpu.Context(rank, 2, timeout=120)
        ctx_on.connect_full_mesh(store_on, dev)

        for nbytes in sizes:
            count = max(1, nbytes // 4)
            for op in ("allreduce", "reduce_scatter"):
                # Stable buffers per (size, arm): the plan cache keys on
                # the pointer, and a training loop's buffers are stable —
                # this measures that steady state.
                # On-arm: the full persistent path — a CollectivePlan
                # handle (one foreign call per step, marshalled once)
                # over the warm native plan. Off-arm: the pre-plan
                # per-call path (classic API, cache disabled).
                x_on = np.full(count, float(rank + 1), dtype=np.float32)
                out_on = np.empty(count // 2, dtype=np.float32)
                if op == "allreduce":
                    plan = ctx_on.allreduce_plan(x_on, tag=7)
                else:
                    # count is a multiple of 2 at every swept size
                    # (>= 16 f32 elements), so the default even split
                    # applies.
                    plan = ctx_on.reduce_scatter_plan(x_on, tag=9,
                                                      output=out_on)
                x_off = np.full(count, float(rank + 1), dtype=np.float32)
                out_off = np.empty(count // 2, dtype=np.float32)
                cells = {"on": [], "off": []}
                ub_delta = {}

                def run_op(ctx, arm, n, record):
                    for _ in range(n):
                        t0 = time.perf_counter()
                        if arm == "on":
                            plan()
                        elif op == "allreduce":
                            ctx.allreduce(x_off, tag=7)
                        else:
                            ctx.reduce_scatter(x_off, tag=9,
                                               output=out_off)
                        if record is not None:
                            record.append(time.perf_counter() - t0)

                # Warm both arms (plan build happens here, outside the
                # timed loops), then interleave A/B passes.
                run_op(ctx_on, "on", warmup, None)
                run_op(ctx_off, "off", warmup, None)
                ub0 = ctx_on.metrics()["ubuf_creates"]
                for _ in range(passes):
                    run_op(ctx_on, "on", iters, cells["on"])
                    run_op(ctx_off, "off", iters, cells["off"])
                ub_delta["on"] = ctx_on.metrics()["ubuf_creates"] - ub0
                if rank == 0:
                    snap = ctx_on.metrics()
                    for arm in ("on", "off"):
                        times = cells[arm]
                        line = {
                            "bench": "latency",
                            "op": op,
                            "bytes": nbytes,
                            "plans": arm == "on",
                            "iters": len(times),
                            "p50_us": round(
                                float(np.median(times)) * 1e6, 2),
                            "p99_us": round(
                                float(np.percentile(times, 99)) * 1e6, 2),
                            "pinned": PIN_RANKS,
                        }
                        if arm == "on":
                            line["ubuf_creates_steady_delta"] = int(
                                ub_delta["on"])
                            line["plan_hits"] = snap["plan_hits"]
                            line["plan_misses"] = snap["plan_misses"]
                        with lock:
                            results.append(line)
        ctx_on.barrier(tag=99)
        ctx_off.barrier(tag=99)
        ctx_on.close()
        ctx_off.close()

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1200)

    for line in results:
        print(json.dumps(line))
    # Summary: geomean p50 speedup (plans on vs off) over the <= 64 KiB
    # cells — the acceptance criterion's number.
    import math
    ratios = []
    by_key = {(l["op"], l["bytes"], l["plans"]): l for l in results}
    for (op_name, nbytes, plans), l in by_key.items():
        if plans or nbytes > 65536:
            continue
        on = by_key.get((op_name, nbytes, True))
        if on and on["p50_us"] > 0:
            ratios.append(l["p50_us"] / on["p50_us"])
    summary = {
        "bench": "latency_summary",
        "cells": len(results),
        "geomean_p50_speedup_le_64KiB": round(
            math.exp(sum(math.log(r) for r in ratios) / len(ratios)), 3)
        if ratios else None,
        "pinned": PIN_RANKS,
    }
    print(json.dumps(summary))
    return results + [summary]


def bench_elastic_soak(seconds, quick=False):
    """--elastic-soak N [--quick]: soak the elastic membership plane
    (docs/elastic.md) for ~N seconds: three workers run a VERIFIED
    mixed workload (allreduce at three sizes + allgather, every result
    checked against its closed form for the CURRENT size) under
    run_elastic while this driver periodically SIGKILLs a live worker
    and respawns a replacement with join=True. No worker ever calls a
    rebuild — every transition is lease-detected, epoch-agreed, and
    auto-recovered. Prints ONE JSON line:

      {"metric": "elastic_soak_3rank_host", "value": <epochs reached>,
       "unit": "epochs", "seconds": N, "kills": k, "rejoins": k,
       "steps": <verified steps across final workers>,
       "rebuild_ms_p50": ..., "rebuild_ms_p99": ...,
       "lease_ms": 200, "lease_grace_ms": 1200, "ok": true}

    rebuild latency = EpochChanged caught -> successor mesh bound, per
    transition per worker (the detect half is bounded separately by the
    lease grace). --quick: one kill/rejoin cycle sized for CI smoke.
    """
    repo = os.path.dirname(os.path.abspath(__file__))
    store_dir = tempfile.mkdtemp()
    world = 3
    env = dict(os.environ, TPUCOLL_LEASE_MS="200",
               TPUCOLL_LEASE_GRACE="1200")

    body = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu
        from gloo_tpu import elastic

        rank = int(sys.argv[1])
        join = sys.argv[2] == "join"
        store = gloo_tpu.FileStore({store!r})
        SIZES = (1 << 12, 1 << 14, 1 << 16)

        def step_fn(ectx, step, state):
            # flag[1] carries this rank's step counter so the size
            # index below comes from the allreduced (group-agreed) sum:
            # a joiner enters with a fresh i=0 while survivors are at
            # i=k, and rank-local SIZES[i % 3] would post mismatched
            # allreduce lengths that wedge the mesh.
            flag = np.zeros(2, dtype=np.float32)
            flag[1] = float(state["i"] % 3)
            if ectx.rank == 0:
                try:
                    store.get("soak_stop", timeout=0.001)
                    flag[0] = 1.0
                except gloo_tpu.Error:
                    pass
            ectx.allreduce(flag, tag=0)
            if flag[0] > 0:
                raise StopIteration
            n = ectx.size
            x = np.full(SIZES[int(flag[1]) % 3], float(ectx.rank + 1),
                        dtype=np.float32)
            ectx.allreduce(x, tag=1)
            assert x[0] == n * (n + 1) / 2, (state["i"], x[0], n)
            g = np.full(256, float(ectx.rank), dtype=np.float32)
            out = ectx.allgather(g, tag=2)
            assert [int(out[r][0]) for r in range(n)] == list(range(n))
            state["i"] += 1
            return state

        res = elastic.run_elastic(
            step_fn, store=store, device=gloo_tpu.Device(), rank=rank,
            world_size={world}, min_size=2, join=join,
            state={{"i": 0}}, timeout=120.0)
        res.pop("state")
        print("OK", json.dumps(res))
    """).format(repo=repo, store=store_dir, world=world)

    def spawn(rank, join=False):
        return subprocess.Popen(
            [sys.executable, "-c", body, str(rank),
             "join" if join else "found"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)

    procs = [spawn(r) for r in range(world)]
    kills = 1 if quick else max(1, int(seconds // 10))
    period = max(5.0, seconds / (kills + 1))
    deadline = time.monotonic() + seconds
    done_kills = 0
    rng = __import__("random").Random(14)
    try:
        while time.monotonic() < deadline and done_kills < kills:
            time.sleep(min(period, max(0.0, deadline - time.monotonic())))
            live = [p for p in procs if p.poll() is None]
            if done_kills >= kills or len(live) < world:
                continue
            victim = rng.choice(live)
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=30)
            done_kills += 1
            time.sleep(1.0)
            procs.append(spawn(100 + done_kills, join=True))
            print(f"[elastic-soak] kill #{done_kills} -> respawned joiner",
                  file=sys.stderr)
        while time.monotonic() < deadline:
            time.sleep(0.25)
    finally:
        # Consensus stop: the current rank 0 folds the key into the
        # next step's flag allreduce, so every worker exits at the
        # same step boundary.
        import gloo_tpu

        gloo_tpu.FileStore(store_dir).set("soak_stop", b"1")

    summaries = []
    ok = True
    for p in procs:
        try:
            out, err = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            ok = False
            print(f"[elastic-soak] worker hung: {err[-400:]!r}",
                  file=sys.stderr)
            continue
        if p.returncode == -signal.SIGKILL:
            continue  # a driver-killed victim
        if p.returncode != 0:
            ok = False
            print(f"[elastic-soak] worker rc={p.returncode}: "
                  f"{err[-400:]!r}", file=sys.stderr)
            continue
        line = [ln for ln in out.splitlines() if ln.startswith("OK ")]
        if not line:
            ok = False
            continue
        summaries.append(json.loads(line[0][3:]))

    ok = ok and len(summaries) == world  # full size at the end
    rebuild_ms = sorted(ms for s in summaries for ms in s["rebuild_ms"])
    epochs = max((e["epoch"] for s in summaries for e in s["epochs"]),
                 default=0)
    sizes_ok = all(e["size"] >= 2 for s in summaries
                   for e in s["epochs"])

    def pct(q):
        if not rebuild_ms:
            return None
        return rebuild_ms[min(len(rebuild_ms) - 1,
                              int(q * (len(rebuild_ms) - 1) + 0.5))]

    line = {
        "metric": "elastic_soak_3rank_host",
        "value": epochs,
        "unit": "epochs",
        "seconds": seconds,
        "kills": done_kills,
        "rejoins": done_kills,
        "steps": sum(s["steps"] for s in summaries),
        "rebuilds": sum(s["rebuilds"] for s in summaries),
        "rebuild_ms_p50": pct(0.50),
        "rebuild_ms_p99": pct(0.99),
        "lease_ms": 200,
        "lease_grace_ms": 1200,
        "ok": bool(ok and sizes_ok and epochs >= 1 + 2 * done_kills),
    }
    print(json.dumps(line))
    if not line["ok"]:
        sys.exit(1)


def bench_chaos_soak(seconds):
    """--chaos-soak N: run a mixed collective/p2p workload for N seconds
    with a low-rate delay/dup fault schedule installed (the soak-mode
    face of the fault plane, docs/faults.md), verifying every result
    against its closed form. Prints ONE JSON line:

      {"metric": "chaos_soak_2rank_host", "value": <ops completed>,
       "unit": "ops", "seconds": N, "faults": <faults injected>,
       "faults_by_action": {...}, "ok": true}

    A wrong value or a hang is a failure; the point is that a transport
    under continuous low-rate fault pressure stays correct, not fast.
    """
    import numpy as np

    import gloo_tpu
    from gloo_tpu import fault

    fault.install({"seed": 0xC405, "faults": [
        {"when": {"opcode": "data", "min_bytes": 1},
         "action": "delay", "ms": 1, "prob": 0.02},
        {"when": {"opcode": "data", "min_bytes": 1},
         "action": "dup", "prob": 0.01},
    ]})
    store = gloo_tpu.HashStore()
    ops_out = [0]
    errors = []
    deadline = time.monotonic() + seconds

    def guarded(rank):
        try:
            worker(rank)
        except BaseException as exc:  # noqa: BLE001 — soak must report it
            errors.append((rank, repr(exc)))

    def worker(rank):
        import numpy as np

        device = gloo_tpu.Device()
        ctx = gloo_tpu.Context(rank, 2, timeout=60)
        ctx.connect_full_mesh(store, device)
        ops = 0
        i = 0
        while True:
            # Rank 0 owns the clock; the decision rides an allreduce so
            # both ranks always agree on the iteration count. Tags and
            # slots are unique per iteration — the dup-tolerance rule
            # (docs/faults.md) — so a stale duplicate can never match a
            # later operation.
            flag = np.array(
                [1.0 if rank != 0 or time.monotonic() < deadline
                 else 0.0], dtype=np.float32)
            ctx.allreduce(flag, op="min", tag=4 * i)
            if flag[0] < 1.0:
                break
            n = 256 + (i * 97) % 4096
            x = np.full(n, float(rank + 1 + i), dtype=np.float32)
            ctx.allreduce(x, tag=4 * i + 1)
            assert x[0] == 2 * i + 3, (i, x[0])
            g = ctx.allgather(np.full(64, float(rank + i), np.float64),
                              tag=4 * i + 2)
            assert g[0][0] == float(i) and g[1][0] == float(1 + i), g
            y = np.arange(n, dtype=np.float64) * (rank + 1)
            out = np.zeros(n, dtype=np.float64)
            ctx.send(y, dst=1 - rank, slot=10_000 + 2 * i + rank)
            ctx.recv(out, src=1 - rank, slot=10_000 + 2 * i + (1 - rank))
            assert out[1] == float(2 - rank), (i, out[1])
            ops += 4
            i += 1
        ctx.barrier(tag=1)
        if rank == 0:
            ops_out[0] = ops
        ctx.close()

    # Daemon threads: the "soak hung" branch must actually exit 1 —
    # interpreter shutdown would otherwise block forever joining the
    # still-alive worker.
    threads = [threading.Thread(target=guarded, args=(r,), daemon=True)
               for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(seconds * 10, 120))
        if t.is_alive():
            print(json.dumps({"metric": "chaos_soak_2rank_host",
                              "ok": False, "error": "soak hung"}))
            sys.exit(1)
    if errors:
        # A wrong value under fault pressure is the bug this soak
        # exists to catch — it must never report ok.
        print(json.dumps({"metric": "chaos_soak_2rank_host",
                          "ok": False,
                          "error": [f"rank {r}: {e}" for r, e in errors]}))
        sys.exit(1)
    fired = fault.report()
    fault.clear()
    by_action = {}
    for e in fired:
        by_action[e["action"]] = by_action.get(e["action"], 0) + 1
    print(json.dumps({
        "metric": "chaos_soak_2rank_host",
        "value": ops_out[0],
        "unit": "ops",
        "seconds": seconds,
        "faults": len(fired),
        "faults_by_action": by_action,
        "ok": True,
    }))


def bench_flightrec_soak(seconds):
    """--flightrec N: the post-mortem soak. Three real processes run a
    mixed collective workload for N seconds with the always-on flight
    recorder pointed at a dump directory; then one rank is SIGKILLed
    mid-collective. The survivors' transport-failure auto-dumps plus the
    victim's ABSENT dump must merge into a verdict that blames the dead
    rank. Prints ONE JSON line:

      {"metric": "flightrec_soak_3rank_host", "value": <ops recorded>,
       "unit": "ops", "seconds": N, "blamed_ranks": [2],
       "verdict": "stall", "dumps": 2, "ok": true}

    A wrong blame (or no dumps) is a failure — the chain under test is
    chaos -> recorder -> merge -> blame, end to end.
    """
    import signal as _signal
    import textwrap

    from gloo_tpu.utils import flightrec

    store = tempfile.mkdtemp()
    fr_dir = os.path.join(store, "flightrec")
    victim = 2
    body = textwrap.dedent("""
        import os, signal, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1]); size = 3
        deadline = time.monotonic() + {seconds}
        ctx = gloo_tpu.Context(rank, size, timeout=30.0)
        ctx.connect_full_mesh(gloo_tpu.FileStore({store!r}),
                              gloo_tpu.Device())
        i = 0
        try:
            while True:
                flag = np.array(
                    [1.0 if rank != 0 or time.monotonic() < deadline
                     else 0.0], dtype=np.float32)
                ctx.allreduce(flag, op="min", tag=3 * i)
                if flag[0] < 1.0:
                    break
                n = 256 + (i * 131) % 2048
                x = np.full(n, float(rank + 1), dtype=np.float32)
                ctx.allreduce(x, tag=3 * i + 1)
                assert x[0] == 6.0, (i, x[0])
                ctx.barrier(tag=3 * i + 2)
                i += 1
            # Soak done: the victim dies INSIDE the next collective so
            # survivors observe a mid-op link death, not a goodbye.
            y = np.full(1 << 16, float(rank + 1), dtype=np.float32)
            if rank == {victim}:
                os.kill(os.getpid(), signal.SIGKILL)
            ctx.allreduce(y, tag=1000000, timeout=5.0)
            print("UNEXPECTED-SUCCESS"); sys.exit(3)
        except gloo_tpu.IoError:
            pass
        print("SOAK-OK", ctx.flightrec_seq())
    """).format(repo=os.path.dirname(os.path.abspath(__file__)),
                seconds=seconds, store=store, victim=victim)
    env = dict(os.environ, TPUCOLL_FLIGHTREC_DIR=fr_dir)
    procs = [subprocess.Popen([sys.executable, "-c", body, str(r)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for r in range(3)]
    outs = [p.communicate(timeout=max(seconds * 10, 120)) for p in procs]

    ok = True
    errors = []
    ops = 0
    if procs[victim].returncode != -_signal.SIGKILL:
        ok = False
        errors.append(f"victim exited {procs[victim].returncode}, "
                      f"expected SIGKILL")
    for r in (0, 1):
        if procs[r].returncode != 0 or "SOAK-OK" not in outs[r][0]:
            ok = False
            errors.append(f"rank {r}: rc={procs[r].returncode} "
                          f"out={outs[r][0][-200:]!r} "
                          f"err={outs[r][1][-200:]!r}")
        else:
            ops = max(ops, int(outs[r][0].split("SOAK-OK", 1)[1]))

    merged = flightrec.merge(fr_dir)
    verdict = flightrec.analyze(merged)
    if verdict["blamed_ranks"] != [victim]:
        ok = False
        errors.append(f"blame miss: {verdict}")
    line = {
        "metric": "flightrec_soak_3rank_host",
        "value": ops,
        "unit": "ops",
        "seconds": seconds,
        "blamed_ranks": verdict["blamed_ranks"],
        "verdict": verdict["kind"],
        "dumps": len(merged["ranks"]),
        "ok": ok,
    }
    if errors:
        line["error"] = errors
    print(json.dumps(line))
    if not ok:
        sys.exit(1)


def bench_channel_sweep(quick=False):
    """--channel-sweep: measure 2-rank allreduce algbw across the
    multi-channel transport grid (loop threads x data channels x stripe
    threshold), one JSON line per point — the measurement source for the
    tuning plane's transport hints (tuning.set_transport_hints). Each
    point runs in fresh subprocesses because the knobs are env-resolved
    at context construction; TPUCOLL_SHM=0 pins the payloads to the TCP
    plane the knobs actually govern (same-host shm bypasses striping).
    """
    import tempfile
    import textwrap

    if quick:
        elements = 1 << 22  # 16 MiB f32
        iters, warmup = 4, 1
        grid = [(1, 1, 1 << 20), (2, 2, 1 << 20)]
    else:
        elements = ELEMENTS  # the headline 64 MiB config
        iters, warmup = 8, 2
        grid = [(loops, ch, stripe)
                for loops in (1, 2, 4)
                for ch in (1, 2, 4)
                for stripe in (256 << 10, 1 << 20, 4 << 20)
                # stripe threshold is meaningless without channels;
                # keep exactly one single-channel baseline per loop count
                if ch > 1 or stripe == 1 << 20]

    body = textwrap.dedent("""
        import sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4]); warm = int(sys.argv[5])
        x = np.full(n, float(rank + 1), dtype=np.float32)
        ctx.allreduce(x)
        assert x[0] == 3.0, x[0]
        x[:] = 1.0
        for _ in range(warm):
            ctx.allreduce(x)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x)
            times.append(time.perf_counter() - t0)
        if rank == 0:
            print("P50US", int(np.median(times) * 1e6))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    ok_all = True
    for loops, channels, stripe in grid:
        store = tempfile.mkdtemp()
        env = dict(os.environ,
                   TPUCOLL_SHM="0",
                   TPUCOLL_LOOP_THREADS=str(loops),
                   TPUCOLL_CHANNELS=str(channels),
                   TPUCOLL_STRIPE_BYTES=str(stripe))
        procs = [subprocess.Popen(
            [sys.executable, "-c", body, str(r), store, str(elements),
             str(iters), str(warmup)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for r in range(2)]
        outs = [p.communicate(timeout=600) for p in procs]
        line = {"metric": "channel_sweep", "loops": loops,
                "channels": channels, "stripe_bytes": stripe,
                "elements": elements, "iters": iters, "unit": "GB/s"}
        if any(p.returncode != 0 for p in procs) or                 "P50US" not in outs[0][0]:
            ok_all = False
            line["ok"] = False
            line["error"] = [f"rank {r}: rc={p.returncode} "
                             f"err={outs[r][1][-200:]!r}"
                             for r, p in enumerate(procs)]
        else:
            p50_us = int(outs[0][0].split("P50US", 1)[1].split()[0])
            line["value"] = round(elements * 4 / (p50_us * 1e-6) / 1e9, 3)
            line["p50_us"] = p50_us
            line["ok"] = True
        print(json.dumps(line))
    if not ok_all:
        sys.exit(1)


def bench_wire_sweep(quick=False):
    """--wire-sweep: 2-rank allreduce algbw per (wire codec x size)
    point under TPUCOLL_SHM=0 — the host plane's wire-compression
    crossover data (ISSUE 11 grid, grown by ISSUE 20: the q4 arm, the
    pipelined-vs-serial engine A/B in interleaved passes, the
    codec-threads axis, and a profiled 64 MiB phase breakdown proving
    the pack+unpack cut). One JSON line per point; fresh subprocesses
    per point so transport state never leaks between cells. Every run
    verifies the reduced values first: exact for the lossless ring,
    within the per-hop error bound for the codecs."""
    import tempfile
    import textwrap

    if quick:
        sizes = [1 << 20]  # 4 MiB f32
        iters, warmup = 3, 1
        ab_passes = 2
    else:
        sizes = [1 << 20, 1 << 22, ELEMENTS]  # 4 MiB, 16 MiB, 64 MiB
        iters, warmup = 8, 2
        ab_passes = 3
    algorithms = ["ring", "ring_bf16_wire", "ring_q8_wire",
                  "ring_q4_wire"]

    body = textwrap.dedent("""
        import sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4])
        warm = int(sys.argv[5]); algo = sys.argv[6]
        x = np.full(n, float(rank + 1), dtype=np.float32)
        ctx.allreduce(x, algorithm=algo)
        # 1+2=3 is exactly representable through the codecs' per-hop
        # quantization only to within one step; bound the error instead
        # of asserting exactness for the lossy arms (q4's step is
        # max|block|/7, the coarsest in the set).
        tol = (0.0 if algo == "ring"
               else 3.0 / 7.0 if algo == "ring_q4_wire"
               else 3.0 / 127.0)
        assert abs(x[0] - 3.0) <= tol, x[0]
        x[:] = 1.0
        for _ in range(warm):
            ctx.allreduce(x, algorithm=algo)
        x[:] = 1.0
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, algorithm=algo)
            times.append(time.perf_counter() - t0)
            x[:] = 1.0  # repeated lossy sums must not drift the scale
        if rank == 0:
            print("P50US", int(np.median(times) * 1e6))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    prof_body = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4])
        warm = int(sys.argv[5]); algo = sys.argv[6]
        x = np.full(n, 1.0, dtype=np.float32)
        for _ in range(warm + 1):
            ctx.allreduce(x, algorithm=algo)
            x[:] = 1.0
        seq0 = ctx.profile()["next_seq"]
        for _ in range(iters):
            ctx.allreduce(x, algorithm=algo)
            x[:] = 1.0
        if rank == 0:
            ops = [o for o in ctx.profile()["ops"] if o["seq"] >= seq0]
            tot = {{}}
            for o in ops:
                for k, v in o.get("phases", {{}}).items():
                    tot[k] = tot.get(k, 0) + v
            print("PHASES", json.dumps(
                {{k: v // max(len(ops), 1) for k, v in tot.items()}}))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    # The engine A/B arms. "serial" pins depth 1 on one lane with the
    # fused transport fold off — byte- and schedule-identical to the
    # pre-pipeline hop (the r11/r15 engine). "pipelined" is the new
    # default shape: depth-4 sub-blocks, a 2-wide codec pool, fused
    # dequant-accumulate on arrival.
    serial_env = {"TPUCOLL_CODEC_PIPELINE": "1",
                  "TPUCOLL_CODEC_THREADS": "1",
                  "TPUCOLL_RECV_REDUCE": "0"}
    piped_env = {"TPUCOLL_CODEC_PIPELINE": "4",
                 "TPUCOLL_CODEC_THREADS": "2",
                 "TPUCOLL_RECV_REDUCE": "1"}

    ok_all = True

    def run_point(src, elements, algo, extra_env=None, marker="P50US"):
        """One fresh 2-rank subprocess pair; returns (payload, errs)."""
        store = tempfile.mkdtemp()
        env = dict(os.environ, TPUCOLL_SHM="0", **(extra_env or {}))
        procs = [subprocess.Popen(
            [sys.executable, "-c", src, str(r), store, str(elements),
             str(iters), str(warmup), algo],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for r in range(2)]
        outs = [p.communicate(timeout=600) for p in procs]
        if any(p.returncode != 0 for p in procs) or \
                marker not in outs[0][0]:
            return None, [f"rank {r}: rc={p.returncode} "
                          f"err={outs[r][1][-200:]!r}"
                          for r, p in enumerate(procs)]
        return outs[0][0].split(marker, 1)[1], None

    def emit(line, payload, errs, elements):
        nonlocal ok_all
        if errs is not None:
            ok_all = False
            line["ok"] = False
            line["error"] = errs
        else:
            p50_us = int(payload.split()[0])
            line["value"] = round(elements * 4 / (p50_us * 1e-6) / 1e9, 3)
            line["p50_us"] = p50_us
            line["ok"] = True
        print(json.dumps(line))

    # 1) The codec-family grid (the r11 shape, plus the q4 arm).
    for elements in sizes:
        for algo in algorithms:
            payload, errs = run_point(body, elements, algo)
            emit({"metric": "wire_sweep", "algorithm": algo,
                  "elements": elements, "bytes": elements * 4,
                  "iters": iters, "unit": "GB/s"}, payload, errs,
                 elements)

    # 2) Pipelined-vs-serial engine A/B, interleaved passes (arm order
    # alternates within each pass so drift lands on both arms equally).
    for elements in sizes:
        for algo in ("ring_q8_wire", "ring_q4_wire"):
            runs = {"serial": [], "pipelined": []}
            for p in range(ab_passes):
                order = [("serial", serial_env), ("pipelined", piped_env)]
                if p % 2:
                    order.reverse()
                for arm, arm_env in order:
                    payload, errs = run_point(body, elements, algo,
                                              arm_env)
                    if errs is not None:
                        ok_all = False
                        print(json.dumps(
                            {"metric": "wire_pipeline_ab", "ok": False,
                             "algorithm": algo, "arm": arm,
                             "elements": elements, "error": errs}))
                    else:
                        runs[arm].append(int(payload.split()[0]))
            for arm in ("serial", "pipelined"):
                if not runs[arm]:
                    continue
                p50 = int(sorted(runs[arm])[len(runs[arm]) // 2])
                print(json.dumps(
                    {"metric": "wire_pipeline_ab", "algorithm": algo,
                     "arm": arm, "elements": elements,
                     "bytes": elements * 4, "iters": iters,
                     "unit": "GB/s", "runs_us": runs[arm],
                     "p50_us": p50,
                     "value": round(elements * 4 / (p50 * 1e-6) / 1e9, 3),
                     "ok": True}))

    # 3) Codec-pool width axis at the largest size (depth pinned to the
    # pipelined arm's 4 so only the pool width moves).
    for threads in (1, 2, 4):
        payload, errs = run_point(
            body, sizes[-1], "ring_q8_wire",
            {"TPUCOLL_CODEC_PIPELINE": "4",
             "TPUCOLL_CODEC_THREADS": str(threads)})
        emit({"metric": "wire_codec_threads", "algorithm": "ring_q8_wire",
              "codec_threads": threads, "elements": sizes[-1],
              "bytes": sizes[-1] * 4, "iters": iters, "unit": "GB/s"},
             payload, errs, sizes[-1])

    # 4) Profiled phase breakdown at the headline size: where did the
    # pack/unpack time go. The serial arm reproduces the pre-pipeline
    # attribution (encode + staged decode on the op thread); the
    # pipelined arm's codec work runs on the pool and in the transport
    # fold, so op-thread pack+unpack must collapse.
    phases = {}
    for arm, arm_env in (("serial", serial_env), ("pipelined", piped_env)):
        payload, errs = run_point(prof_body, sizes[-1], "ring_q8_wire",
                                  dict(arm_env, TPUCOLL_PROFILE="1"),
                                  marker="PHASES")
        line = {"metric": "wire_phase_ab", "algorithm": "ring_q8_wire",
                "arm": arm, "elements": sizes[-1],
                "bytes": sizes[-1] * 4, "iters": iters}
        if errs is not None:
            ok_all = False
            line["ok"] = False
            line["error"] = errs
        else:
            line["mean_phase_us"] = json.loads(payload)
            line["ok"] = True
            phases[arm] = line["mean_phase_us"]
        print(json.dumps(line))
    if len(phases) == 2:
        codec_us = {a: p.get("pack", 0) + p.get("unpack", 0)
                    for a, p in phases.items()}
        print(json.dumps(
            {"metric": "wire_phase_cut", "elements": sizes[-1],
             "pack_unpack_us": codec_us,
             "cut": round(codec_us["serial"] /
                          max(codec_us["pipelined"], 1), 2),
             "ok": True}))

    if not ok_all:
        sys.exit(1)


def bench_profile(quick=False):
    """--profile: per-phase breakdown per (size x algorithm) cell plus
    the profiler overhead A/B (ISSUE 15; docs/profiling.md).

    Each cell runs a fresh 2-rank subprocess pair under TPUCOLL_SHM=0,
    times `iters` allreduces, and reports the mean per-phase breakdown
    from Context.profile() restricted to the timed ops. The A/B block
    re-times the largest cell's ring allreduce with TPUCOLL_PROFILE=1
    vs =0 in interleaved passes — the committed evidence (PROF_r15.json)
    that the profiler stays inside host noise."""
    import tempfile
    import textwrap

    if quick:
        sizes = [1 << 18]  # 1 MiB f32
        iters, warmup, ab_passes = 3, 1, 2
    else:
        sizes = [1 << 18, 1 << 22, ELEMENTS]  # 1 MiB, 16 MiB, 64 MiB
        iters, warmup, ab_passes = 8, 2, 5
    algorithms = ["ring", "hd", "ring_q8_wire"]

    body = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4])
        warm = int(sys.argv[5]); algo = sys.argv[6]
        x = np.full(n, 1.0, dtype=np.float32)
        for _ in range(warm):
            ctx.allreduce(x, algorithm=algo)
            x[:] = 1.0
        first_seq = len(ctx.profile()["ops"])  # == ring seq after warm-up
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, algorithm=algo)
            times.append(time.perf_counter() - t0)
            x[:] = 1.0
        if rank == 0:
            snap = ctx.profile()
            timed = [o for o in snap["ops"]
                     if o["op"] == "allreduce" and o["seq"] >= first_seq]
            phases = {{}}
            total = 0
            for o in timed:
                total += o["total_us"]
                for k, v in o["phases"].items():
                    phases[k] = phases.get(k, 0) + v
            out = {{"p50_us": int(np.median(times) * 1e6),
                    "profiled_ops": len(timed),
                    "enabled": snap["enabled"],
                    "mean_total_us": total // max(len(timed), 1),
                    "mean_phase_us": {{k: v // max(len(timed), 1)
                                       for k, v in sorted(phases.items())}}}}
            print("RESULT " + json.dumps(out))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    def run_cell(elements, algo, profile_on):
        store = tempfile.mkdtemp()
        env = dict(os.environ, TPUCOLL_SHM="0",
                   TPUCOLL_PROFILE="1" if profile_on else "0")
        procs = [subprocess.Popen(
            [sys.executable, "-c", body, str(r), store, str(elements),
             str(iters), str(warmup), algo],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for r in range(2)]
        outs = [p.communicate(timeout=600) for p in procs]
        if any(p.returncode != 0 for p in procs) or \
                "RESULT " not in outs[0][0]:
            return None, [f"rank {r}: rc={p.returncode} "
                          f"err={outs[r][1][-200:]!r}"
                          for r, p in enumerate(procs)]
        return json.loads(outs[0][0].split("RESULT ", 1)[1]), None

    ok_all = True
    for elements in sizes:
        for algo in algorithms:
            res, err = run_cell(elements, algo, profile_on=True)
            line = {"metric": "profile_phases", "algorithm": algo,
                    "elements": elements, "bytes": elements * 4,
                    "iters": iters}
            if res is None:
                ok_all = False
                line.update(ok=False, error=err)
            else:
                line.update(ok=True, **res)
            print(json.dumps(line))

    # Overhead A/B on the largest ring cell: interleaved passes so host
    # drift hits both arms equally; the JSON records both p50 series.
    ab_elements = sizes[-1]
    on_us, off_us = [], []
    ab_errors = []
    for _ in range(ab_passes):
        for arm, acc in (("on", on_us), ("off", off_us)):
            res, err = run_cell(ab_elements, "ring", arm == "on")
            if res is None:
                ab_errors.extend(err)
            else:
                acc.append(res["p50_us"])
    line = {"metric": "profile_overhead_ab", "algorithm": "ring",
            "elements": ab_elements, "bytes": ab_elements * 4,
            "passes": ab_passes}
    # A pass failure anywhere invalidates the A/B as committed evidence
    # (a median over fewer samples than `passes` claims would quietly
    # understate its own noise): every collected error is emitted and
    # flips ok=False, even when both arms still have survivors.
    if not on_us or not off_us or ab_errors:
        ok_all = False
        line.update(ok=False, error=ab_errors,
                    runs_on_us=on_us, runs_off_us=off_us)
    else:
        med_on = sorted(on_us)[len(on_us) // 2]
        med_off = sorted(off_us)[len(off_us) // 2]
        line.update(ok=True, p50_us_profile_on=med_on,
                    p50_us_profile_off=med_off,
                    runs_on_us=on_us, runs_off_us=off_us,
                    overhead=round(med_on / med_off - 1.0, 4))
    print(json.dumps(line))
    if not ok_all:
        sys.exit(1)


def bench_critpath(quick=False):
    """--critpath: overhead A/B of the causal span recorder (ISSUE 19;
    docs/critpath.md) plus a critical-path attribution sanity cell.

    The A/B times 2-rank ring allreduces with TPUCOLL_SPANS=1 vs =0 in
    interleaved passes (host drift hits both arms equally) — the
    committed evidence (CRIT_r19.json) that span recording stays inside
    host noise. The attribution cell runs one spans-on pair, merges
    both ranks' Context.spans() through utils.critpath.analyze(), and
    reports how much of the op latency the extracted critical path
    explains and that every wire edge matched."""
    import tempfile
    import textwrap

    if quick:
        elements, iters, warmup, ab_passes = 1 << 18, 3, 1, 2
    else:
        elements, iters, warmup, ab_passes = 1 << 22, 8, 2, 5

    body = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4])
        warm = int(sys.argv[5]); store = sys.argv[2]
        x = np.full(n, 1.0, dtype=np.float32)
        for _ in range(warm):
            ctx.allreduce(x, algorithm="ring")
            x[:] = 1.0
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, algorithm="ring")
            times.append(time.perf_counter() - t0)
            x[:] = 1.0
        # Every rank parks its span snapshot in the store dir before the
        # barrier so rank 0 can fold a cross-rank analysis after it.
        import os
        with open(os.path.join(store, f"spans-rank{{rank}}.json"),
                  "w") as f:
            json.dump(ctx.spans(), f)
        ctx.barrier()
        if rank == 0:
            from gloo_tpu.utils import critpath
            snaps = []
            for r in range(2):
                with open(os.path.join(store,
                                       f"spans-rank{{r}}.json")) as f:
                    snaps.append(json.load(f))
            out = {{"p50_us": int(np.median(times) * 1e6),
                    "spans_enabled": snaps[0]["enabled"]}}
            if snaps[0]["enabled"]:
                a = critpath.analyze(critpath.merge(snaps))
                covs, unmatched = [], 0
                for op in a["ops"]:
                    if op["total_us"] <= 0:
                        continue
                    covered = sum(r["contrib_us"] for r in op["path"])
                    covs.append(covered / op["total_us"])
                    unmatched += sum(op["unmatched"].values())
                covs.sort()
                out.update(analyzed_ops=len(covs), unmatched=unmatched,
                           path_coverage_p50=round(
                               covs[len(covs) // 2], 4) if covs else 0.0)
            print("RESULT " + json.dumps(out))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    def run_cell(spans_on):
        store = tempfile.mkdtemp()
        env = dict(os.environ, TPUCOLL_SHM="0",
                   TPUCOLL_SPANS="1" if spans_on else "0")
        procs = [subprocess.Popen(
            [sys.executable, "-c", body, str(r), store, str(elements),
             str(iters), str(warmup)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for r in range(2)]
        outs = [p.communicate(timeout=600) for p in procs]
        if any(p.returncode != 0 for p in procs) or \
                "RESULT " not in outs[0][0]:
            return None, [f"rank {r}: rc={p.returncode} "
                          f"err={outs[r][1][-200:]!r}"
                          for r, p in enumerate(procs)]
        return json.loads(outs[0][0].split("RESULT ", 1)[1]), None

    ok_all = True

    # Attribution sanity cell (spans on, one pair).
    res, err = run_cell(spans_on=True)
    line = {"metric": "critpath_attribution", "algorithm": "ring",
            "elements": elements, "bytes": elements * 4, "iters": iters}
    if res is None:
        ok_all = False
        line.update(ok=False, error=err)
    else:
        line.update(ok=True, **res)
    print(json.dumps(line))

    # Overhead A/B: interleaved passes so host drift hits both arms
    # equally; the JSON records both p50 series.
    on_us, off_us = [], []
    ab_errors = []
    for i in range(ab_passes):
        # Alternate which arm goes first so per-pass warm-up transients
        # (page cache, cpufreq) don't land on one arm systematically.
        arms = (("on", on_us), ("off", off_us))
        for arm, acc in arms if i % 2 == 0 else arms[::-1]:
            res, err = run_cell(spans_on=arm == "on")
            if res is None:
                ab_errors.extend(err)
            else:
                acc.append(res["p50_us"])
    line = {"metric": "critpath_overhead_ab", "algorithm": "ring",
            "elements": elements, "bytes": elements * 4,
            "passes": ab_passes}
    # A pass failure anywhere invalidates the A/B as committed evidence
    # (same rule as profile_overhead_ab): every collected error is
    # emitted and flips ok=False, even when both arms have survivors.
    if not on_us or not off_us or ab_errors:
        ok_all = False
        line.update(ok=False, error=ab_errors,
                    runs_on_us=on_us, runs_off_us=off_us)
    else:
        med_on = sorted(on_us)[len(on_us) // 2]
        med_off = sorted(off_us)[len(off_us) // 2]
        line.update(ok=True, p50_us_spans_on=med_on,
                    p50_us_spans_off=med_off,
                    runs_on_us=on_us, runs_off_us=off_us,
                    overhead=round(med_on / med_off - 1.0, 4))
    print(json.dumps(line))
    if not ok_all:
        sys.exit(1)


def bench_fleetobs(quick=False):
    """--fleetobs: overhead A/B of the in-band fleet observability
    plane (ISSUE 16; docs/fleet.md).

    Each arm runs a fresh 4-rank subprocess grid over a FileStore with
    two simulated hosts (TPUCOLL_HOST_ID per process) so the full
    member -> leader -> rank 0 relay is live, and times `iters` ring
    allreduces with the plane aggregating at a 100 ms interval (on) vs
    TPUCOLL_FLEETOBS=0 (off). Arms are interleaved so host drift hits
    both equally. The on-arm also reports the fleet document's
    coverage — the committed evidence (OBS_r16.json) that the plane
    covers every rank while staying inside host noise."""
    import tempfile
    import textwrap

    if quick:
        elements, iters, warmup, ab_passes = 1 << 18, 3, 1, 2
    else:
        elements, iters, warmup, ab_passes = 1 << 20, 8, 2, 5
    size, rph = 4, 2

    body = textwrap.dedent("""
        import json, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu
        from gloo_tpu.utils import fleet as fleet_util

        rank = int(sys.argv[1])
        ctx = gloo_tpu.Context(rank, {size}, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[2]),
                              gloo_tpu.Device())
        n = int(sys.argv[3]); iters = int(sys.argv[4])
        warm = int(sys.argv[5]); fleet_on = sys.argv[6] == "on"
        if fleet_on:
            ctx.fleetobs_start()
        x = np.full(n, 1.0, dtype=np.float32)
        for _ in range(warm):
            ctx.allreduce(x, algorithm="ring")
            x[:] = 1.0
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx.allreduce(x, algorithm="ring")
            times.append(time.perf_counter() - t0)
            x[:] = 1.0
        coverage = None
        if fleet_on and rank == 0:
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                coverage = fleet_util.coverage(ctx.fleet())
                if coverage["complete"]:
                    break
                time.sleep(0.1)
        if rank == 0:
            out = {{"p50_us": int(np.median(times) * 1e6),
                    "running": ctx.fleetobs_running(),
                    "coverage": coverage}}
            print("RESULT " + json.dumps(out))
        ctx.barrier()
        if fleet_on:
            ctx.fleetobs_stop()
        ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)),
                size=size)

    def run_arm(arm):
        store = tempfile.mkdtemp()
        procs = []
        for r in range(size):
            env = dict(os.environ, TPUCOLL_SHM="0",
                       TPUCOLL_HOST_ID=f"obshost{r // rph}",
                       TPUCOLL_FLEETOBS="1" if arm == "on" else "0",
                       TPUCOLL_FLEETOBS_INTERVAL_MS="100")
            procs.append(subprocess.Popen(
                [sys.executable, "-c", body, str(r), store,
                 str(elements), str(iters), str(warmup), arm],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env))
        outs = [p.communicate(timeout=600) for p in procs]
        if any(p.returncode != 0 for p in procs) or \
                "RESULT " not in outs[0][0]:
            return None, [f"rank {r}: rc={p.returncode} "
                          f"err={outs[r][1][-200:]!r}"
                          for r, p in enumerate(procs)]
        return json.loads(outs[0][0].split("RESULT ", 1)[1]), None

    on_us, off_us, ab_errors = [], [], []
    coverages = []
    for _ in range(ab_passes):
        for arm, acc in (("on", on_us), ("off", off_us)):
            res, err = run_arm(arm)
            if res is None:
                ab_errors.extend(err)
                continue
            acc.append(res["p50_us"])
            if arm == "on":
                coverages.append(res["coverage"])
    line = {"metric": "fleetobs_overhead_ab", "algorithm": "ring",
            "ranks": size, "hosts": size // rph, "elements": elements,
            "bytes": elements * 4, "iters": iters, "passes": ab_passes}
    covered = bool(coverages) and all(
        c and c["complete"] for c in coverages)
    # Same evidence discipline as the profiler A/B: any pass failure or
    # coverage hole flips ok=False — a partial median would quietly
    # overstate its own confidence.
    if not on_us or not off_us or ab_errors or not covered:
        line.update(ok=False, error=ab_errors, coverage=coverages,
                    runs_on_us=on_us, runs_off_us=off_us)
        print(json.dumps(line))
        sys.exit(1)
    med_on = sorted(on_us)[len(on_us) // 2]
    med_off = sorted(off_us)[len(off_us) // 2]
    line.update(ok=True, p50_us_fleetobs_on=med_on,
                p50_us_fleetobs_off=med_off,
                runs_on_us=on_us, runs_off_us=off_us,
                coverage=coverages[-1],
                overhead=round(med_on / med_off - 1.0, 4))
    print(json.dumps(line))


def bench_hier_sweep(quick=False):
    """--hier-sweep: flat (ring) vs hierarchical allreduce per
    (size x simulated hosts x ranks-per-host) cell, one JSON line per
    cell (ISSUE 13; docs/topology.md).

    Each cell spawns hosts*rph real processes over a FileStore, with
    TPUCOLL_HOST_ID grouping them into simulated hosts — so intra-"host"
    pairs negotiate the shm plane while cross-"host" pairs stay on TCP
    (the topology mask pins them there), exactly the mixed fabric the
    hierarchical schedule is built for. Both arms run in the same
    process set (same mesh, interleaved) and verify the reduced value
    first; `hier_vs_flat` is the bandwidth ratio (>1 = hier faster)."""
    import tempfile
    import textwrap

    if quick:
        cells = [(2, 2, 1 << 18)]  # 2 hosts x 2 rph, 1 MiB f32
        iters, warmup = 3, 1
    else:
        cells = [(hosts, rph, elements)
                 for hosts, rph in ((2, 2), (2, 3))
                 for elements in (1 << 16, 1 << 18, 1 << 20, 1 << 22)]
        iters, warmup = 8, 2

    body = textwrap.dedent("""
        import sys, time, json
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1]); size = int(sys.argv[2])
        rph = int(sys.argv[3]); n = int(sys.argv[4])
        iters = int(sys.argv[5]); warm = int(sys.argv[6])
        ctx = gloo_tpu.Context(rank, size, timeout=120)
        ctx.set_host_id("simhost%d" % (rank // rph))
        ctx.connect_full_mesh(gloo_tpu.FileStore(sys.argv[7]),
                              gloo_tpu.Device())
        topo = ctx.topology()
        assert topo["n_hosts"] == size // rph and topo["non_flat"], topo
        expect = float(sum(range(1, size + 1)))
        # Correctness first, then INTERLEAVED timed passes: alternating
        # the arms inside each iteration exposes both to the same host
        # drift (this box's run-to-run spread dwarfs the arm delta).
        times = {{"ring": [], "hier": []}}
        x = np.full(n, float(rank + 1), dtype=np.float32)
        for algo in ("ring", "hier"):
            ctx.allreduce(x, algorithm=algo)
        x = np.full(n, float(rank + 1), dtype=np.float32)
        ctx.allreduce(x, algorithm="hier")
        assert x[0] == expect and x[-1] == expect, x[0]
        x[:] = 1.0
        for _ in range(warm):
            for algo in ("ring", "hier"):
                ctx.allreduce(x, algorithm=algo)
        for _ in range(iters):
            for algo in ("ring", "hier"):
                t0 = time.perf_counter()
                ctx.allreduce(x, algorithm=algo)
                times[algo].append(time.perf_counter() - t0)
                x[:] = 1.0
        results = {{a: int(np.median(t) * 1e6)
                    for a, t in times.items()}}
        # Mixed-fabric evidence: intra-host pairs negotiated shm.
        assert ctx.shm_stats()["active_pairs"] == rph - 1
        if rank == 0:
            print("P50US", json.dumps(results))
        ctx.barrier(); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    ok_all = True
    for hosts, rph, elements in cells:
        size = hosts * rph
        store = tempfile.mkdtemp()
        procs = [subprocess.Popen(
            [sys.executable, "-c", body, str(r), str(size), str(rph),
             str(elements), str(iters), str(warmup), store],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for r in range(size)]
        outs = [p.communicate(timeout=600) for p in procs]
        line = {"metric": "hier_sweep", "hosts": hosts,
                "ranks_per_host": rph, "ranks": size,
                "elements": elements, "bytes": elements * 4,
                "iters": iters, "unit": "GB/s"}
        if any(p.returncode != 0 for p in procs) or \
                "P50US" not in outs[0][0]:
            ok_all = False
            line["ok"] = False
            line["error"] = [f"rank {r}: rc={p.returncode} "
                             f"err={outs[r][1][-200:]!r}"
                             for r, p in enumerate(procs)]
        else:
            p50 = json.loads(
                outs[0][0].split("P50US", 1)[1].strip().splitlines()[0])
            line["flat_p50_us"] = p50["ring"]
            line["hier_p50_us"] = p50["hier"]
            line["flat_gbps"] = round(
                elements * 4 / (p50["ring"] * 1e-6) / 1e9, 3)
            line["hier_gbps"] = round(
                elements * 4 / (p50["hier"] * 1e-6) / 1e9, 3)
            line["hier_vs_flat"] = round(p50["ring"] / p50["hier"], 3)
            line["ok"] = True
        print(json.dumps(line))
    if not ok_all:
        sys.exit(1)


def bench_grad_bucket(n_tensors, lanes=2, pin=False):
    """--grad-bucket N: the training-shaped workload — N heterogeneous
    gradient tensors with log-normally distributed sizes, allreduced
    per step either sequentially (one blocking allreduce per tensor,
    the pre-async baseline) or through the async engine + gradient
    bucketer (docs/async.md: per-dtype ~TPUCOLL_BUCKET_BYTES flat
    buckets, issued async so bucket k+1's pack overlaps bucket k's wire
    time). Two real rank processes over a FileStore; per mode the step
    time is the median of 5 timed steps after a warm-up step; three
    size-distribution seeds; ONE JSON line:

      {"metric": "grad_bucket_allreduce_2rank_host",
       "value": <geomean over seeds of seq_ms / bucketed_ms>,
       "unit": "x_speedup_vs_sequential", "tensors": N, "lanes": L,
       "bucket_bytes": B, "pinned": bool,
       "cells": [{"seed", "total_mb", "seq_ms", "bucketed_ms",
                  "speedup"}, ...]}

    Every step's results are verified against the closed form on both
    ranks before anything is timed.
    """
    import math
    import textwrap

    from gloo_tpu.bucketer import DEFAULT_BUCKET_BYTES

    bucket_bytes = int(os.environ.get("TPUCOLL_BUCKET_BYTES",
                                      DEFAULT_BUCKET_BYTES))
    body = textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, {repo!r})
        import numpy as np
        import gloo_tpu

        rank = int(sys.argv[1]); store_path = sys.argv[2]
        n = int(sys.argv[3]); seed = int(sys.argv[4])
        lanes = int(sys.argv[5]); pin = int(sys.argv[6])
        if pin:
            os.sched_setaffinity(0, {{rank % (os.cpu_count() or 1)}})
        ctx = gloo_tpu.Context(rank, 2, timeout=120)
        ctx.connect_full_mesh(gloo_tpu.FileStore(store_path),
                              gloo_tpu.Device())

        # Log-normal tensor sizes (the shape of a real model's gradient
        # list: many small, a few large), identical on both ranks.
        rng = np.random.default_rng(seed)
        nbytes = np.exp(rng.normal(np.log(64 * 1024), 1.25, size=n))
        nbytes = np.clip(nbytes, 1024, 8 << 20).astype(np.int64)
        tensors = [np.empty(max(1, int(b) // 4), dtype=np.float32)
                   for b in nbytes]

        def refill():
            for t in tensors:
                t[:] = rank + 1.0

        def verify():
            for t in tensors:
                assert t[0] == 3.0, t[0]

        STEPS = 5

        # Sequential baseline: one blocking allreduce per tensor.
        refill(); ctx.barrier(tag=1)
        for t in tensors:
            ctx.allreduce(t)
        verify()
        seq_times = []
        for _ in range(STEPS):
            refill(); ctx.barrier(tag=2)
            t0 = time.perf_counter()
            for t in tensors:
                ctx.allreduce(t)
            seq_times.append(time.perf_counter() - t0)
        verify()

        # Bucketed-async: per-dtype flat buckets on the engine lanes.
        engine = ctx.async_engine(lanes=lanes)
        bucketer = gloo_tpu.GradientBucketer(engine)
        refill(); ctx.barrier(tag=3)
        for t in tensors:
            bucketer.add(t)
        bucketer.finish()
        verify()
        bkt_times = []
        for _ in range(STEPS):
            refill(); ctx.barrier(tag=4)
            t0 = time.perf_counter()
            for t in tensors:
                bucketer.add(t)
            bucketer.finish()
            bkt_times.append(time.perf_counter() - t0)
        verify()
        if rank == 0:
            print("SEQ_MS", round(float(np.median(seq_times)) * 1e3, 2),
                  "BKT_MS", round(float(np.median(bkt_times)) * 1e3, 2),
                  "TOTAL_MB",
                  round(float(sum(t.nbytes for t in tensors)) / 2**20, 1))
        ctx.barrier(tag=5); ctx.close()
    """).format(repo=os.path.dirname(os.path.abspath(__file__)))

    cells = []
    ok_all = True
    for seed in (11, 23, 47):
        store = tempfile.mkdtemp()
        procs = [subprocess.Popen(
            [sys.executable, "-c", body, str(r), store, str(n_tensors),
             str(seed), str(lanes), "1" if pin else "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for r in range(2)]
        outs = [p.communicate(timeout=900) for p in procs]
        if any(p.returncode != 0 for p in procs) or \
                "SEQ_MS" not in outs[0][0]:
            ok_all = False
            cells.append({"seed": seed, "ok": False,
                          "error": [f"rank {r}: rc={p.returncode} "
                                    f"err={outs[r][1][-300:]!r}"
                                    for r, p in enumerate(procs)]})
            continue
        fields = outs[0][0].split()
        seq_ms = float(fields[fields.index("SEQ_MS") + 1])
        bkt_ms = float(fields[fields.index("BKT_MS") + 1])
        total_mb = float(fields[fields.index("TOTAL_MB") + 1])
        cells.append({"seed": seed, "total_mb": total_mb,
                      "seq_ms": seq_ms, "bucketed_ms": bkt_ms,
                      "speedup": round(seq_ms / bkt_ms, 3)})
        print(f"[grad-bucket] seed {seed}: {n_tensors} tensors "
              f"({total_mb:.1f} MiB) seq {seq_ms:.1f}ms bucketed "
              f"{bkt_ms:.1f}ms ({seq_ms / bkt_ms:.2f}x)",
              file=sys.stderr)
    line = {
        "metric": "grad_bucket_allreduce_2rank_host",
        "unit": "x_speedup_vs_sequential",
        "tensors": n_tensors,
        "lanes": lanes,
        "bucket_bytes": bucket_bytes,
        "pinned": pin,
        "cells": cells,
        "ok": ok_all,
    }
    good = [c["speedup"] for c in cells if "speedup" in c]
    if good:
        line["value"] = round(
            math.exp(sum(math.log(s) for s in good) / len(good)), 3)
    print(json.dumps(line))
    if not ok_all:
        sys.exit(1)


def bench_bootstrap_sweep(quick=False, out_path=None):
    """--bootstrap-sweep [--quick]: measure the bootstrap plane
    (docs/bootstrap.md) along its three acceptance axes and write ONE
    JSON document (default BOOT_r18.json next to this script):

    1. Store choreography: tc_boot_rendezvous_bench runs an in-process
       N-thread rendezvous over a shared FileStore for N in {8, 32,
       128, 512} ({8, 32} with --quick), once with the leader-relayed
       lazy protocol and once with the full-mesh simulation the seed's
       connectFullMesh performs. The lazy arm's store traffic is
       O(hosts^2 + N) vs O(N^2); by N=512 the wall-clock gap must be
       superlinear in N (the committed evidence for P>=512 scaling).
    2. Real bring-up at small N: 8 thread-ranks across 2 simulated
       hosts connect with TPUCOLL_BOOT_MODE=lazy vs the default eager
       full mesh, verifying the reduced value both ways, then soak the
       lazy mesh with a mixed alltoall/allreduce/p2p workload under
       TPUCOLL_MAX_PAIRS=2 and assert the broker held the steady-state
       broker-dialed pair count at or under the cap (with evictions
       actually exercised).
    3. Elastic rebuild with per-host lease aggregation: re-runs the
       --elastic-soak quick cell with TPUCOLL_LEASE_AGG=1 and checks
       rebuild_ms_p50 against the committed ELASTIC_r14.json p50 —
       aggregation must not slow the small-N rebuild it exists to
       protect at large N.
    """
    import numpy as np

    import gloo_tpu
    from gloo_tpu import _lib

    repo = os.path.dirname(os.path.abspath(__file__))
    if out_path is None:
        out_path = os.path.join(repo, "BOOT_r18.json")
    rph, shards, payload = 8, 8, 64
    ns = (8, 32) if quick else (8, 32, 128, 512)
    ok_all = True

    # -- 1. store choreography curves (native N-thread rendezvous sim) --
    choreography = []
    for n in ns:
        cell = {"nranks": n, "hosts": max(1, n // rph)}
        for arm in ("lazy", "full"):
            d = tempfile.mkdtemp()
            raw = _lib.copy_out(
                _lib.lib.tc_boot_rendezvous_bench, d.encode(), n, rph,
                shards, 1 if arm == "lazy" else 0, payload, 300000)
            cell[arm] = {k: v for k, v in json.loads(raw).items()
                         if k in ("wall_ms", "publish_ms", "topo_ms",
                                  "exchange_ms", "store_ops",
                                  "store_bytes")}
        cell["wall_ratio"] = round(
            cell["full"]["wall_ms"] / max(cell["lazy"]["wall_ms"], 1e-9), 2)
        cell["ops_ratio"] = round(
            cell["full"]["store_ops"] / max(cell["lazy"]["store_ops"], 1), 2)
        # Crossover: the relay round-trips cost more than they save at
        # tiny N; from 128 up the O(N^2) store scan must lose.
        if n >= 128 and cell["wall_ratio"] <= 1.0:
            ok_all = False
        choreography.append(cell)
        print(f"[bootstrap-sweep] N={n}: lazy "
              f"{cell['lazy']['wall_ms']:.0f}ms/"
              f"{cell['lazy']['store_ops']} ops, full "
              f"{cell['full']['wall_ms']:.0f}ms/"
              f"{cell['full']['store_ops']} ops "
              f"({cell['wall_ratio']}x wall)", file=sys.stderr)
    # Superlinear gap: the full/lazy wall ratio must itself grow with N.
    ratios = [c["wall_ratio"] for c in choreography]
    if not quick and not ratios[-1] > ratios[-2]:
        ok_all = False

    # -- 2. real bring-up + capped-broker soak at 8 ranks / 2 hosts --
    size, cap = 8, 2

    def bringup(lazy, soak):
        errs = []
        connect_ms = [0.0] * size
        stats = [None] * size
        store_dir = tempfile.mkdtemp()
        barrier = threading.Barrier(size)

        def worker(rank):
            try:
                ctx = gloo_tpu.Context(rank, size, timeout=60)
                ctx.set_host_id("bootbench%d" % (rank // 4))
                barrier.wait()
                t0 = time.perf_counter()
                ctx.connect_full_mesh(gloo_tpu.FileStore(store_dir),
                                      gloo_tpu.Device())
                connect_ms[rank] = (time.perf_counter() - t0) * 1e3
                eager = ctx.metrics()["boot"]["pairs_connected"]
                x = np.full(64, float(rank + 1), dtype=np.float32)
                ctx.allreduce(x)
                assert x[0] == size * (size + 1) / 2, x[0]
                if soak:
                    for i in range(12):
                        a2a = np.full((size, 8), float(rank),
                                      dtype=np.float32)
                        out = ctx.alltoall(a2a, tag=1)
                        assert out[rank][0] == float(rank), out[rank][0]
                        y = np.ones(256, dtype=np.float32)
                        ctx.allreduce(y)
                        assert y[0] == size, y[0]
                    # Quiesced single fresh dial per rank: the cap is
                    # enforced at dial time (in-flight pairs are pinned
                    # and may transiently exceed it), so the steady-
                    # state claim is "after a dial with the mesh idle,
                    # broker pairs <= cap".
                    ctx.barrier(tag=2)
                    z = np.full(16, float(rank), dtype=np.float32)
                    ctx.send(z, (rank + 3) % size, slot=7)
                    w = np.empty(16, dtype=np.float32)
                    ctx.recv(w, (rank - 3) % size, slot=7)
                    assert w[0] == float((rank - 3) % size), w[0]
                    boot = ctx.metrics()["boot"]
                    broker = boot["pairs_connected"] - eager
                    assert broker <= cap, (rank, broker, boot)
                    stats[rank] = {"eager": eager,
                                   "broker_end": broker,
                                   "evicted": boot["pairs_evicted"],
                                   "dials": boot["lazy_dials"]}
                ctx.barrier(tag=3)
                ctx.close()
            except BaseException as e:  # noqa: B036 - report & join
                errs.append(f"rank {rank}: {type(e).__name__}: {e}")

        env_keys = ("TPUCOLL_BOOT_MODE", "TPUCOLL_MAX_PAIRS")
        saved = {k: os.environ.get(k) for k in env_keys}
        try:
            if lazy:
                os.environ["TPUCOLL_BOOT_MODE"] = "lazy"
                os.environ["TPUCOLL_MAX_PAIRS"] = str(cap)
            else:
                os.environ.pop("TPUCOLL_BOOT_MODE", None)
                os.environ.pop("TPUCOLL_MAX_PAIRS", None)
            threads = [threading.Thread(target=worker, args=(r,))
                       for r in range(size)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if errs:
            raise RuntimeError("; ".join(errs))
        return max(connect_ms), stats

    e2e = {"nranks": size, "hosts": 2, "cap": cap}
    try:
        lazy_ms, soak_stats = bringup(lazy=True, soak=True)
        full_ms, _ = bringup(lazy=False, soak=False)
        e2e["connect_ms_lazy"] = round(lazy_ms, 1)
        e2e["connect_ms_full"] = round(full_ms, 1)
        e2e["soak"] = {
            "iters": 12,
            "eager_pairs": [s["eager"] for s in soak_stats],
            "broker_pairs_end": [s["broker_end"] for s in soak_stats],
            "evictions": sum(s["evicted"] for s in soak_stats),
            "dials": sum(s["dials"] for s in soak_stats),
        }
        e2e["ok"] = (max(s["broker_end"] for s in soak_stats) <= cap
                     and e2e["soak"]["evictions"] > 0)
    except RuntimeError as e:
        e2e["ok"] = False
        e2e["error"] = str(e)[-500:]
    ok_all = ok_all and e2e["ok"]
    print(f"[bootstrap-sweep] e2e 8-rank: {e2e}", file=sys.stderr)

    # -- 3. elastic rebuild with aggregated leases vs ELASTIC_r14 --
    base_p50 = 11
    try:
        with open(os.path.join(repo, "ELASTIC_r14.json")) as f:
            base_p50 = json.load(f)["rebuild_ms_p50"]
    except (OSError, KeyError, ValueError):
        pass
    soak_s = "8" if quick else "20"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--elastic-soak", soak_s, "--quick"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, TPUCOLL_LEASE_AGG="1"))
    elastic = {"baseline_r14_p50_ms": base_p50, "lease_agg": True}
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    if proc.returncode == 0 and lines:
        soak_line = json.loads(lines[-1])
        elastic["rebuild_ms_p50"] = soak_line["rebuild_ms_p50"]
        elastic["rebuild_ms_p99"] = soak_line["rebuild_ms_p99"]
        elastic["epochs"] = soak_line["value"]
        elastic["kills"] = soak_line["kills"]
        # Same-machine jitter allowance: the claim is "aggregation does
        # not slow the small-N rebuild", not a microbenchmark tie.
        elastic["ok"] = (soak_line["ok"]
                         and soak_line["rebuild_ms_p50"] <= base_p50 * 2)
    else:
        elastic["ok"] = False
        elastic["error"] = (proc.stderr or proc.stdout)[-500:]
    ok_all = ok_all and elastic["ok"]
    print(f"[bootstrap-sweep] elastic agg rebuild: {elastic}",
          file=sys.stderr)

    doc = {
        "metric": "bootstrap_scale_sweep",
        "unit": "x_full_over_lazy_wall",
        "value": ratios[-1],
        "quick": quick,
        "ranks_per_host": rph,
        "shards": shards,
        "choreography": choreography,
        "e2e_8rank": e2e,
        "elastic_rebuild": elastic,
        "ok": ok_all,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, sort_keys=True)
        f.write("\n")
    print(json.dumps({k: doc[k] for k in ("metric", "value", "ok")}))
    if not ok_all:
        sys.exit(1)


def main():
    global PIN_RANKS
    if "--pin" in sys.argv[1:]:
        PIN_RANKS = True
    if "--grad-bucket" in sys.argv[1:]:
        i = sys.argv.index("--grad-bucket") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--grad-bucket requires a tensor count")
        lanes = 2
        if "--lanes" in sys.argv[1:]:
            j = sys.argv.index("--lanes") + 1
            if j >= len(sys.argv) or sys.argv[j].startswith("--"):
                sys.exit("--lanes requires a count")
            lanes = int(sys.argv[j])
        bench_grad_bucket(int(sys.argv[i]), lanes=lanes, pin=PIN_RANKS)
        return
    if "--flightrec" in sys.argv[1:]:
        i = sys.argv.index("--flightrec") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--flightrec requires a duration (seconds)")
        bench_flightrec_soak(float(sys.argv[i]))
        return
    if "--latency" in sys.argv[1:]:
        bench_latency(quick="--quick" in sys.argv[1:])
        return
    if "--channel-sweep" in sys.argv[1:]:
        bench_channel_sweep(quick="--quick" in sys.argv[1:])
        return
    if "--wire-sweep" in sys.argv[1:]:
        bench_wire_sweep(quick="--quick" in sys.argv[1:])
        return
    if "--hier-sweep" in sys.argv[1:]:
        bench_hier_sweep(quick="--quick" in sys.argv[1:])
        return
    if "--bootstrap-sweep" in sys.argv[1:]:
        out = None
        if "--bootstrap-out" in sys.argv[1:]:
            i = sys.argv.index("--bootstrap-out") + 1
            if i >= len(sys.argv) or sys.argv[i].startswith("--"):
                sys.exit("--bootstrap-out requires a path argument")
            out = sys.argv[i]
        bench_bootstrap_sweep(quick="--quick" in sys.argv[1:],
                              out_path=out)
        return
    if "--profile" in sys.argv[1:]:
        bench_profile(quick="--quick" in sys.argv[1:])
        return
    if "--fleetobs" in sys.argv[1:]:
        bench_fleetobs(quick="--quick" in sys.argv[1:])
        return
    if "--critpath" in sys.argv[1:]:
        bench_critpath(quick="--quick" in sys.argv[1:])
        return
    if "--elastic-soak" in sys.argv[1:]:
        i = sys.argv.index("--elastic-soak") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--elastic-soak requires a duration (seconds)")
        bench_elastic_soak(float(sys.argv[i]),
                           quick="--quick" in sys.argv[1:])
        return
    if "--chaos-soak" in sys.argv[1:]:
        i = sys.argv.index("--chaos-soak") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--chaos-soak requires a duration (seconds)")
        bench_chaos_soak(float(sys.argv[i]))
        return
    if "--schedule-sweep" in sys.argv[1:]:
        out = None
        if "--schedule-out" in sys.argv[1:]:
            i = sys.argv.index("--schedule-out") + 1
            if i >= len(sys.argv) or sys.argv[i].startswith("--"):
                sys.exit("--schedule-out requires a path argument")
            out = sys.argv[i]
        bench_schedule_sweep(quick="--quick" in sys.argv[1:],
                             out_path=out)
        return
    if "--autotune" in sys.argv[1:]:
        out = None
        if "--autotune-out" in sys.argv[1:]:
            i = sys.argv.index("--autotune-out") + 1
            if i >= len(sys.argv) or sys.argv[i].startswith("--"):
                sys.exit("--autotune-out requires a path argument")
            out = sys.argv[i]
        bench_autotune(quick="--autotune-quick" in sys.argv[1:],
                       out_path=out)
        return
    # Median-of-5 full measurements after one discarded warm-up run:
    # this host's run-to-run spread was measured at ~9.4% over
    # median-of-3 (BENCH_r05), which is noise the channel sweep's
    # comparisons cannot afford. The warm-up run pays the first-touch /
    # page-cache / cpufreq transients once, outside the sample; five
    # samples tighten the median's own variance. `spread` =
    # (max - min) / median — readers (and the round-over-round diff)
    # see the remaining noise floor next to the number.
    # --metrics: include a per-op metrics digest (calls, bytes, p50/p95
    # latency from the native registry's histograms) from the last run's
    # rank-0 context in the JSON line. Opt-in so the headline number's
    # methodology is untouched by default.
    with_metrics = "--metrics" in sys.argv[1:]
    metrics_out = [] if with_metrics else None
    warmup = bench_ours()
    print(f"[bench] warm-up run: {warmup:.3f} GB/s (discarded)",
          file=sys.stderr)
    runs = []
    for i in range(5):
        # Only the final run collects metrics (digest matches the last
        # measurement rather than mixing contexts).
        collect = metrics_out if with_metrics and i == 4 else None
        runs.append(bench_ours(collect))
    runs = sorted(runs)
    ours = runs[2]
    spread = (runs[-1] - runs[0]) / ours if ours > 0 else 0.0
    print(f"[bench] five runs: {[round(r, 3) for r in runs]} GB/s "
          f"(spread {spread:.1%})", file=sys.stderr)
    ref = bench_reference()
    if ref is None:
        ref = RECORDED_REFERENCE_GBPS
        print(f"[bench] reference build absent; using recorded baseline "
              f"{ref} GB/s", file=sys.stderr)
    line = {
        "metric": "allreduce_algbw_2rank_64MiB_host",
        "value": round(ours, 3),
        "unit": "GB/s",
        "vs_baseline": round(ours / ref, 3),
        "spread": round(spread, 3),
        "runs": [round(r, 3) for r in runs],
        "pinned": PIN_RANKS,
    }
    if with_metrics and metrics_out:
        from gloo_tpu.utils.metrics import summarize_ops

        line["metrics"] = summarize_ops(metrics_out[0])
    print(json.dumps(line))


if __name__ == "__main__":
    main()
