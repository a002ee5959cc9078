"""Machine-readable benchmark sweep -> BASELINE_sweep.json.

The reference's benchmark harness is built for reproducible comparison
(gloo/benchmark/runner.cc:475-516: timed iterations, percentile
summaries, one line per config). This sweep is the repo's equivalent
artifact: every cell of workload x payload x ranks x payload-plane
{plain TCP, shm, encrypted} x event engine {epoll, uring} measured with
the SAME multi-process methodology (FileStore rendezvous, one OS
process per rank — the deployment shape, not the thread harness), so
notes can cite committed JSON instead of hand-transcribed prose, and
round-over-round regressions are a `diff` away.

Usage: python tools/bench_sweep.py [--quick] [--out BASELINE_sweep.json]
Each cell records p50/p99/min latency (us), algorithm bandwidth at p50,
and iteration count, straight from tpucoll_bench --json.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "build", "tpucoll_bench")

OPS = ["allreduce", "reduce_scatter", "broadcast"]
ELEMENTS = [1024, 262144, 4194304]  # 4 KiB, 1 MiB, 16 MiB of f32
RANKS = [2, 4]
# (label, env overrides, extra argv) — the payload-plane tiers.
PLANES = [
    ("plain", {"TPUCOLL_SHM": "0"}, []),
    # Pinned to "1" so an inherited TPUCOLL_SHM=0 cannot silently turn
    # the shm cells into plain-TCP measurements labeled "shm".
    ("shm", {"TPUCOLL_SHM": "1"}, []),
    ("encrypted", {"TPUCOLL_SHM": "0"},
     ["--auth-key", "sweep-key", "--encrypt"]),
]
ENGINES = ["epoll", "uring"]


def run_cell(op, elements, ranks, plane, engine, min_time):
    """One measurement cell. Fault-isolated: a hung/crashed/garbled cell
    returns {"error": ...} instead of aborting the sweep, and its rank
    processes and rendezvous dir are always reaped."""
    label, env_over, extra = plane
    store = tempfile.mkdtemp(prefix="tcsweep-")
    env = dict(os.environ, TPUCOLL_ENGINE=engine, **env_over)
    base = [BENCH, "--size", str(ranks), "--store", f"file:{store}",
            "--op", op, "--elements", str(elements),
            "--min-time", str(min_time), "--json", *extra]
    procs = []
    try:
        procs = [subprocess.Popen(base + ["--rank", str(r)], env=env,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.DEVNULL, text=True)
                 for r in range(1, ranks)]
        out = subprocess.run(base + ["--rank", "0"], env=env,
                             capture_output=True, text=True, timeout=120)
        for p in procs:
            p.communicate(timeout=120)
        if out.returncode != 0:
            return {"error": out.stderr.strip()[-200:]}
        # A non-rank-0 worker can fail after rank 0 finishes (e.g. a
        # teardown crash); numbers from such a cell are not trustworthy.
        bad = [p for p in procs if p.returncode != 0]
        if bad:
            return {"error": f"{len(bad)} worker(s) exited non-zero: "
                             f"{[p.returncode for p in bad]}"}
        d = json.loads(out.stdout.splitlines()[0])
        return {"p50_us": d["p50_us"], "p99_us": d["p99_us"],
                "min_us": d["min_us"], "algbw_gbps": d["algbw_gbps"],
                "iters": d["iters"]}
    except subprocess.TimeoutExpired as exc:
        # Structured kind, not just prose: the rep loop branches on this
        # flag (substring-matching "Timeout" in a truncated message was
        # fragile — the type name can be cut off at the 200-char cap or
        # appear inside an unrelated worker error).
        return {"error": f"{type(exc).__name__}: {exc}"[:200],
                "timeout": True}
    except (json.JSONDecodeError, IndexError, KeyError) as exc:
        return {"error": f"{type(exc).__name__}: {exc}"[:200]}
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
        shutil.rmtree(store, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="output path (default: BASELINE_sweep.json; "
                         "--quick defaults elsewhere so smoke runs never "
                         "clobber the committed regression baseline)")
    ap.add_argument("--quick", action="store_true",
                    help="0.5s cells instead of 2s (smoke runs)")
    ap.add_argument("--reps", type=int, default=1,
                    help="repetitions per cell; >1 records the "
                         "median-p50 rep (plus every rep's p50) so a "
                         "single scheduler transient cannot fabricate a "
                         "3x regression — the r5 sweep hit exactly that")
    args = ap.parse_args()
    if args.reps < 1:
        ap.error("--reps must be >= 1")
    if args.out is None:
        args.out = ("/tmp/BASELINE_sweep_quick.json" if args.quick
                    else os.path.join(REPO, "BASELINE_sweep.json"))
    if not os.path.exists(BENCH):
        sys.exit("build/tpucoll_bench missing - run `make native` first")
    min_time = 0.5 if args.quick else 2.0

    cells = []
    t0 = time.time()
    total = len(OPS) * len(ELEMENTS) * len(RANKS) * len(PLANES) * \
        len(ENGINES)
    n = 0
    for op in OPS:
        for elements in ELEMENTS:
            for ranks in RANKS:
                for plane in PLANES:
                    for engine in ENGINES:
                        n += 1
                        runs = []
                        for _ in range(args.reps):
                            r = run_cell(op, elements, ranks, plane,
                                         engine, min_time)
                            runs.append(r)
                            if r.get("timeout"):
                                # A 120s timeout is a hang (cells run
                                # 0.5-2s), not a transient: don't burn
                                # reps x 2min on a dead config.
                                break
                        ok = [r for r in runs if "p50_us" in r]
                        if not ok:
                            res = runs[0]
                            if len(runs) > 1:
                                # All reps failed: keep every rep's
                                # error, not just the first (failure
                                # modes can differ across reps).
                                res = dict(res,
                                           rep_errors=[r.get("error")
                                                       for r in runs])
                        else:
                            # Lower median: with an even rep count the
                            # upper-middle pick would select the SLOWER
                            # rep — the transient this flag suppresses.
                            res = sorted(ok, key=lambda r: r["p50_us"])[
                                (len(ok) - 1) // 2]
                            if args.reps > 1:
                                res = dict(res,
                                           rep_p50s=[r["p50_us"]
                                                     for r in ok])
                                errs = [r["error"] for r in runs
                                        if "error" in r]
                                if errs:
                                    # Flaky cell: keep the evidence in
                                    # the artifact, not just the
                                    # surviving rep's numbers.
                                    res["rep_errors"] = errs
                        cell = {"op": op, "elements": elements,
                                "bytes": elements * 4, "ranks": ranks,
                                "plane": plane[0], "engine": engine,
                                **res}
                        cells.append(cell)
                        print(f"[{n}/{total}] {op} {elements * 4 >> 10}KiB "
                              f"P={ranks} {plane[0]}/{engine}: "
                              f"{res.get('p50_us', res)} us p50",
                              file=sys.stderr)

    doc = {
        "methodology": "multi-process (one OS process per rank), "
                       "FileStore rendezvous, tpucoll_bench --json; "
                       "p50/p99/min over timed iterations after warmup; "
                       f"min-time {min_time}s per cell; "
                       f"reps {args.reps} (lower-median-p50 rep kept)",
        "reps": args.reps,
        "host": "single shared core (+/-15% run-to-run); "
                "treat cross-cell ratios, not absolutes, as the signal",
        "timestamp_unix": int(t0),
        "cells": cells,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}: {len(cells)} cells in "
          f"{time.time() - t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
