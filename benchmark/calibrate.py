"""The readings that the limits of `correct` are set from, on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange,...] \
        [--program-only | --reference-only]

For every seed, the program's readings after the same set-up a run makes
and the reference's; their gaps are the lower readings. For each control
seed, the readings of the control (the reference at fp8, put in the
program's place) and of each planted fault (the reference with the fault,
put in the program's place), and their gaps. A state left unchanged reads
1 by the measure and needs no run. `--program-only` runs the program
alone on the cell's chips; `--reference-only` runs the reference side
alone on one chip. One JSON line per reading, raw readings included, so
that gaps can be formed across calls; the last line holds the largest
program gap and the smallest control and fault gaps.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _raw(readings: dict) -> dict:
    return {k: [float(x) for x in v] for k, v in readings.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    side = ap.add_mutually_exclusive_group()
    side.add_argument("--program-only", action="store_true")
    side.add_argument("--reference-only", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax

    from benchmark import check, harness, run

    jax.config.update("jax_compilation_cache_dir", run.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = harness.load_cell(args.workload)
    devices = run.require_chips(1 if args.reference_only else cell.chips)
    b = harness.build(cell, devices)
    seeds = [int(x) for x in args.seeds.split(",")]
    controls = [int(x) for x in args.control_seeds.split(",") if x]
    faults = [f for f in args.faults.split(",") if f]
    found = {k: [] for k in ["program", "control"] + faults}

    def emit(kind, seed, readings, ref, seconds):
        line = {"kind": kind, "seed": seed, "seconds": seconds,
                "readings": _raw(readings)}
        if ref is not None:
            gaps = check.gaps(readings, ref)
            found[kind].append(gaps)
            line.update(gaps)
        print(json.dumps(line), flush=True)

    for seed in seeds + [c for c in controls if c not in seeds]:
        t0 = time.perf_counter()
        if args.reference_only:
            s = harness.seeded_tokens(b, seed)
        else:
            s = harness.set_up(b, seed)
            s.state = s.batches = None
        t1 = time.perf_counter()
        if args.program_only:
            emit("program", seed, s.program, None, t1 - t0)
            continue
        ref = harness.reference_readings(b, s)
        t2 = time.perf_counter()
        emit("reference", seed, ref, None, t2 - t1)
        if not args.reference_only and seed in seeds:
            emit("program", seed, s.program, ref, t1 - t0)
        if seed not in controls:
            continue
        for kind, kw in [("control", {"quant": "fp8"})] + [
                (f, {"fault": f}) for f in faults]:
            t0 = time.perf_counter()
            other = harness.reference_readings(b, s, **kw)
            emit(kind, seed, other, ref, time.perf_counter() - t0)
    summary = {}
    for kind, gaps in found.items():
        if gaps:
            pick = max if kind == "program" else min
            summary[kind] = {n: pick(g[n] for g in gaps)
                             for n in check.NAMES}
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
