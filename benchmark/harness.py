"""Runs one cell: set-up, the measured window, the traced window, the
comparison with the reference, and the result's line.

Everything that belongs to one configuration, traffic mix, recipe or
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives:

- `benchmark/configs/<config>.json`: sizes, optimizer, `family`;
- `benchmark/references/<family>.py`: the weights from the seed and the
  plain reference; `benchmark/flops/<family>.py`: FLOPs and bytes;
- `benchmark/traffic/<traffic>.json`: the generator's parameters;
- `benchmark/workloads/<cell>.json`: the recipe and the limits;
- `benchmark/recipes/<recipe>.py`: builds the system under test;
- `benchmark/metrics/<metric>.py`: `read(run)`, one per-layer metric.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark import check, generate

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 3.0


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, loaded from its path."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> SimpleNamespace:
    """The cell `name` of BENCHMARK.json with every file it names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return SimpleNamespace(
        name=name, chips=entry["chips"],
        config=_json("configs", entry["config"] + ".json"),
        traffic=_json("traffic", entry["traffic"] + ".json"),
        workload=_json("workloads", name + ".json"),
        end_to_end=e2e, per_layer=per_layer)


def _window(step, state, batches, first: int, seconds: float):
    """The closed loop with one step in flight: dispatch step i + 1, then
    block on step i's loss. Step time i is completion(i) -
    completion(i - 1); the window ends at the first completion past
    `seconds`. The step still in flight is drained and not counted. The
    host spans label the device's idle gaps in a traced run."""
    from jax.profiler import TraceAnnotation as span

    params, opt_state = state
    n = len(batches)
    i = first
    times, losses = [], []
    t_start = prev = time.perf_counter()
    with span("bench.dispatch"):
        params, opt_state, pending = step(params, opt_state, batches[i % n])
    while True:
        i += 1
        with span("bench.select_batch"):
            batch = batches[i % n]
        with span("bench.dispatch"):
            params, opt_state, loss = step(params, opt_state, batch)
        with span("bench.wait_loss"):
            pending.block_until_ready()
        now = time.perf_counter()
        times.append(now - prev)
        losses.append(pending)
        prev, pending = now, loss
        if now - t_start >= seconds:
            break
    pending.block_until_ready()
    return (params, opt_state), SimpleNamespace(
        step_s=np.asarray(times), seconds=prev - t_start,
        losses=[float(x) for x in losses])


def build(cell, devices) -> SimpleNamespace:
    """The system under test on a `data` mesh of `devices`, with the
    configuration's reference and FLOP modules."""
    from jax.sharding import Mesh

    cfg = cell.config
    mesh = Mesh(np.asarray(devices), ("data",))
    return SimpleNamespace(
        cell=cell, devices=devices,
        system=module("recipes", cell.workload["recipe"]).build(cfg, mesh),
        ref=module("references", cfg["family"]),
        flops=module("flops", cfg["family"]))


def set_up(b, seed: int) -> SimpleNamespace:
    """Data, weights and state on the device from the seed, then steps 1-3
    through the window's own call and feed, on rows that all differ, with
    what the reference follows read off them, and step 4 on the ring's
    last batch. Returns the state the window continues from."""
    import jax

    cfg, system, ref = b.cell.config, b.system, b.ref
    s = seeded_tokens(b, seed)
    words, tokens = s.words, s.tokens
    batches = [jax.device_put((tokens[i, :, :-1], tokens[i, :, 1:]),
                              system.batch_sharding)
               for i in range(tokens.shape[0])]
    params = jax.jit(functools.partial(ref.init_params, cfg),
                     out_shardings=system.param_sharding)(words)
    opt_state = system.init_opt(params)
    norms = jax.jit(ref.leaf_norms)
    change = jax.jit(lambda p, w: ref.leaf_norms(jax.tree.map(
        lambda x, y: x - y, p, ref.init_params(cfg, w))))

    step = system.step
    program = {"losses": []}
    for i in range(3):
        params, opt_state, loss = step(params, opt_state, batches[i])
        program["losses"].append(float(loss))
        if i == 0:
            program["grad_norms"] = np.asarray(norms(system.first_moment(
                opt_state))) / (1 - cfg["optimizer"]["b1"])
    program["change_norms"] = np.asarray(change(params, words))
    params, opt_state, loss = step(params, opt_state, batches[3])
    loss.block_until_ready()
    s.batches, s.state, s.program = batches, (params, opt_state), program
    return s


def seeded_tokens(b, seed: int) -> SimpleNamespace:
    """The seed's words and the traffic's token batches, made on chip 0."""
    import jax

    words = b.ref.seed_words(seed)
    with jax.default_device(b.devices[0]):
        tokens = generate.make_tokens(
            b.cell.traffic, b.cell.config["published"]["vocab_size"], words)
    return SimpleNamespace(words=words, tokens=tokens)


def reference_readings(b, s, **kw) -> dict:
    """The reference's readings on chip 0 from the same seed and tokens."""
    import jax

    kw.setdefault("chips", b.cell.chips)
    with jax.default_device(b.devices[0]):
        return b.ref.readings(
            b.cell.config, s.words, s.tokens,
            rows_per_block=b.cell.workload.get("reference_rows", 1), **kw)


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             t_process: float):
    """Set up, measure and check one cell on `devices`; returns the
    result's fields and what was compared. The caller has checked the
    devices."""
    import jax
    import jax.profiler

    from benchmark import trace as tracing

    t_build = time.perf_counter()
    b = build(cell, devices)
    t_set_up = time.perf_counter()
    s = set_up(b, seed)
    step = b.system.step
    compiled_before = step._cache_size()
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    print(f"bench: set-up {setup_s:.3f} s: start to devices "
          f"{t_build - t_process:.3f}, build {t_set_up - t_build:.3f}, "
          f"data, weights and four steps {t_window - t_set_up:.3f}",
          file=sys.stderr, flush=True)

    # ---- the measured window (or, with --trace 1, the traced one)
    state, batches = s.state, s.batches
    s.state = s.batches = None
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            state, win = _window(step, state, batches, 4,
                                 min(seconds, TRACE_SECONDS) if trace
                                 else seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        compiled_in_window = step._cache_size() - compiled_before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        del state, batches
        if trace:
            if os.environ.get("BENCH_KEEP_TRACE"):
                shutil.copytree(trace_dir, os.environ["BENCH_KEEP_TRACE"],
                                dirs_exist_ok=True)
            reduced = tracing.reduce(tracing.xplane_path(trace_dir),
                                     [d.id for d in devices])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    traffic = cell.traffic
    tokens_per_step = generate.global_rows(traffic) * traffic["seq_len"]
    metrics, out = {}, {}
    if trace:
        run = SimpleNamespace(
            trace=reduced, config=cell.config, traffic=traffic,
            chips=len(devices), flops=b.flops,
            peaks=peaks(devices[0].device_kind),
            tokens_per_step=tokens_per_step)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        for m in cell.per_layer:
            value = module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = reduced.breakdown()
    else:
        e2e = {"tokens_per_s": len(win.step_s) * tokens_per_step
               / win.seconds,
               "step_ms_p95": float(np.percentile(win.step_s, 95)) * 1e3,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # ---- the reference, once the program's state is freed
    t_ref = time.perf_counter()
    reference = reference_readings(b, s)
    print(f"bench: reference {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr, flush=True)
    found = check.gaps(s.program, reference)
    ok, checks = check.judge(found, cell.workload["limits"])
    failed = sum(not math.isfinite(x) for x in win.losses)
    result = {"correct": bool(ok and failed == 0),
              "attempted": len(win.losses), "failed": failed,
              "metrics": metrics, "device": device, **out,
              "checks": checks}
    return result, SimpleNamespace(
        program=s.program, reference=reference, gaps=found,
        compiled_in_window=compiled_in_window)


def peaks(kind: str) -> dict:
    """The peaks of `kind` from benchmark/peaks.json; no default."""
    table = _json("peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def print_result(result: dict, extra) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    print(f"bench: leaves left out {extra.gaps['leaves_left_out']}; "
          f"programs compiled inside the window "
          f"{extra.compiled_in_window}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
