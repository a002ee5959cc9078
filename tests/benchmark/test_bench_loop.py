"""The step loop and the result's line, driven on virtual CPU devices by a
test-only call into the harness (no command-line option reaches it). A
CPU run gives the host-clock metrics only: no device metric is computed
here."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import bench_tiny
from benchmark import harness

ROOT = bench_tiny.ROOT


class _Loss:
    """A loss whose completion is a clock reading: block_until_ready
    advances the fake clock to the step's completion time."""

    def __init__(self, clock, done):
        self.clock, self.done = clock, done

    def block_until_ready(self):
        self.clock.now = max(self.clock.now, self.done)
        return self

    def __float__(self):
        return 1.0


def test_window_times_steps_by_completion(monkeypatch):
    class Clock:
        now = 100.0

    clock = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock.now)
    durations = iter([0.5, 0.25, 0.25, 1.0, 0.5, 0.5, 0.5, 0.5])
    last = [clock.now]

    def step(params, opt_state, batch):
        last[0] += next(durations)  # the device runs the steps in order
        return params, opt_state, _Loss(clock, last[0])

    _, win = harness._window(step, (0, 0), [0, 1, 2, 3], 4, seconds=2.0)
    # Completions at 100.5, 100.75, 101.0, 102.0: the window closes at the
    # first one 2 s past its start; the fifth step, in flight, is drained
    # and not counted.
    np.testing.assert_allclose(win.step_s, [0.5, 0.25, 0.25, 1.0])
    assert win.seconds == pytest.approx(2.0)
    assert len(win.losses) == 4


def test_run_cell_on_four_virtual_devices():
    c = bench_tiny.cell("gpt2s-dp4-b2")
    res, extra = bench_tiny.run(c, jax.devices()[:4])
    assert res["correct"], res["checks"]
    assert extra.compiled_in_window == 0
    m = res["metrics"]
    assert set(m) == {"tokens_per_s", "step_ms_p95", "setup_s"}
    assert m["tokens_per_s"]["unit"] == "tokens/s"
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_norm_gap",
                                  "change_norm_gap"}
    json.dumps(res)


def _copy_of_benchmark_alone(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_command_refuses_without_a_tpu(where, tmp_path):
    cwd = ROOT if where == "checkout" else _copy_of_benchmark_alone(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2s-dp1-b8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
