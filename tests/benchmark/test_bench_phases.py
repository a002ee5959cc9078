"""The phase metrics (`benchmark/phases.py`): forward_ms, backward_ms,
optimizer_ms and grad_sync_mb on a trace drawn by hand, on the trace
recorded on four v5e chips with the op_names of the step compiled for a
v5e:2x2 (`data/op_names_dp4.json.gz`: a compile is deterministic, and
the described chip's instruction names and op_names match the chip's),
and `step_stats` of a tiny cell's step compiled on four CPU devices."""

import dataclasses
import gzip
import json
import os

import jax
import numpy as np
import pytest

import bench_tiny
from benchmark import harness, phases
from gloo_tpu.tpu import CollectiveStats
from test_bench_trace import FLASH_B, FLASH_F, RECORDED, _drawn, _run

HERE = os.path.dirname(os.path.abspath(__file__))
OP_NAMES = os.path.join(HERE, "data", "op_names_dp4.json.gz")
LOSS = "jit(step)/shard_map/gloo_tpu.ddp.loss/"
OPT = "jit(step)/gloo_tpu.ddp.optimizer/"
METRICS = ("forward_ms", "backward_ms", "optimizer_ms", "grad_sync_mb")


def _read(run, *names):
    return {m: harness.module("metrics", m).read(run) for m in names}


@pytest.fixture
def counted(monkeypatch):
    """Make `step_stats` return the CollectiveStats a test sets."""
    box = {}
    monkeypatch.setattr(phases, "step_stats", lambda run: box.get("stats"))
    return box


def test_phases_of_the_drawn_trace(counted):
    counted["stats"] = CollectiveStats(
        calls={"all-reduce": 2, "all-reduce-start": 1, "all-gather": 1},
        bytes={"all-reduce": 3_000_000, "all-reduce-start": 1_000_000,
               "all-gather": 5},
        op_names={
            "fusion.1": LOSS + "jvp()/dot_general",
            FLASH_F.split(" ")[0]:
                LOSS + "jvp(jit(flash_attention))/pallas_call",
            "all-reduce.1": LOSS + "transpose(jvp())/psum_invariant",
            "copy.1": OPT + "add",
            FLASH_B.split(" ")[0]:
                LOSS + "transpose(jvp(jit(flash_attention)))/pallas_call",
            "fusion.0": OPT + "mul"})
    read = _read(_run(_drawn()), *METRICS)
    # Window [100, 300), 2 steps: forward fusion.1 40 + flash 20; backward
    # flash 30 (the all-reduces are grad_sync_ms's); optimizer copy.1 50;
    # fusion.0 lies before the window; the async all-reduce is not on the
    # one-at-a-time line.
    assert read == pytest.approx({
        "forward_ms": 60 / 2 / 1e6, "backward_ms": 30 / 2 / 1e6,
        "optimizer_ms": 50 / 2 / 1e6, "grad_sync_mb": 4.0})


def test_phases_read_nothing_without_scopes_or_counter(counted):
    run = _run(_drawn())
    assert _read(run, *METRICS) == dict.fromkeys(METRICS)
    counted["stats"] = CollectiveStats(calls={"all-gather": 1},
                                       bytes={"all-gather": 5})
    assert _read(run, *METRICS) == dict.fromkeys(METRICS)


@pytest.mark.parametrize("name,op_name,expected", [
    ("fusion.1 fusion f32[2]", LOSS + "jvp()/add", "forward"),
    ("fusion.2 fusion f32[2]", LOSS + "transpose(jvp())/dot_general",
     "backward"),
    ("fusion.3 fusion f32[2]",
     LOSS + "jvp(jit(flash_attention))/transpose", "forward"),
    ("psum_invariant.616 all-reduce f32[8]",
     LOSS + "transpose(jvp())/psum_invariant", "grad_sync"),
    ("fusion.4 fusion f32[2]", OPT + "add", "optimizer"),
    ("copy-done.5 copy-done f32[2]", "", None),
    ("div.6 multiply f32[2]", "jit(step)/shard_map/gloo_tpu.allreduce/div",
     None),
])
def test_phase_of_an_op(name, op_name, expected):
    assert phases.phase(name, op_name) == expected


@pytest.fixture(scope="module")
def recorded():
    from benchmark import trace

    with gzip.open(RECORDED, "rt") as f:
        t = trace.from_json(f.read())
    with gzip.open(OP_NAMES, "rt") as f:
        return t, json.load(f)


def test_recorded_phases_cover_the_busy_time(recorded, counted):
    """On chips 0 and 1 forward, backward, optimizer and the all-reduces
    add up to 95-100.5% of the busy time; what is left has no op_name
    (the done halves of async copies and slices)."""
    from benchmark import trace

    t, op_names = recorded
    counted["stats"] = CollectiveStats(op_names=op_names)
    for chip in t.chips:
        read = _read(_run(dataclasses.replace(t, chips=[chip])),
                     "forward_ms", "backward_ms", "optimizer_ms",
                     "grad_sync_ms")
        lo, hi, steps = t.window(chip)
        busy_ms = t.busy_ns(chip) / 1e6 / steps
        assert 0.95 <= sum(read.values()) / busy_ms <= 1.005, read
        for name, _, _ in t.ops[chip]:
            if trace.is_all_reduce(name):
                assert phases.LOSS_SCOPE + "transpose(" in \
                    op_names[name.split(" ")[0]]


def test_step_stats_of_a_tiny_cell():
    """The counter compiles the cell's own step once: every param's
    gradient crosses the all-reduces (the CPU backend reduces in f32), and
    the phases' scopes are in its op_names. The exact v5e bytes are
    `tests/test_tpu_compile.py`'s."""
    c = bench_tiny.cell("gpt2s-dp4-b2")
    run = _run(None)
    run.config, run.traffic, run.chips = c.config, c.traffic, c.chips
    stats = phases.step_stats(run)
    ref = harness.module("references", c.config["family"])
    params = jax.eval_shape(lambda w: ref.init_params(c.config, w),
                            np.zeros(2, np.uint32))
    elems = sum(x.size for x in jax.tree.leaves(params))
    assert stats.calls["all-reduce"] >= 1
    assert stats.bytes["all-reduce"] >= 4 * elems
    names = stats.op_names.values()
    assert any(phases.OPTIMIZER_SCOPE in n for n in names)
    assert any(phases.phase("x.1 fusion", n) == "forward" for n in names)
    assert phases.step_stats(run) is stats
