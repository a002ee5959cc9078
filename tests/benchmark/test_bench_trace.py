"""The reduction from trace to metrics: a trace drawn by hand with known
busy, idle, kernel and exposed-collective times, and a trace recorded on
four v5e chips (`gpt2s-dp4-b2`, three step runs on chips 0 and 1),
checked against an independent sweep over its events."""

import gzip
import os
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401 - puts the checkout on sys.path
from benchmark import harness, trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_dp4_3steps.json.gz")

FLASH_F = "jvp_jit_flash_attention__.3 custom-call (bf16[2],f32[2])"
FLASH_B = "flash_attention_bwd_fused.2 custom-call (f32[2],f32[2])"


def _drawn():
    steps = [("jit_step(1)", 0, 100), ("jit_step(1)", 100, 200),
             ("jit_step(1)", 200, 300)]
    ops = [("fusion.1 fusion f32[2]", 100, 140), (FLASH_F, 140, 160),
           ("all-reduce.1 all-reduce f32[4]", 170, 190),
           ("copy.1 copy f32[1]", 200, 250), (FLASH_B, 260, 290),
           ("fusion.0 fusion f32[2]", 50, 90)]  # before the window
    async_ops = [("all-reduce-start.2 all-reduce-start f32[8]", 220, 280)]
    host = [("bench.wait_loss", 155, 205)]
    return trace.Trace(ops={0: ops}, async_ops={0: async_ops},
                       steps={0: steps}, host=host, chips=[0])


def test_drawn_trace_times():
    t = _drawn()
    # The first step run is left out: the window is [100, 300), 2 steps.
    assert t.window(0) == (100, 300, 2)
    assert t.busy_ns(0) == 40 + 20 + 20 + 50 + 30
    assert t.op_ns(0, trace.is_flash) == (20 + 30, 2)
    assert t.op_ns(0, trace.is_all_reduce) == (20 + 60, 2)
    # all-reduce covers [170, 190) and [220, 280); other ops run in
    # [220, 250) and [260, 280) of it.
    assert t.exposed_ns(0, trace.is_all_reduce) == (80 - 50, 2)
    gaps = t.breakdown()["idle_gaps"]
    assert sorted(gaps) == sorted([["bench.wait_loss", 10e-9]] * 2
                                  + [["no bench span", 10e-9]] * 2)
    assert t.breakdown()["device_ops"][0] == ["copy.1 copy f32[1]", 50e-9]


def test_op_names_from_hlo_text():
    assert trace.op_name(
        "%copy.466 = f32[96,1024,1]{2,1,0:T(8,128)} copy(f32[96,1024,1]"
        "{2,1,0:T(8,128)} %jvp_jit_flash_attention__.160)") == \
        "copy.466 copy f32[96,1024,1]"
    name = trace.op_name(
        "%flash_attention_bwd_fused.25 = (f32[96,1024,64]{2,1,0:T(8,128)},"
        " f32[96,1024,64]{2,1,0:T(8,128)}) custom-call(bf16[96,1024,64]"
        "{2,1,0:T(8,128)(2,1)} %bitcast.1266)")
    assert trace.is_flash(name) and not trace.is_all_reduce(name)
    assert not trace.is_flash("copy.466 copy f32[96,1024,1]")


def _run(t, cell_name="gpt2s-dp4-b2"):
    c = harness.load_cell(cell_name)
    return SimpleNamespace(
        trace=t, config=c.config, traffic=c.traffic, chips=c.chips,
        flops=harness.module("flops", c.config["family"]),
        peaks=harness.peaks("TPU v5 lite"),
        tokens_per_step=8 * 1024)


def test_metric_readers_on_the_drawn_trace():
    run = _run(_drawn())
    read = {m: harness.module("metrics", m).read(run) for m in (
        "mfu_pct", "device_idle_pct", "flash_attn_roofline", "grad_sync_ms",
        "grad_sync_exposed_ms")}
    assert read["device_idle_pct"] == pytest.approx(100 * 40 / 200)
    assert read["grad_sync_ms"] == pytest.approx(40 / 1e6)
    assert read["grad_sync_exposed_ms"] == pytest.approx(15 / 1e6)
    flops = run.flops.flops_per_token(run.config, 1024) * 8 * 1024 * 2
    assert read["mfu_pct"] == pytest.approx(
        100 * flops / 200e-9 / (4 * 1.97e14))
    need = run.flops.flash_attention(run.config, 2, 1024)
    least = max(need["flops"] / 1.97e14, need["bytes"] / 8.19e11)
    assert read["flash_attn_roofline"] == pytest.approx(
        100 * least / 25e-9)


def test_readers_return_nothing_without_their_ops():
    t = _drawn()
    t.ops[0] = [op for op in t.ops[0] if "fusion" in op[0]]
    t.async_ops[0] = []
    run = _run(t)
    for m in ("flash_attn_roofline", "grad_sync_ms", "grad_sync_exposed_ms"):
        assert harness.module("metrics", m).read(run) is None


def _sweep(intervals, lo, hi):
    """Covered length by an event sweep: +1 at a start, -1 at an end."""
    marks = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals
                      if e > lo and s < hi])
    total, depth, last = 0, 0, None
    for x, d in marks:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return trace.from_json(f.read())


def test_recorded_trace_against_a_sweep(recorded):
    t = recorded
    for chip in t.chips:
        lo, hi, n = t.window(chip)
        assert n == 2
        ops = [(s, e) for _, s, e in t.ops[chip]]
        assert t.busy_ns(chip) == _sweep(ops, lo, hi)
        flash = [(s, e) for name, s, e in t.ops[chip] if trace.is_flash(name)]
        # 12 layers, a forward and a backward kernel each, 2 steps.
        assert len([1 for s, e in flash if lo <= s < hi]) == 2 * 12 * 2
        ar = [(s, e) for name, s, e in t.ops[chip] + t.async_ops[chip]
              if trace.is_all_reduce(name)]
        other = [(s, e) for name, s, e in t.ops[chip]
                 if not trace.is_all_reduce(name)]
        both = _sweep(ar, lo, hi) + _sweep(other, lo, hi) - _sweep(
            ar + other, lo, hi)
        assert t.exposed_ns(chip, trace.is_all_reduce)[0] == \
            _sweep(ar, lo, hi) - both
        assert t.op_ns(chip, trace.is_all_reduce)[0] > 0


def test_summary_and_reduce_of_a_cpu_trace(tmp_path):
    """`summarize` lists a trace's planes for a look by hand; `reduce`
    refuses a trace with no TPU planes rather than read a CPU as a chip."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.xplane_path(str(tmp_path))
    planes = {p["plane"] for p in trace.summarize(path)["planes"]}
    assert any(p.startswith("/host:") for p in planes)
    with pytest.raises(RuntimeError, match="no device ops"):
        trace.reduce(path, [0])
