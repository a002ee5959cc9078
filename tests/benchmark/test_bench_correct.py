"""`correct` comes out false when the timed path is broken, and for the
control. A tiny cell on virtual CPU devices drives the rest of a run (the
harness's look for a chip is skipped) with each fault a training cell can
have planted in the step the window drives, and with the cell's own
limits. The control is the reference at fp8 put in the program's place."""

import jax
import optax
import pytest
from jax.sharding import PartitionSpec as P

import bench_tiny
from benchmark import check, harness


def _frozen(make):
    """A step that returns its state unchanged."""
    def build(loss_fn, opt, mesh, axis="data"):
        step = make(loss_fn, opt, mesh, axis)
        return jax.jit(lambda p, o, b: (p, o, step(p, o, b)[2]))
    return build


def _half_batch(make):
    """Half of each chip's rows left out, the mean taken over the rest."""
    def build(loss_fn, opt, mesh, axis="data"):
        def half(params, batch):
            n = batch[0].shape[0] // 2
            return loss_fn(params, (batch[0][:n], batch[1][:n]))
        return make(half, opt, mesh, axis)
    return build


def _no_exchange(make):
    """The exchange between chips left out: each chip keeps its own
    gradient, divided by the chip count."""
    def build(loss_fn, opt, mesh, axis="data"):
        def local(params, batch):
            loss, g = jax.value_and_grad(loss_fn)(params, batch)
            n = jax.lax.axis_size(axis)
            return (jax.lax.pmean(loss, axis),
                    jax.tree.map(lambda x: x / n, g))

        grads = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                              out_specs=(P(), P()), check_vma=False)

        @jax.jit
        def step(params, opt_state, batch):
            loss, g = grads(params, batch)
            updates, opt_state = opt.update(g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return step
    return build


def _answer_altered(make):
    """The loss altered where the step produces it, by 1%."""
    def build(loss_fn, opt, mesh, axis="data"):
        step = make(loss_fn, opt, mesh, axis)

        @jax.jit
        def altered(p, o, b):
            p, o, loss = step(p, o, b)
            return p, o, loss * 1.01
        return altered
    return build


FAULTS = {"frozen_state": _frozen, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    import gloo_tpu.parallel

    make = gloo_tpu.parallel.make_ddp_train_step
    monkeypatch.setattr(gloo_tpu.parallel, "make_ddp_train_step",
                        FAULTS[fault](make))
    c = bench_tiny.cell("gpt2s-dp4-b2")
    res, extra = bench_tiny.run(c, jax.devices()[:4])
    assert not res["correct"], (fault, extra.gaps)
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", bench_tiny.cells())
def test_sound_program_is_correct_and_control_is_not(name):
    c = bench_tiny.cell(name, rows=4)
    b = harness.build(c, jax.devices()[:c.chips])
    s = harness.set_up(b, 2**32 + 99)
    s.state = s.batches = None
    ref = harness.reference_readings(b, s)
    ok, checks = check.judge(check.gaps(s.program, ref),
                             c.workload["limits"])
    assert ok, checks
    control = harness.reference_readings(b, s, quant="fp8")
    ok, checks = check.judge(check.gaps(control, ref), c.workload["limits"])
    assert not ok, checks
