"""Pipeline parallelism over the device mesh.

Two schedules built from gloo_tpu collectives, both one compiled XLA
program with static control flow (`lax.scan` over ticks, `spmd.shift`
ppermutes over ICI):

- `pipeline_apply`: GPipe-style forward pipeline. S + M - 1 ticks; at
  tick t, stage s computes microbatch t - s.
- `pipeline_train_1f1b`: the 1F1B training schedule (one-forward-
  one-backward; the non-interleaved PipeDream-flush/Megatron schedule).
  Each stage runs min(S-1-s, M) warmup forwards, then strictly
  alternates forward/backward, then drains. The point of 1F1B over a
  GPipe-style all-forwards-then-all-backwards training schedule is the
  activation footprint: a stage stashes at most S in-flight microbatch
  inputs instead of all M — every buffer here has static leading
  dimension S, independent of M.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from gloo_tpu.tpu import spmd


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis: str):
    """Run a pipeline of `stage_fn` across the mesh axis.

    Call inside shard_map. Per-device arguments:
      stage_params: this device's stage weights (stage s on position s);
      x_microbatches: (M, ...) microbatches, only meaningful on stage 0
        (other stages may pass zeros of the same shape).
    Returns (M, ...) outputs, meaningful on the LAST stage.

    stage_fn(params, x) -> y must be shape-preserving across stages (equal
    widths) so activations can rotate; pad stages to a common width
    otherwise.
    """
    stages = spmd.size(axis)
    my_stage = spmd.rank(axis)
    m = x_microbatches.shape[0]
    ticks = stages + m - 1

    def tick(carry, t):
        inflight, outputs = carry
        # Which microbatch does stage 0 inject this tick?
        feed_idx = jnp.clip(t, 0, m - 1)
        injected = x_microbatches[feed_idx]
        incoming = jnp.where(my_stage == 0, injected, inflight)

        computed = stage_fn(stage_params, incoming)
        # Stages outside their active window pass zeros along; harmless
        # because their results are never recorded.
        active = jnp.logical_and(t - my_stage >= 0, t - my_stage < m)
        computed = jnp.where(active, computed, jnp.zeros_like(computed))

        # Record finished microbatch t - (stages - 1) on the last stage.
        done_idx = jnp.clip(t - (stages - 1), 0, m - 1)
        record = jnp.logical_and(my_stage == stages - 1,
                                 jnp.logical_and(t >= stages - 1,
                                                 t - (stages - 1) < m))
        outputs = jnp.where(
            record,
            outputs.at[done_idx].set(computed),
            outputs)

        # Rotate activations to the next stage.
        with jax.named_scope("gloo_tpu.pp.stage_shift"):
            nxt = spmd.shift(computed, axis, 1)
        return (nxt, outputs), None

    # pcast: the carry becomes device-varying after the first tick; fresh
    # zeros must be pre-marked to keep scan carry types stable under
    # shard_map's vma checking.
    inflight0 = lax.pcast(jnp.zeros_like(x_microbatches[0]), (axis,),
                          to="varying")
    outputs0 = lax.pcast(jnp.zeros_like(x_microbatches), (axis,),
                         to="varying")
    (_, outputs), _ = lax.scan(tick, (inflight0, outputs0),
                               jnp.arange(ticks))
    return outputs


def _build_1f1b_tables(stages: int, m: int):
    """Event-driven simulation of the non-interleaved 1F1B timetable.

    Returns (fwd, bwd): int32 arrays [T, S]; entry = the microbatch that
    stage s forwards/backwards at tick t, or -1. Policy per stage: run
    min(S-1-s, M) warmup forwards, then alternate forward/backward
    starting with a forward (the "1F1B" steady state), stalling on data
    dependencies (an op's input must have been produced at an EARLIER
    tick — the inter-tick ppermute is the only transport). With M >= S
    this reproduces the classic 2(M + S - 1)-tick timeline.
    """
    warm = [min(stages - 1 - s, m) for s in range(stages)]
    f_done = [[-1] * m for _ in range(stages)]  # tick F(s,i) completed
    b_done = [[-1] * m for _ in range(stages)]
    fc = [0] * stages  # forwards issued per stage
    bc = [0] * stages  # backwards issued per stage
    fwd_rows, bwd_rows = [], []
    t = 0
    limit = 4 * (m + stages) + 8  # any valid schedule is far shorter
    while any(b < m for b in bc):
        assert t < limit, "1F1B table simulation failed to converge"
        row_f, row_b = [-1] * stages, [-1] * stages
        for s in range(stages):
            i_f, i_b = fc[s], bc[s]
            # Completion times are recorded AFTER the per-stage loop, so
            # a recorded tick is always < t: "produced at an earlier
            # tick" is exactly "!= -1" here.
            can_f = i_f < m and (s == 0 or f_done[s - 1][i_f] != -1)
            can_b = i_b < m and f_done[s][i_b] != -1 and (
                s == stages - 1 or b_done[s + 1][i_b] != -1)
            if fc[s] < warm[s]:
                turn = "f"  # warmup
            elif fc[s] < m and (fc[s] - warm[s]) == bc[s]:
                turn = "f"  # steady state: forward's turn
            else:
                turn = "b"
            if turn == "f" and can_f:
                row_f[s] = i_f
            elif turn == "b" and can_b:
                row_b[s] = i_b
            # else: stall this tick (dependency bubble)
        for s in range(stages):
            if row_f[s] >= 0:
                f_done[s][row_f[s]] = t
                fc[s] += 1
            if row_b[s] >= 0:
                b_done[s][row_b[s]] = t
                bc[s] += 1
        fwd_rows.append(row_f)
        bwd_rows.append(row_b)
        t += 1
    return (np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32))


def pipeline_train_1f1b(stage_fn: Callable, loss_fn: Callable,
                        stage_params, x_microbatches, y_microbatches,
                        axis: str):
    """One 1F1B training step across the mesh axis. Call inside
    shard_map.

    Per-device arguments:
      stage_params: this device's stage weights (stage s on position s);
      x_microbatches: (M, ...) inputs, meaningful on stage 0;
      y_microbatches: (M, ...) targets, meaningful on the LAST stage.

    stage_fn(params, x) -> y must be shape-preserving across stages;
    loss_fn(y, target) -> scalar is applied by the last stage. Returns
    (grads, loss_sum): grads is this device's stage-parameter gradient
    SUMMED over microbatches (scale by 1/M for the mean); loss_sum is
    the summed loss, nonzero on the last stage (psum it to broadcast).

    Memory: the input stash and both receive rings have static leading
    dimension S — the 1F1B bound of at most S in-flight microbatches
    per stage (a GPipe-style training schedule would stash all M).
    XLA cost note: ticks are SPMD-uniform, so each tick computes a
    masked forward AND a masked backward (selected, not branched);
    schedule wins here are memory and the comm pattern, not flop count.
    """
    stages = spmd.size(axis)
    my_stage = spmd.rank(axis)
    m = x_microbatches.shape[0]
    fwd_np, bwd_np = _build_1f1b_tables(stages, m)
    fwd_tbl = jnp.asarray(fwd_np)
    bwd_tbl = jnp.asarray(bwd_np)
    ticks = fwd_np.shape[0]
    is_last = my_stage == stages - 1

    x0 = jnp.zeros_like(x_microbatches[0])

    def tick(carry, t):
        x_stash, a_recv, g_recv, grad_acc, loss_acc = carry
        f_mb = fwd_tbl[t, my_stage]
        b_mb = bwd_tbl[t, my_stage]
        do_f = f_mb >= 0
        do_b = b_mb >= 0
        f_slot = jnp.clip(f_mb, 0, m - 1) % stages
        b_idx = jnp.clip(b_mb, 0, m - 1)
        b_slot = b_idx % stages

        # ---- forward ----
        x_in = jnp.where(my_stage == 0,
                         x_microbatches[jnp.clip(f_mb, 0, m - 1)],
                         a_recv[f_slot])
        y_out = stage_fn(stage_params, x_in)
        x_stash = jnp.where(do_f, x_stash.at[f_slot].set(x_in), x_stash)

        # ---- backward ----
        # One stage_fn transpose, seeded per identity: the last stage
        # seeds from the loss gradient, others from the received
        # cotangent (SPMD ticks are uniform across devices, so the seed
        # is a select, not a branch).
        xb = x_stash[b_slot]
        yb = y_microbatches[b_idx]
        y_b, vjp_fn = jax.vjp(stage_fn, stage_params, xb)
        loss_val, dldy = jax.value_and_grad(loss_fn)(y_b, yb)
        ct = jnp.where(is_last, dldy, g_recv[b_slot])
        gp, gx = vjp_fn(ct)
        grad_acc = jax.tree.map(
            lambda acc, g: acc + jnp.where(do_b, g, 0), grad_acc, gp)
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(do_b, is_last), loss_val, 0.0)

        # ---- communication (the inter-tick transport) ----
        with jax.named_scope("gloo_tpu.pp.fwd_shift"):
            sent_f = spmd.shift(
                jnp.where(do_f, y_out, jnp.zeros_like(y_out)), axis, 1)
        left_f = fwd_tbl[t, (my_stage - 1) % stages]
        take_f = jnp.logical_and(my_stage > 0, left_f >= 0)
        a_recv = jnp.where(
            take_f,
            a_recv.at[jnp.clip(left_f, 0, m - 1) % stages].set(sent_f),
            a_recv)
        with jax.named_scope("gloo_tpu.pp.bwd_shift"):
            sent_b = spmd.shift(jnp.where(do_b, gx, jnp.zeros_like(gx)),
                                axis, -1)
        right_b = bwd_tbl[t, (my_stage + 1) % stages]
        take_b = jnp.logical_and(my_stage < stages - 1, right_b >= 0)
        g_recv = jnp.where(
            take_b,
            g_recv.at[jnp.clip(right_b, 0, m - 1) % stages].set(sent_b),
            g_recv)

        return (x_stash, a_recv, g_recv, grad_acc, loss_acc), None

    def dev_varying(x):
        # Idempotent: zeros_like of the (already device-varying) stage
        # params is born varying; only fresh replicated zeros need the
        # cast for stable scan carry types under shard_map vma checking.
        if axis in jax.typeof(x).vma:
            return x
        return lax.pcast(x, (axis,), to="varying")

    stash0 = dev_varying(jnp.zeros((stages,) + x0.shape, x0.dtype))
    grad0 = jax.tree.map(
        lambda p: dev_varying(jnp.zeros_like(p)), stage_params)
    carry0 = (stash0, stash0, stash0, grad0,
              dev_varying(jnp.zeros((), jnp.float32)))
    (_, _, _, grads, loss_sum), _ = lax.scan(tick, carry0,
                                             jnp.arange(ticks))
    return grads, loss_sum
