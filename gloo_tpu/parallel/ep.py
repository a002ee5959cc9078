"""Expert parallelism: a dropless mixture-of-experts layer over a ragged
all-to-all.

The pattern the reference's alltoall exists to serve (SURVEY.md §2.10:
"alltoall → EP/MoE routing"), on the device plane. Each chip along the
expert axis holds a block of consecutive experts. Every token is routed
over all of the router's experts; each chip sends each other chip only
the rows routed to that chip's experts, counts first and rows after, so
no capacity is reserved and no token is dropped. The chip runs its own
experts as grouped matmuls over the rows it received and sends the
results back, where they are summed into token order with their routing
weights. Assignments to experts that no chip on the axis holds add
nothing: a chip of a larger deployment computes its experts' part.

Static shapes: a chip's row buffers are sized for the worst case (every
token's top-k on one chip's experts), and the grouped matmul's cost
follows the rows that came, not the buffer. Which rows count is kept in
token space, never applied as a pass over a buffer: an assignment to an
expert no chip on the axis holds has weight 0 and is selected away
where the combine and the sort's transpose gather its row, so whatever
the buffers hold past the rows that count reaches neither the output
nor any gradient.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from gloo_tpu.tpu import spmd


def moe(x, router, w_gate, w_up, w_down, *, first_expert, top_k: int,
        axis=None, scale: float = 1.0):
    """DeepSeek's routed experts: softmax over every expert, greedy top-k,
    the unnormalised top-k scores times `scale` as weights, SwiGLU experts.

    Per chip (inside shard_map when `axis` is given):
      x: (T, D) this chip's tokens;
      router: (D, G) f32, the whole router, the same on every chip;
      w_gate, w_up: (E, D, F) and w_down: (E, F, D), this chip's experts,
        global ids first_expert .. first_expert + E - 1; the chips along
        `axis` hold consecutive blocks of E experts.
    Returns (y, probs, top): y (T, D) in x's dtype, the routed experts'
    weighted sum; probs (T, G) f32 the router's softmax; top (T, k) the
    experts chosen. With no axis, or a one-chip one, nothing is
    exchanged."""
    t = x.shape[0]
    held = w_gate.shape[0]
    chips = 1 if axis is None else spmd.size(axis)
    with jax.named_scope("gloo_tpu.ep.route"):
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weight, top = lax.top_k(probs, top_k)
        first = first_expert - (spmd.rank(axis) * held if chips > 1 else 0)
        slot = top - first                       # expert's place on the axis
        mine = (slot >= 0) & (slot < chips * held)
        key = jnp.where(mine, slot, chips * held).reshape(-1)
        order = jnp.argsort(key, stable=True)    # by chip, then by expert
        inverse = jnp.argsort(order).reshape(t, top_k)
        counts = jnp.sum(key[:, None] == jnp.arange(chips * held), axis=0,
                         dtype=jnp.int32)
        w = jnp.where(mine, weight * scale, 0.0)
        # Rows past counts.sum() are other experts' tokens: sent nowhere,
        # covered by no group, and never gathered back (see `_combine`).
        rows = _sort_rows(x, order, inverse, mine)
    if chips == 1:
        received, groups = rows, counts
    else:
        with jax.named_scope("gloo_tpu.ep.dispatch"):
            received, recv = spmd.ragged_alltoall(
                rows, counts.reshape(chips, held), axis,
                out_rows=chips * t * min(top_k, held))
            groups = recv.sum(axis=0)
    with jax.named_scope("gloo_tpu.ep.experts"):
        # Gate and up as one grouped matmul: the rows are read once, and
        # their cotangent leaves one kernel rather than as a sum of two.
        dtype = x.dtype
        gate_up = lax.ragged_dot(
            received, jnp.concatenate([w_gate, w_up], axis=-1).astype(dtype),
            groups)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(dtype),
                             groups)
    with jax.named_scope("gloo_tpu.ep.combine"):
        if chips > 1:
            out = spmd.ragged_alltoall_reverse(
                out, counts.reshape(chips, held), axis, t * top_k)
        y = _combine(out, w, order, inverse, mine)
    return y, probs, top


# `lax.ragged_dot` leaves the rows that no group covers zero on the CPU
# (XLA's expansion masks them). On a TPU v5e it leaves them as it found
# the buffer, NaN and values near f32's largest among them, in its output
# and in its transpose for the rows; its transpose for the weights
# contracts only over the rows its groups cover. `_sort_rows`'s transpose
# and `_combine` therefore read a sorted row only where `mine` says its
# assignment is held on the axis, and select the others away inside the
# gather, never multiply them by a weight of 0.


@jax.custom_vjp
def _sort_rows(x, order, inverse, mine):
    """Row p is token order[p] // k's, k = mine.shape[1]: a gather whose
    transpose is a k-way gather-accumulate (by the inverse permutation),
    not a scatter-add."""
    return x[order // mine.shape[1]]


def _sort_rows_fwd(x, order, inverse, mine):
    return _sort_rows(x, order, inverse, mine), (inverse, mine)


def _sort_rows_bwd(res, g):
    """dx[t] = Σ_j g[inverse[t, j]] over the held assignments, in f32."""
    inverse, mine = res
    dx = sum(jnp.where(mine[:, j, None],
                       g[inverse[:, j]].astype(jnp.float32), 0.0)
             for j in range(mine.shape[1]))
    return dx.astype(g.dtype), None, None, None


_sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


@jax.custom_vjp
def _combine(out, w, order, inverse, mine):
    """y[t] = Σ_j w[t, j] out[inverse[t, j]] over the held assignments
    (w is 0 at the others), accumulated in f32 and given in out's dtype:
    the rows are gathered back into token order k at a time, never as a
    (T, k, D) tensor."""
    y = sum(jnp.where(mine[:, j, None],
                      w[:, j, None] * out[inverse[:, j]].astype(jnp.float32),
                      0.0)
            for j in range(w.shape[1]))
    # Kept out of its consumer's fusion: merged into it, XLA widens each
    # gathered row to f32 in a pass of its own.
    return lax.optimization_barrier(y.astype(out.dtype))


def _combine_fwd(out, w, order, inverse, mine):
    return _combine(out, w, order, inverse, mine), (out, w, order, inverse,
                                                    mine)


def _combine_bwd(res, dy):
    """In the rows' sorted order, dy's rows gathered there once: d out[p] =
    w(p) dy[order[p] // k] in the rows' dtype (0 past the held rows, whose
    weight is 0) and d w(p) = <out[p], dy[order[p] // k]> in f32. The
    weights move between the two orders by sorting on the permutation,
    not by gathers of single elements."""
    out, w, order, inverse, mine = res
    k = w.shape[1]
    dy_rows = dy[order // k].astype(jnp.float32)
    w_sorted = lax.sort((inverse.reshape(-1), w.reshape(-1)), num_keys=1)[1]
    d_out = (w_sorted[:, None] * dy_rows).astype(out.dtype)
    d_w = lax.sort((order, jnp.sum(out.astype(jnp.float32) * dy_rows, -1)),
                   num_keys=1)[1].reshape(w.shape)
    return d_out, jnp.where(mine, d_w, 0.0), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)

