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

Static shapes: a chip's buffers are sized for the worst case (every
token's top-k on one chip's experts), and the grouped matmul's cost
follows the rows that came, not the buffer.
"""

from __future__ import annotations

import functools

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
    t, d = x.shape
    held = w_gate.shape[0]
    chips = 1 if axis is None else spmd.size(axis)
    with jax.named_scope("gloo_tpu.ep.route"):
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weight, top = lax.top_k(probs, top_k)
        first = first_expert - (spmd.rank(axis) * held if chips > 1 else 0)
        slot = top.reshape(-1) - first           # expert's place on the axis
        mine = (slot >= 0) & (slot < chips * held)
        key = jnp.where(mine, slot, chips * held)
        order = jnp.argsort(key, stable=True)    # by chip, then by expert
        inverse = jnp.argsort(order)
        counts = jnp.sum(key[:, None] == jnp.arange(chips * held), axis=0,
                         dtype=jnp.int32)
        valid = jnp.arange(t * top_k) < counts.sum()
        rows = jnp.where(valid[:, None], _sort_rows(x, order, inverse, top_k),
                         0)
    if chips == 1:
        received, groups = rows, counts
    else:
        with jax.named_scope("gloo_tpu.ep.dispatch"):
            received, recv = spmd.ragged_alltoall(
                rows, counts.reshape(chips, held), axis,
                out_rows=chips * t * min(top_k, held))
            groups = recv.sum(axis=0)
    with jax.named_scope("gloo_tpu.ep.experts"):
        dtype = x.dtype
        gate = lax.ragged_dot(received, w_gate.astype(dtype), groups)
        up = lax.ragged_dot(received, w_up.astype(dtype), groups)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(dtype),
                             groups)
    with jax.named_scope("gloo_tpu.ep.combine"):
        if chips > 1:
            out = spmd.ragged_alltoall_reverse(
                out, counts.reshape(chips, held), axis, t * top_k)
        out = jnp.where(valid[:, None], out, 0)
        back = _unsort_rows(out, order, inverse).reshape(t, top_k, d)
        w = jnp.where(mine, weight.reshape(-1) * scale, 0.0)
        y = jnp.einsum("tkd,tk->td", back.astype(jnp.float32),
                       w.reshape(t, top_k))
    return y.astype(x.dtype), probs, top


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sort_rows(x, order, inverse, k):
    """Row p is token order[p] // k's: a gather whose transpose is a
    gather too (by the inverse permutation), not a scatter-add."""
    return x[order // k]


def _sort_rows_fwd(x, order, inverse, k):
    return _sort_rows(x, order, inverse, k), (order, inverse)


def _sort_rows_bwd(k, res, g):
    order, inverse = res
    per_token = g[inverse].reshape(-1, k, g.shape[-1])
    return per_token.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, \
        None


_sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


@jax.custom_vjp
def _unsort_rows(rows, order, inverse):
    """rows[inverse]: back to (token, choice) order."""
    return rows[inverse]


def _unsort_rows_fwd(rows, order, inverse):
    return rows[inverse], (order, inverse)


def _unsort_rows_bwd(res, g):
    order, _ = res
    return g[order], None, None


_unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)
