"""Collective primitives for use inside shard_map / pjit SPMD code.

These mirror the host collective suite (csrc/tpucoll/collectives/) but
operate on the per-device shard inside an SPMD region, compiling to XLA
collectives that ride ICI (reference analog: the NCCL op wrappers in
gloo/nccl/nccl.h — here the "wrapper" is XLA itself, which also fuses and
schedules them).

All functions take `axis`: the mesh axis name the collective runs over.
`op` accepts "sum" | "product" | "min" | "max".

Every collective runs under a `jax.named_scope("gloo_tpu.<op>")`: the
scope lands in XLA op metadata, so a jax profiler trace of the device
plane shows which gloo_tpu collective produced each ICI op — and lines
up with the host plane's tracer spans and metrics (same op names) in one
Perfetto investigation (docs/observability.md). Named scopes cost
nothing at runtime; they only annotate the HLO.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

Axis = Union[str, Sequence[str]]


def rank(axis: Axis):
    """Position of this shard along `axis` (the device-plane 'rank')."""
    return lax.axis_index(axis)


def size(axis: Axis) -> int:
    return lax.axis_size(axis)


def allreduce(x, axis: Axis, op: str = "sum"):
    with jax.named_scope("gloo_tpu.allreduce"):
        if op == "sum":
            return lax.psum(x, axis)
        if op == "max":
            return lax.pmax(x, axis)
        if op == "min":
            return lax.pmin(x, axis)
        if op in ("product", "prod"):
            # No pprod primitive: gather and reduce locally. XLA turns the
            # all_gather + reduce into an efficient fused loop.
            return jnp.prod(lax.all_gather(x, axis), axis=0)
    raise ValueError(f"unknown op: {op}")


def mean(x, axis: Axis):
    with jax.named_scope("gloo_tpu.allreduce"):
        return lax.pmean(x, axis)


def reduce_scatter(x, axis: Axis, op: str = "sum", scatter_axis: int = 0):
    """Reduce across `axis` and leave each shard with its 1/P slice."""
    with jax.named_scope("gloo_tpu.reduce_scatter"):
        if op != "sum":
            # psum_scatter is sum-only; emulate others via allreduce +
            # slice.
            full = allreduce(x, axis, op)
            p = size(axis)
            idx = rank(axis)
            chunk = x.shape[scatter_axis] // p
            return lax.dynamic_slice_in_dim(full, idx * chunk, chunk,
                                            axis=scatter_axis)
        return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                                tiled=True)


def allgather(x, axis: Axis, gather_axis: int = 0, tiled: bool = True):
    """Concatenate every shard's x along `gather_axis`."""
    with jax.named_scope("gloo_tpu.allgather"):
        return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def alltoall(x, axis: Axis, split_axis: int = 0, concat_axis: int = 0):
    """Scatter `split_axis` across the group and gather along `concat_axis`."""
    with jax.named_scope("gloo_tpu.alltoall"):
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ragged_alltoall(rows, send_counts, axis: Axis, out_rows: int = None):
    """Send each destination only the rows meant for it: no capacity, no
    padding on the wire. The counts are exchanged first, then the rows.

    rows: (R, ...) grouped by destination along `axis`, and within a
    destination by slot (an expert, say); rows past the counts' sum are
    not sent. send_counts: (n, s) integer rows for each destination's s
    slots. Returns (received, recv_counts): `received` is (out_rows, ...),
    slot-major with the sources in axis order inside a slot, zero past
    the rows that came; recv_counts[i, l] is the rows from source i for
    slot l. out_rows defaults to n * R, the most that can arrive.
    Differentiable: the gradient is the reverse exchange."""
    n = size(axis)
    out_rows = n * rows.shape[0] if out_rows is None else out_rows
    with jax.named_scope("gloo_tpu.ragged_alltoall"):
        counts = _all_counts(send_counts, axis)
        me = rank(axis)
        sent = _flat_starts(counts[me]).reshape(-1)
        land = _slot_starts(counts) + _source_starts(counts)
        recv = counts[:, me]
        received = _ragged_exchange(rows, out_rows, sent,
                                    counts[me].reshape(-1),
                                    land[me].reshape(-1), recv.reshape(-1),
                                    axis)
        return received, recv


def ragged_alltoall_reverse(received, send_counts, axis: Axis,
                            out_rows: int):
    """The inverse of `ragged_alltoall(rows, send_counts, axis)`: every
    received row goes back to where it came from. `received` is laid out
    as that call returned it and `send_counts` is what the caller gave it;
    returns (out_rows, ...) in the original senders' row order, zero past
    the rows they sent."""
    with jax.named_scope("gloo_tpu.ragged_alltoall"):
        counts = _all_counts(send_counts, axis)
        me = rank(axis)
        land = _slot_starts(counts) + _source_starts(counts)
        back = jax.vmap(_flat_starts)(counts)           # (src, dst, slot)
        return _ragged_exchange(received, out_rows, land[:, me].reshape(-1),
                                counts[:, me].reshape(-1),
                                back[:, me].reshape(-1),
                                counts[me].reshape(-1), axis)


def _all_counts(send_counts, axis):
    """(sources, destinations, slots) int32: every chip's send counts."""
    return lax.all_gather(send_counts.astype(jnp.int32), axis)


def _flat_starts(counts):
    """Exclusive running sum over (destination, slot), row-major."""
    flat = counts.reshape(-1)
    return (jnp.cumsum(flat) - flat).reshape(counts.shape)


def _slot_starts(counts):
    """(dst, slot): where each slot's rows begin on each receiver, the
    slots in order, every source's rows for a slot together."""
    per_slot = counts.sum(axis=0)                       # (dst, slot)
    return (jnp.cumsum(per_slot, axis=1) - per_slot)[None]


def _source_starts(counts):
    """(src, dst, slot): each source's place inside a slot, by axis
    position."""
    return jnp.cumsum(counts, axis=0) - counts


def _ragged_exchange(operand, out_rows, input_offsets, send_sizes,
                     output_offsets, recv_sizes, axis):
    """`lax.ragged_all_to_all` into zeros where the backend has the op;
    elsewhere (XLA:CPU has none) an exact emulation over `all_to_all`."""
    out = jnp.zeros((out_rows,) + operand.shape[1:], operand.dtype)
    args = [x.astype(jnp.int32) for x in (input_offsets, send_sizes,
                                          output_offsets, recv_sizes)]
    if jax.default_backend() != "cpu":
        if axis in jax.typeof(operand).vma:
            # The op's result takes `out`'s type: varying, as the rows are,
            # or AD would all-reduce its cotangent across the axis.
            out = lax.pcast(out, axis, to="varying")
        return lax.ragged_all_to_all(operand, out, *args, axis_name=axis)
    input_offsets, send_sizes, output_offsets, recv_sizes = args
    # Every slice padded to the whole operand, sent whole; the receiver
    # writes the rows that count where the sender said they go.
    pad = operand.shape[0]
    row = jnp.arange(pad, dtype=jnp.int32)
    keep = row[None] < send_sizes[:, None]
    idx = jnp.where(keep, input_offsets[:, None] + row[None], 0)
    blocks = jnp.where(keep.reshape(keep.shape + (1,) * (operand.ndim - 1)),
                       operand[idx], 0)
    blocks = lax.all_to_all(blocks, axis, 0, 0, tiled=True)
    where = lax.all_to_all(output_offsets, axis, 0, 0, tiled=True)
    dest = jnp.where(row[None] < recv_sizes[:, None],
                     where[:, None] + row[None], out_rows)
    return out.at[dest.reshape(-1)].add(
        blocks.reshape((-1,) + operand.shape[1:]), mode="drop")


def broadcast(x, axis: Axis, root: int = 0):
    """Every shard receives the root shard's value."""
    with jax.named_scope("gloo_tpu.broadcast"):
        idx = rank(axis)
        zeros = jnp.zeros_like(x)
        return lax.psum(jnp.where(idx == root, x, zeros), axis)


def reduce(x, axis: Axis, root: int = 0, op: str = "sum"):
    """Full reduction; non-root shards receive zeros (XLA has no rooted
    reduce — the collective cost is the same on ICI, matching psum)."""
    full = allreduce(x, axis, op)
    idx = rank(axis)
    return jnp.where(idx == root, full, jnp.zeros_like(full))


def scatter(x, axis: Axis, root: int = 0, scatter_axis: int = 0):
    """Root's x is split into P slices; shard i receives slice i."""
    rooted = broadcast(x, axis, root)
    p = size(axis)
    idx = rank(axis)
    chunk = x.shape[scatter_axis] // p
    return lax.dynamic_slice_in_dim(rooted, idx * chunk, chunk,
                                    axis=scatter_axis)


def ppermute(x, axis: Axis, perm: Sequence[tuple]):
    """Point-to-point shift: pairs of (source_rank, dest_rank)."""
    with jax.named_scope("gloo_tpu.ppermute"):
        return lax.ppermute(x, axis, perm=perm)


def shift(x, axis: Axis, offset: int = 1, wrap: bool = True):
    """Send each shard to rank + offset (ring neighbor exchange); the
    building block for pipeline stages and ring attention."""
    p = size(axis)
    if wrap:
        perm = [(i, (i + offset) % p) for i in range(p)]
    else:
        perm = [(i, i + offset) for i in range(p)
                if 0 <= i + offset < p]
    with jax.named_scope("gloo_tpu.ppermute"):
        return lax.ppermute(x, axis, perm=perm)


def barrier(axis: Axis):
    """Synchronization point: returns a token-like scalar whose value
    depends on every participant (XLA cannot elide or reorder it past uses
    that consume the result)."""
    with jax.named_scope("gloo_tpu.barrier"):
        return lax.psum(jnp.ones((), dtype=jnp.int32), axis)
