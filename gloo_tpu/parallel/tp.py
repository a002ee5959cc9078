"""Megatron-style tensor parallelism over a mesh axis.

Built purely from gloo_tpu device-plane collectives — demonstrating that
the collective layer is sufficient to express TP, the same way users build
TP on the reference's allreduce/allgather (SURVEY.md §2.10). All functions
run inside shard_map with the weight shards as per-device values.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from gloo_tpu.tpu import spmd


def column_parallel_dense(x, w_shard, axis: str):
    """y_shard = x @ w_shard where w is split along its output dim.

    No forward communication; consumers either keep working on the output
    shard (paired with a following row-parallel layer) or allgather.
    """
    return x @ w_shard


def row_parallel_dense(x_shard, w_shard, axis: str):
    """y = sum_over_ranks(x_shard @ w_shard): w split along its input dim,
    x arriving already split (e.g. from a column-parallel layer). The psum
    is the TP allreduce on the ICI mesh."""
    partial = x_shard @ w_shard
    with jax.named_scope("gloo_tpu.tp.row_sync"):
        return spmd.allreduce(partial, axis, "sum")


def tp_mlp_block(x, w_up_shard, w_down_shard, axis: str, activation=None):
    """The canonical 2-layer TP block: column-parallel up-projection,
    nonlinearity on the shard, row-parallel down-projection (one psum per
    block, like Megatron's MLP)."""
    import jax

    act = activation if activation is not None else jax.nn.gelu
    h = column_parallel_dense(x, w_up_shard, axis)
    h = act(h)
    return row_parallel_dense(h, w_down_shard, axis)


def row_parallel_dense_scattered(x_shard, w_shard, axis: str,
                                 interpret: bool = False, mesh_axes=None):
    """Row-parallel dense with the output SCATTERED over rows (sequence
    dim) instead of replicated — and the reduce-scatter fused into the
    matmul at ring-chunk granularity (gloo_tpu.ops.matmul_reduce_scatter):
    each ICI hop flies while the MXU computes the next chunk's partial.
    The Megatron-sp pattern (row-parallel -> reduce-scatter) in one
    kernel; pair with allgather_matmul_dense for the gather side. On a
    multi-axis mesh pass mesh_axes (the Mesh's full axis order)."""
    from gloo_tpu.ops import matmul_reduce_scatter

    return matmul_reduce_scatter(x_shard, w_shard, axis,
                                 interpret=interpret, mesh_axes=mesh_axes)


def allgather_matmul_dense(x_rows_shard, w, axis: str,
                           interpret: bool = False, mesh_axes=None):
    """Column-parallel-style dense whose input rows are sequence-sharded:
    gather(x) @ w with the allgather overlapped against per-chunk matmuls
    (gloo_tpu.ops.allgather_matmul). The dual of
    row_parallel_dense_scattered — together they close the Megatron-sp
    loop with both collectives fused. On a multi-axis mesh pass
    mesh_axes (the Mesh's full axis order)."""
    from gloo_tpu.ops import allgather_matmul

    return allgather_matmul(x_rows_shard, w, axis, interpret=interpret,
                            mesh_axes=mesh_axes)


# ---------------------------------------------------------------------------
# Shape-aware fused/unfused dispatch (r5).
#
# The fused overlap kernels hide the TP collective entirely but pay a
# chunking cost on the matmul itself. An early single-chip v5e reading
# (round 4, not reproduced on the current code) had the cost track the
# kernel's shape family: near-parity with >=512-row chunks and K<=2048,
# but down to 0.68x of the plain-dot step at 256-row chunks with K=4096. Whether fusing wins therefore depends on how much
# of the unfused step the collective would cost: with ratio = fused
# compute throughput / plain-dot throughput and share = collective time
# / unfused step time, fused wins iff share > 1 - ratio. Encoding that
# rule HERE keeps a user on a single ICI domain with K-heavy shards
# from silently losing a third of their step time to an
# unconditionally-fused pair.
# ---------------------------------------------------------------------------

#: Conservative single-chip throughput of the fused kernels relative to
#: a plain dot of the same FLOPs, by shape family. Calibrated against
#: the two measured end-to-end points (0.93 at M=4096/D=F=2048 ->
#: chunk 512/K=2048; 0.68 at M=2048/D=F=4096 -> chunk 256/K=4096) and
#: the per-kernel sweeps; the slow draw of the bimodal 2048x4096 cell
#: is the one encoded (conservatism favors unfused, whose cost is
#: bounded and stable).
_FUSED_BASE_RATIO = 0.95
_SMALL_CHUNK_PENALTY = 0.85   # chunk_rows < 512
_WIDE_K_PENALTY = 0.85        # K > 2048


def fused_compute_ratio(m: int, k: int, axis_size: int) -> float:
    """Estimated fused-kernel compute throughput as a fraction of the
    plain dot's, for a per-shard [m, k] matmul on a ring of axis_size
    (ring chunks are m // axis_size rows)."""
    chunk_rows = max(1, m // max(1, axis_size))
    ratio = _FUSED_BASE_RATIO
    if chunk_rows < 512:
        ratio *= _SMALL_CHUNK_PENALTY
    if k > 2048:
        ratio *= _WIDE_K_PENALTY
    return ratio


def estimate_comm_share(m: int, k: int, cols: int, axis_size: int,
                        dtype_bytes: int = 2,
                        ici_bytes_per_s: float | None = None,
                        flops_per_s: float | None = None,
                        wire_elems: int | None = None) -> float:
    """Estimated collective share of the UNFUSED step for a per-shard
    [m, k] @ [k, cols] matmul paired with its TP collective over
    `axis_size` devices. `wire_elems` is the element count the
    collective moves: default m*cols (the [m, cols] result riding a
    reduce-scatter); the allgather side must pass its INPUT size
    instead (m*k — the gathered X), which differs whenever k != cols.

    Defaults are v5e-ish and env-tunable — TPUCOLL_TP_ICI_GBPS
    (effective per-hop ring bandwidth, default 90 GB/s: two of the four
    45 GB/s ICI links active in a bidirectional ring) and
    TPUCOLL_TP_TFLOPS (sustained matmul throughput, default 170: the
    measured plain-dot rate on v5e, not the 197 nameplate). Estimates
    feed a one-bit decision with a wide gap between the families, so
    ~30% parameter error does not flip it; re-tune on other
    generations via the env knobs.
    """
    if axis_size <= 1:
        return 0.0
    if ici_bytes_per_s is None:
        ici_bytes_per_s = float(
            os.environ.get("TPUCOLL_TP_ICI_GBPS", "90")) * 1e9
    if flops_per_s is None:
        flops_per_s = float(
            os.environ.get("TPUCOLL_TP_TFLOPS", "170")) * 1e12
    if wire_elems is None:
        wire_elems = m * cols
    wire_bytes = (wire_elems * dtype_bytes) * (axis_size - 1) / axis_size
    t_comm = wire_bytes / ici_bytes_per_s
    t_mm = (2.0 * m * k * cols) / flops_per_s
    return t_comm / (t_comm + t_mm)


def use_fused_overlap(m: int, k: int, cols: int, axis_size: int,
                      comm_share: float | None = None,
                      dtype_bytes: int = 2,
                      wire_elems: int | None = None,
                      ratio: float | None = None) -> bool:
    """The dispatch decision: fuse iff the collective's share of the
    unfused step exceeds the fused kernels' compute penalty
    (share > 1 - ratio). Pass `comm_share` directly when measured;
    otherwise it is estimated from shape + hardware parameters. Pass
    `ratio` from measure_fused_ratio() to use THIS process's measured
    compile draw instead of the shape model (the fused kernels'
    throughput was once seen bimodal across compiles on some shapes, and
    a measured slow draw should fall back to
    unfused even where the model would fuse).
    TPUCOLL_TP_OVERLAP=fused|unfused forces either way (auto/unset =
    decide); anything else raises.

    CAUTION: the env var is read at TRACE time. A jitted caller bakes
    the decision into its compiled computation, so flipping
    TPUCOLL_TP_OVERLAP after the first call has NO effect on already-
    traced shapes — re-jit the function or call jax.clear_caches() to
    make a new setting take effect."""
    mode = os.environ.get("TPUCOLL_TP_OVERLAP", "auto")
    if mode == "fused":
        return True
    if mode == "unfused":
        return False
    if mode not in ("", "auto"):
        raise ValueError(
            f"TPUCOLL_TP_OVERLAP must be fused|unfused|auto, got: {mode}")
    if comm_share is None:
        comm_share = estimate_comm_share(m, k, cols, axis_size,
                                         dtype_bytes=dtype_bytes,
                                         wire_elems=wire_elems)
    if ratio is None:
        ratio = fused_compute_ratio(m, k, axis_size)
    return comm_share > 1.0 - ratio


_PROBE_CACHE: dict = {}


def measure_fused_ratio(m: int, k: int, axis_size: int,
                        dtype=None, chain: int = 64, reps: int = 3,
                        interpret: bool = False) -> float:
    """Measure THIS process's fused-kernel compute throughput relative
    to a plain dot of the same FLOPs, on one local device via the
    self-loop virtual ring (the kernel runs its full axis_size-step
    schedule with the ICI leg replaced by on-chip DMA — identical
    compute pipeline, no other participants needed).

    Why measure instead of model: the fused kernels' throughput was
    once seen bimodal across process restarts on some shapes; the shape
    model cannot know which draw this process got, a probe can. Feed the
    result to use_fused_overlap(ratio=...) — a slow draw then falls
    back to plain dots + explicit collectives.

    Caveat on what the probe proves: it times its OWN compile of the
    self-loop kernel, not the deployed step's compile. Under the
    r4 observation (the draw is process-correlated: stable within a
    process, bimodal across restarts) that is the same draw; if the
    nondeterminism turns out to be fully per-compile
    (tools/overlap_probe.py is the committed discrimination
    experiment), the probe bounds the distribution but cannot
    guarantee the deployed kernel's draw — time the real step when
    you need certainty.

    The probe runs the square [m, k] @ [k, k] member of the shape
    family — the penalty tracked (chunk rows, K), not the output width,
    in the round-4 sweeps, and the square output chains
    back into the timing loop. Cost: one extra compile of the
    self-loop kernel (minutes for unrolled rings on TPU — comparable
    to the training step's own compile) plus ~chain*reps kernel
    executions. Cached per (m, k, axis_size, dtype) for the process
    lifetime.
    """
    import time

    import jax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from gloo_tpu.ops.overlap import _matmul_rs_shard

    if dtype is None:
        dtype = jnp.bfloat16
    key = (m, k, axis_size, str(dtype))
    if not interpret and key in _PROBE_CACHE:
        return _PROBE_CACHE[key]
    if m % axis_size:
        raise ValueError(f"rows {m} not divisible by ring size {axis_size}")
    if chain < 2:
        raise ValueError(f"chain must be >= 2, got {chain}")
    chunk = m // axis_size
    import numpy as np

    # local_devices: on a multi-host pod every process probes its OWN
    # chip (jax.devices()[0] is only addressable from host 0).
    mesh = Mesh(np.asarray(jax.local_devices()[:1], dtype=object),
                ("_probe",))
    w = jnp.full((k, k), 1.0 / k, dtype)
    x = jnp.ones((m, k), dtype)

    def fused_body(c):
        y = _matmul_rs_shard(c, w, axis_name="_probe", mesh_axes=None,
                             collective_id=29, interpret=interpret,
                             virtual_ranks=axis_size)
        return c.at[:chunk, :].set(y)

    def plain_body(c):
        return jnp.dot(c, w, preferred_element_type=jnp.float32
                       ).astype(c.dtype)

    def chained(body):
        # Trip count is a TRACED argument: one compiled executable
        # serves both chain lengths, so the probe pays the fused
        # kernel's multi-minute compile once (not twice) and the
        # differenced t1/tk time the SAME schedule draw by
        # construction.
        def outer(xv, n):
            return lax.fori_loop(0, n, lambda i, c: body(c), xv)
        return jax.jit(jax.shard_map(outer, mesh=mesh,
                                     in_specs=(P(), P()), out_specs=P(),
                                     check_vma=False))

    def run(f, n):
        jax.block_until_ready(f(x, jnp.int32(n)))

    def rate(body, name):
        f = chained(body)
        run(f, 1), run(f, chain)
        t1 = tk = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(f, 1)
            t1 = min(t1, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run(f, chain)
            tk = min(tk, time.perf_counter() - t0)
        if tk <= t1 and not interpret:
            # Noise exceeded chain-1 iterations of kernel time: a
            # clamped value here would cache a garbage ratio and drive
            # dispatch with it. Caller should raise `chain`.
            raise RuntimeError(
                f"measure_fused_ratio: timing noise exceeded the "
                f"{name} kernel's chained time at chain={chain}; "
                f"retry with a longer chain")
        return max(tk - t1, 1e-9) / (chain - 1)

    ratio = rate(plain_body, "plain") / rate(fused_body, "fused")
    if not interpret:
        # Interpreter-mode timings are meaningless — never serve them
        # to a later real measurement of the same shape.
        _PROBE_CACHE[key] = ratio
    return ratio


def row_parallel_dense_scattered_auto(x_shard, w_shard, axis: str,
                                      comm_share: float | None = None,
                                      interpret: bool = False,
                                      mesh_axes=None,
                                      ratio: float | None = None):
    """row_parallel_dense_scattered with the fused/unfused choice made
    by use_fused_overlap: the fused matmul_reduce_scatter kernel when
    hiding the collective pays for the chunking cost, else the plain
    dot + explicit reduce-scatter (identical semantics: [m/P, cols]
    row-scattered output). Pass ratio from measure_fused_ratio() to
    dispatch on this process's measured compile draw.

    The dispatch (including its TPUCOLL_TP_OVERLAP override) happens at
    trace time: under jit, a traced shape keeps whichever branch it was
    compiled with until the caller re-jits or runs jax.clear_caches()."""
    m, k = x_shard.shape
    cols = w_shard.shape[1]
    p = spmd.size(axis)
    if use_fused_overlap(m, k, cols, p, comm_share=comm_share,
                         ratio=ratio,
                         dtype_bytes=x_shard.dtype.itemsize):
        return row_parallel_dense_scattered(x_shard, w_shard, axis,
                                            interpret=interpret,
                                            mesh_axes=mesh_axes)
    partial = jnp.dot(x_shard, w_shard,
                      preferred_element_type=jnp.float32).astype(
                          x_shard.dtype)
    with jax.named_scope("gloo_tpu.tp.row_scatter"):
        return spmd.reduce_scatter(partial, axis, "sum", scatter_axis=0)


def allgather_matmul_dense_auto(x_rows_shard, w, axis: str,
                                comm_share: float | None = None,
                                interpret: bool = False, mesh_axes=None,
                                ratio: float | None = None):
    """allgather_matmul_dense with the fused/unfused choice made by
    use_fused_overlap (same rule as the reduce-scatter side: the two
    kernels are duals with the same chunk geometry), falling back to an
    explicit allgather + plain dot. Pass ratio from
    measure_fused_ratio(rows * axis_size, k, axis_size) — the kernel
    gathers the FULL [rows*P, k] input, so the probe's m is the total
    rows, not this shard's (unlike the reduce-scatter dual, whose m is
    the local shard's rows).

    As with the reduce-scatter dual, the fused/unfused choice (and any
    TPUCOLL_TP_OVERLAP override) is captured at trace time — changing
    the env var needs a re-jit or jax.clear_caches() to take effect."""
    rows, k = x_rows_shard.shape
    cols = w.shape[1]
    p = spmd.size(axis)
    m_total = rows * p
    if use_fused_overlap(m_total, k, cols, p, comm_share=comm_share,
                         ratio=ratio,
                         dtype_bytes=x_rows_shard.dtype.itemsize,
                         wire_elems=m_total * k):
        return allgather_matmul_dense(x_rows_shard, w, axis,
                                      interpret=interpret,
                                      mesh_axes=mesh_axes)
    with jax.named_scope("gloo_tpu.tp.allgather_x"):
        x_full = spmd.allgather(x_rows_shard, axis, gather_axis=0)
    return jnp.dot(x_full, w,
                   preferred_element_type=jnp.float32).astype(
                       x_rows_shard.dtype)
