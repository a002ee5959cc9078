"""Fused matmul+collective kernels (gloo_tpu/ops/overlap.py), validated on
the distributed-interpreter CPU mesh against reference einsums, including
their transposed-dual VJPs."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from gloo_tpu.ops import allgather_matmul, matmul_reduce_scatter  # noqa: E402


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.asarray(devs[:n], dtype=object), ("x",))


def _rand(shape, seed, dtype=np.float32):
    return np.random.RandomState(seed).uniform(-1, 1, shape).astype(dtype)


@pytest.mark.parametrize("n", [2, 3, 4, 8])
def test_matmul_reduce_scatter_forward(n):
    mesh = _mesh(n)
    m, k_total, cols = 8 * n, 16 * n, 128
    x = _rand((m, k_total), 0)          # global X, k sharded
    w = _rand((k_total, cols), 1)       # global W, k sharded

    fn = jax.jit(jax.shard_map(
        lambda xs, ws: matmul_reduce_scatter(xs, ws, "x", interpret=True),
        mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
        out_specs=P("x", None), check_vma=False))
    out = np.asarray(fn(x, w))          # [m, cols]: rank r rows stacked
    expected = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_allgather_matmul_forward(n):
    mesh = _mesh(n)
    m_total, k, cols = 8 * n, 32, 128
    x = _rand((m_total, k), 2)          # global X, rows sharded
    w = _rand((k, cols), 3)             # replicated W

    fn = jax.jit(jax.shard_map(
        lambda xs, ws: allgather_matmul(xs, ws, "x", interpret=True),
        mesh=mesh, in_specs=(P("x", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))
    # Every device computes the FULL product; out_specs=P(None) asserts
    # replication and returns one copy.
    out = np.asarray(fn(x, w))
    expected = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


def test_allgather_matmul_column_sharded_w(n=4):
    """w column-sharded (true column-parallel): each device computes its
    own output columns for ALL rows."""
    mesh = _mesh(n)
    m_total, k, cols = 8 * n, 32, 128 * n
    x = _rand((m_total, k), 4)
    w = _rand((k, cols), 5)

    fn = jax.jit(jax.shard_map(
        lambda xs, ws: allgather_matmul(xs, ws, "x", interpret=True),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=P(None, "x"), check_vma=False))
    out = np.asarray(fn(x, w))
    expected = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


def test_matmul_reduce_scatter_grads(n=4):
    """VJP against the unfused reference: dx and dw must match the plain
    einsum composition's grads (the duality allgather <-> reduce-scatter)."""
    mesh = _mesh(n)
    m, k_total, cols = 8 * n, 16 * n, 128
    x = _rand((m, k_total), 6)
    w = _rand((k_total, cols), 7)

    def fused_loss(xv, wv):
        def shard(xs, ws):
            y = matmul_reduce_scatter(xs, ws, "x", interpret=True)
            return y
        y = jax.shard_map(shard, mesh=mesh,
                          in_specs=(P(None, "x"), P("x", None)),
                          out_specs=P("x", None), check_vma=False)(xv, wv)
        return jnp.sum(jnp.sin(y))

    def ref_loss(xv, wv):
        return jnp.sum(jnp.sin(xv @ wv))

    gx_f, gw_f = jax.grad(fused_loss, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(ref_loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                               rtol=2e-4, atol=2e-5)


def test_allgather_matmul_grads(n=4):
    """Backward of the gather-side op runs the fused dual
    (matmul_reduce_scatter) — grads must match the plain composition."""
    mesh = _mesh(n)
    m_total, k, cols = 8 * n, 32, 128
    x = _rand((m_total, k), 8)
    w = _rand((k, cols), 9)

    def fused_loss(xv, wv):
        def shard(xs, ws):
            return allgather_matmul(xs, ws, "x", interpret=True)
        y = jax.shard_map(shard, mesh=mesh,
                          in_specs=(P("x", None), P(None, None)),
                          out_specs=P(None, None), check_vma=False)(xv, wv)
        return jnp.sum(jnp.cos(y))

    def ref_loss(xv, wv):
        return jnp.sum(jnp.cos(xv @ wv))

    gx_f, gw_f = jax.grad(fused_loss, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(ref_loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                               rtol=2e-4, atol=2e-5)


def test_matmul_reduce_scatter_bf16(n=4):
    mesh = _mesh(n)
    m, k_total, cols = 8 * n, 16 * n, 128
    x = _rand((m, k_total), 10).astype(jnp.bfloat16)
    w = _rand((k_total, cols), 11).astype(jnp.bfloat16)
    fn = jax.jit(jax.shard_map(
        lambda xs, ws: matmul_reduce_scatter(xs, ws, "x", interpret=True),
        mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
        out_specs=P("x", None), check_vma=False))
    out = np.asarray(fn(x, w).astype(jnp.float32))
    expected = np.asarray(x.astype(np.float32)) @ np.asarray(
        w.astype(np.float32))
    np.testing.assert_allclose(out, expected, rtol=0.1, atol=0.1)


def test_megatron_sp_roundtrip_fused(n=4):
    """The Megatron sequence-parallel loop with BOTH collectives fused:
    sequence-sharded x -> allgather_matmul_dense (gather fused into the
    up-projection) -> gelu -> row_parallel_dense_scattered (reduce-scatter
    fused into the down-projection) -> sequence-sharded y. Must match the
    plain dense MLP."""
    from gloo_tpu.parallel.tp import (allgather_matmul_dense,
                                      row_parallel_dense_scattered)

    mesh = _mesh(n)
    seq, d, h = 8 * n, 32, 16 * n
    x = _rand((seq, d), 20)
    w_up = _rand((d, h), 21)      # columns sharded over the axis
    w_down = _rand((h, d), 22)    # rows sharded over the axis

    def shard(xs, wu, wd):
        hidden = allgather_matmul_dense(xs, wu, "x", interpret=True)
        hidden = jax.nn.gelu(hidden)
        return row_parallel_dense_scattered(hidden, wd, "x", interpret=True)

    fn = jax.jit(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P("x", None), P(None, "x"), P("x", None)),
        out_specs=P("x", None), check_vma=False))
    out = np.asarray(fn(x, w_up, w_down))
    expected = np.asarray(jax.nn.gelu(jnp.asarray(x @ w_up))) @ w_down
    np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)


def test_matmul_reduce_scatter_multi_axis_mesh():
    """2x2 mesh, ring over the minor 'model' axis: mesh_axes routes the
    RDMA by flattened logical device id (omitting it would misroute)."""
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.asarray(devs[:4], dtype=object).reshape(2, 2),
                ("data", "model"))
    n = 2
    m, k_total, cols = 8 * n, 16 * n, 128
    x = _rand((m, k_total), 30)
    w = _rand((k_total, cols), 31)

    fn = jax.jit(jax.shard_map(
        lambda xs, ws: matmul_reduce_scatter(
            xs, ws, "model", interpret=True,
            mesh_axes=("data", "model")),
        mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None), check_vma=False))
    out = np.asarray(fn(x, w))
    expected = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


def test_virtual_ring_selfloop_bench_path():
    """The single-chip timing mode (virtual_ranks on a 1-device axis):
    self-loop RDMA means every hop
    adds this rank's own staged sum, so matmul_rs degenerates to
    sum over row-blocks of X_b @ W and allgather_matmul's own chunk
    is exact. Guards the timing harness against schedule rot."""
    from gloo_tpu.ops.overlap import _ag_matmul_shard, _matmul_rs_shard

    mesh = _mesh(1)
    V, m, k, cols = 4, 64, 32, 128
    chunk = m // V
    x = _rand((m, k), 7)
    w = _rand((k, cols), 8)

    out = jax.jit(jax.shard_map(
        lambda xs, ws: _matmul_rs_shard(
            xs, ws, axis_name="x", mesh_axes=None, collective_id=21,
            interpret=True, virtual_ranks=V),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))(
            x, w)
    expected = sum(x[b * chunk:(b + 1) * chunk].astype(np.float64)
                   @ w.astype(np.float64) for b in range(V))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-5,
                               atol=2e-5)

    xs = _rand((chunk, k), 9)
    y, _gx = jax.jit(jax.shard_map(
        lambda xv, ws: _ag_matmul_shard(
            xv, ws, axis_name="x", mesh_axes=None, collective_id=23,
            interpret=True, virtual_ranks=V),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))(
            xs, w)
    # Only this rank's own chunk (row-block 0 for rank 0) is defined in
    # self-loop mode; the rest of gx is never received.
    np.testing.assert_allclose(np.asarray(y)[:chunk],
                               xs.astype(np.float64) @ w.astype(np.float64),
                               rtol=2e-5, atol=2e-5)


def test_fori_fallback_matches_unrolled(n=4, monkeypatch=None):
    """Pod-size rings (> _kMaxUnrollRing) take the fori_loop form of the
    ring walk instead of the static unroll; force it and pin both
    kernels against the same oracles the unrolled path satisfies."""
    import gloo_tpu.ops.overlap as ov

    saved = ov._kMaxUnrollRing
    ov._kMaxUnrollRing = 1  # every ring takes the fallback
    # New jit cache keys: bump collective ids so cached unrolled
    # executables are not reused.
    try:
        mesh = _mesh(n)
        m, k_total, cols = 8 * n, 16 * n, 128
        x = _rand((m, k_total), 20)
        w = _rand((k_total, cols), 21)
        fn = jax.jit(jax.shard_map(
            lambda xs, ws: matmul_reduce_scatter(
                xs, ws, "x", interpret=True, collective_id=41),
            mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
            out_specs=P("x", None), check_vma=False))
        np.testing.assert_allclose(
            np.asarray(fn(x, w)),
            x.astype(np.float64) @ w.astype(np.float64),
            rtol=2e-5, atol=2e-5)

        x2 = _rand((8 * n, 32), 22)
        w2 = _rand((32, cols), 23)
        fn2 = jax.jit(jax.shard_map(
            lambda xs, ws: allgather_matmul(
                xs, ws, "x", interpret=True, collective_id=43),
            mesh=mesh, in_specs=(P("x", None), P(None, None)),
            out_specs=P(None, None), check_vma=False))
        np.testing.assert_allclose(
            np.asarray(fn2(x2, w2)),
            x2.astype(np.float64) @ w2.astype(np.float64),
            rtol=2e-5, atol=2e-5)
    finally:
        ov._kMaxUnrollRing = saved
