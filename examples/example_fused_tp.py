"""Fused tensor parallelism: the Megatron-SP MLP with both collectives
fused into their matmuls (gloo_tpu.ops.overlap collective-matmul kernels).

The sequence dim stays sharded outside the block; inside, the gather-side
projection runs allgather_matmul (each ICI hop flies while the MXU
computes the next chunk) and the scatter-side projection runs
matmul_reduce_scatter — no standalone collective anywhere, forward or
backward (the two kernels are each other's VJP).

Runs on any JAX backend; to simulate a multi-chip TPU slice on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/example_fused_tp.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gloo_tpu.parallel.tp import (allgather_matmul_dense,
                                  row_parallel_dense_scattered)
from gloo_tpu.tpu import make_mesh

# The Pallas interpreter backs the kernels off-TPU; on a real slice drop
# interpret=True.
INTERPRET = jax.default_backend() != "tpu"


def main():
    mesh = make_mesh({"model": -1})
    n = mesh.shape["model"]
    seq, d_model, d_ff = 16 * n, 64, 32 * n
    rng = np.random.RandomState(0)
    x = rng.normal(size=(seq, d_model)).astype(np.float32) * 0.1
    w_up = rng.normal(size=(d_model, d_ff)).astype(np.float32) * 0.1
    w_down = rng.normal(size=(d_ff, d_model)).astype(np.float32) * 0.1

    def block(xs, wu, wd):
        h = allgather_matmul_dense(xs, wu, "model", interpret=INTERPRET)
        h = jax.nn.gelu(h)
        return row_parallel_dense_scattered(h, wd, "model",
                                            interpret=INTERPRET)

    fused = jax.jit(jax.shard_map(
        block, mesh=mesh,
        in_specs=(P("model", None), P(None, "model"), P("model", None)),
        out_specs=P("model", None), check_vma=False))

    y = np.asarray(fused(x, w_up, w_down))
    ref = np.asarray(jax.nn.gelu(jnp.asarray(x @ w_up))) @ w_down
    err = float(np.abs(y - ref).max())
    print(f"mesh: {mesh.shape}  fused MLP out {y.shape}  max|err| {err:.2e}")
    assert err < 2e-3

    # Gradients flow through the dual kernels (no unfused collective in
    # the backward either).
    def loss(xs, wu, wd):
        out = jax.shard_map(
            block, mesh=mesh,
            in_specs=(P("model", None), P(None, "model"), P("model", None)),
            out_specs=P("model", None), check_vma=False)(xs, wu, wd)
        return jnp.mean(out ** 2)

    g = jax.grad(loss, argnums=1)(x, w_up, w_down)
    print(f"dL/dw_up via fused VJPs: {np.asarray(g).shape}, "
          f"|g| {float(jnp.abs(g).mean()):.2e}")

    # Production entry point (r5): the *_auto variants decide per shape
    # whether fusing pays — the fused kernels give up some MXU
    # throughput to hide the collective, and on shapes where the
    # collective is cheap relative to that penalty (K-heavy shards,
    # small chunks) they fall
    # back to plain dots + explicit collectives. Force either arm with
    # TPUCOLL_TP_OVERLAP=fused|unfused; feed
    # parallel.measure_fused_ratio() into use_fused_overlap for a
    # probe-measured decision on real hardware.
    from gloo_tpu.parallel import (allgather_matmul_dense_auto,
                                   row_parallel_dense_scattered_auto,
                                   use_fused_overlap)

    def block_auto(xs, wu, wd):
        h = allgather_matmul_dense_auto(xs, wu, "model",
                                        interpret=INTERPRET)
        h = jax.nn.gelu(h)
        return row_parallel_dense_scattered_auto(h, wd, "model",
                                                 interpret=INTERPRET)

    y2 = np.asarray(jax.jit(jax.shard_map(
        block_auto, mesh=mesh,
        in_specs=(P("model", None), P(None, "model"), P("model", None)),
        out_specs=P("model", None), check_vma=False))(x, w_up, w_down))
    assert float(np.abs(y2 - ref).max()) < 2e-3
    picked = use_fused_overlap(seq, d_ff // n, d_model, n)
    print(f"auto dispatcher on this shape/mesh picks: "
          f"{'fused' if picked else 'unfused'}")
    print("fused tensor-parallel example OK")


if __name__ == "__main__":
    main()
