"""FSDP + long-context tour: ZeRO-3-style sharded training and the three
sequence-parallel attention recipes (ring, ring-flash, Ulysses).

Runs on any JAX backend; to simulate a multi-chip TPU slice on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/example_fsdp_long_context.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gloo_tpu.models.mlp import MLP
from gloo_tpu.parallel import (make_fsdp_train_step, ring_attention,
                               shard_params, ulysses_attention,
                               unshard_params)
from gloo_tpu.tpu import make_mesh


def fsdp_demo(mesh):
    n = mesh.shape["data"]
    model = MLP([16, 64, 1])
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(8 * n, 16), jnp.float32)
    ys = jnp.sin(xs.sum(-1, keepdims=True))

    step = make_fsdp_train_step(model.loss, params, "data", lr=0.05)

    def run(p, x, y):
        sharded = shard_params(p, "data")  # 1/n of the model per device
        def body(i, carry):
            sh, _ = carry
            return step(sh, (x, y))
        sharded, loss = jax.lax.fori_loop(0, 20, body,
                                          (sharded, jnp.float32(0)))
        return unshard_params(sharded, p, "data"), loss

    params2, loss = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))(params, xs, ys)
    print(f"fsdp      : 20 SGD steps, final global loss {float(loss):.4f} "
          f"(params sharded 1/{n} per device, grads reduce-scattered by "
          "the all_gather transpose)")


def sequence_parallel_demo(mesh):
    n = mesh.shape["data"]
    b, h, t, d = 1, n, 16 * n, 32
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    specs = (P(None, None, "data"),) * 3
    ring = jax.jit(jax.shard_map(
        lambda a, k, v: ring_attention(a, k, v, "data"), mesh=mesh,
        in_specs=specs, out_specs=P(None, None, "data")))
    uly = jax.jit(jax.shard_map(
        lambda a, k, v: ulysses_attention(a, k, v, "data"), mesh=mesh,
        in_specs=specs, out_specs=P(None, None, "data"), check_vma=False))

    r, u = ring(q, q, q), uly(q, q, q)
    print(f"ring vs ulysses attention: max delta "
          f"{float(jnp.abs(r - u).max()):.2e} (same math, ppermute ring "
          "vs one all-to-all per direction)")


def main():
    mesh = make_mesh({"data": -1})
    print(f"mesh: {mesh.shape}")
    fsdp_demo(mesh)
    sequence_parallel_demo(mesh)
    print("fsdp + long-context example OK")


if __name__ == "__main__":
    main()
