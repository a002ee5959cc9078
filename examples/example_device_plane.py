"""Device-plane tour: mesh collectives, DDP training step, ring attention.

Runs on any JAX backend; to simulate a multi-chip TPU slice on CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/example_device_plane.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
import optax

from gloo_tpu.models import Transformer, TransformerConfig
from gloo_tpu.parallel import make_ddp_train_step
from gloo_tpu.tpu import TpuProcessGroup, make_mesh


def main():
    mesh = make_mesh({"data": -1})
    pg = TpuProcessGroup(mesh)
    print(f"mesh: {mesh.shape}, group size {pg.size}")

    # Array-level collectives (host-API mirror)
    x = pg.shard(np.arange(pg.size * 4, dtype=np.float32).reshape(pg.size, 4))
    print("allreduce :", pg.unshard(pg.allreduce(x))[0])
    print("broadcast :", pg.unshard(pg.broadcast(x, root=0))[0])
    pg.barrier()

    # DDP training step: batch sharded over the mesh, grads psum'd on ICI
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=2,
                            n_layers=2, d_ff=128, max_seq_len=32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)
    step = make_ddp_train_step(model.loss, optimizer, mesh)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size,
                         (4 * pg.size, cfg.max_seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    for i in range(20):
        params, opt_state, loss = step(params, opt_state, (tokens, targets))
        if i % 5 == 0:
            print(f"ddp step {i:2d} loss {float(loss):.4f}")

    print("done")


if __name__ == "__main__":
    main()
