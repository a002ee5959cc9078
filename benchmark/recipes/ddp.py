"""The DDP recipe: gloo_tpu's `make_ddp_train_step` over a `data` mesh.

The system under test is the jitted step the library returns: the
Transformer forward with the Pallas flash kernel, the loss, the
backward, the gradient mean over `data`, and optax's AdamW, one call a
step. Params and AdamW state are replicated; each chip takes its rows.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def build(cfg: dict, mesh) -> SimpleNamespace:
    """step(params, opt_state, (tokens, targets)) -> (params, opt_state,
    loss); init_opt(params); first_moment(opt_state) -> the Adam first
    moment, a tree like params."""
    import optax

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step

    model = Transformer(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        d_ff=cfg["n_inner"], max_seq_len=cfg["n_positions"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        use_flash_attention=cfg["flash_attention"]))
    hp = cfg["optimizer"]
    opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"])
    replicated = NamedSharding(mesh, P())

    def first_moment(state):
        for s in jax.tree.leaves(
                state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState)):
            if isinstance(s, optax.ScaleByAdamState):
                return s.mu
        raise ValueError("no Adam state in the optimizer state")

    return SimpleNamespace(
        step=make_ddp_train_step(model.loss, opt, mesh),
        init_opt=jax.jit(opt.init, out_shardings=replicated),
        first_moment=first_moment,
        batch_sharding=NamedSharding(mesh, P("data")),
        param_sharding=replicated)
