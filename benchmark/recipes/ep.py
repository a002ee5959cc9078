"""The EP recipe: gloo_tpu's DeepSeekV2 through `make_ddp_train_step` over
a `data` mesh, its routed experts split over the same axis.

The system under test is the jitted step the library returns: MLA through
the Pallas flash kernel, dense and shared-expert SwiGLUs, the routed
experts through `gloo_tpu.parallel.moe` (a ragged all-to-all each way
where the axis has more than one chip), the loss, the backward, the
gradient mean over `data`, and optax's AdamW, one call a step. Every
leaf but the routed experts is replicated and each chip takes its rows;
each chip holds its block of the experts and their AdamW state. The
recipe's own jitted `step` around the library's donates the params and
AdamW state it is given: with one step in flight the window would
otherwise hold three copies of a state that fills 6.4 GB of a chip's 16.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def build(cfg: dict, mesh) -> SimpleNamespace:
    """step(params, opt_state, (tokens, targets)) -> (params, opt_state,
    loss); init_opt(params); first_moment(opt_state) -> the Adam first
    moment, a tree like params; param_sharding and opt_sharding, trees of
    shardings."""
    import optax

    from gloo_tpu.models import DeepSeekV2, DeepSeekV2Config
    from gloo_tpu.parallel import make_ddp_train_step

    rope = cfg["rope_scaling"]
    if rope["mscale"] != rope["mscale_all_dim"] or cfg["norm_topk_prob"]:
        raise ValueError("the model scales cos/sin by 1 and does not "
                         "renormalise the top-k scores")
    model = DeepSeekV2(DeepSeekV2Config(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        first_dense_layers=cfg["first_dense_layers"], d_ff=cfg["n_inner"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_experts=cfg["n_routed_experts"],
        router_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        aux_loss_alpha=cfg["aux_loss_alpha"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], yarn_factor=rope["factor"],
        yarn_original_positions=rope["original_max_position_embeddings"],
        yarn_beta_fast=rope["beta_fast"], yarn_beta_slow=rope["beta_slow"],
        yarn_mscale_all_dim=rope["mscale_all_dim"],
        dtype=jnp.dtype(cfg["compute_dtype"]), remat=cfg["remat"],
        ep_axis="data"))
    hp = cfg["optimizer"]
    opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"])
    specs = model.param_specs("data")
    replicated = NamedSharding(mesh, P())
    param_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))
    opt_sharding = optax.tree_map_params(
        opt, lambda _, s: s,
        jax.eval_shape(opt.init, jax.eval_shape(model.init,
                                                jax.random.key(0))),
        param_sharding, transform_non_params=lambda _: replicated,
        is_leaf=lambda x: isinstance(x, NamedSharding))

    def first_moment(state):
        for s in jax.tree.leaves(
                state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState)):
            if isinstance(s, optax.ScaleByAdamState):
                return s.mu
        raise ValueError("no Adam state in the optimizer state")

    train_step = make_ddp_train_step(model.loss, opt, mesh,
                                     param_specs=specs)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        return train_step(params, opt_state, batch)

    return SimpleNamespace(
        step=step,
        init_opt=jax.jit(opt.init, out_shardings=opt_sharding),
        first_moment=first_moment,
        batch_sharding=NamedSharding(mesh, P("data")),
        param_sharding=param_sharding, opt_sharding=opt_sharding)
