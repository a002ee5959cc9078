"""DeepSeek-V2's decoder (DeepSeek-AI 2024, arXiv:2405.04434): multi-head
latent attention (§2.1) and DeepSeekMoE (§2.2), trained with its
sequence-wise balance loss.

- MLA without a query compression: q = x W_q gives each head a 128-wide
  part and a 64-wide rotary part; keys and values come from one 512-wide
  latent c = RMSNorm(x W_kva[:512]), k_nope | v = c W_kvb, and one 64-wide
  rotary key x W_kva[512:] that every head shares. q and k are 192 wide,
  v 128. Attention runs through the Pallas flash kernel at 192 with v
  zero-padded to 192; q is pre-scaled by YaRN's mscale² so that the
  kernel's 1/√192 gives DeepSeek's softmax scale.
- YaRN rotary frequencies (`ops.rope.yarn_inv_freq`), rotate-half layout.
- The first `first_dense_layers` layers have a SwiGLU MLP; the rest have
  shared experts (one SwiGLU `n_shared_experts` times the expert width)
  plus routed experts through `gloo_tpu.parallel.moe`, which exchanges
  tokens between chips over `ep_axis` when that axis has more than one.
- An untied output head; the loss is logsumexp minus the target logit in
  f32, plus α Σ_i f_i P_i per sequence over every router expert.

bfloat16 activations over float32 params, like `Transformer`. The params
are a pytree: `embed`, `layers` (a list), `ln_f`, `head`; `param_specs`
names the expert leaves that expert parallelism splits over its axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class DeepSeekV2Config:
    vocab_size: int = 12800
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 5
    first_dense_layers: int = 1
    d_ff: int = 10944                 # the dense layers' SwiGLU width
    moe_d_ff: int = 1408              # one expert's width
    n_shared_experts: int = 2
    n_experts: int = 8                # routed experts held on the axis
    router_experts: int = 64          # experts the router scores
    top_k: int = 6
    routed_scale: float = 1.0
    aux_loss_alpha: float = 0.001
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    yarn_factor: float = 40.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 0.707
    dtype: Any = jnp.bfloat16
    # Rematerialize each decoder layer in the backward pass.
    remat: bool = True
    # The mesh axis the routed experts are split over (None: all here).
    ep_axis: str | None = None


class DeepSeekV2:
    def __init__(self, config: DeepSeekV2Config):
        self.cfg = config

    # ---- params ----

    def init(self, key, std: float = 0.006) -> Dict:
        """Normal(0, std) weights (DeepSeek-V2 §3.1.2), norm scales 1;
        `n_experts` routed experts a MoE layer."""
        shapes = self._shapes()
        leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            jnp.ones(s[1:], jnp.float32) if s[0] == "ones"
            else jax.random.normal(k, s[1:], jnp.float32) * std
            for k, s in zip(keys, leaves)])

    def param_specs(self, axis: str) -> Dict:
        """PartitionSpecs like params: the routed experts on `axis`,
        every other leaf replicated."""
        def spec(path, _):
            return P(axis) if any(getattr(p, "key", None) == "experts"
                                  for p in path) else P()
        return jax.tree_util.tree_map_with_path(spec, self._shapes(),
                                                is_leaf=_is_shape)

    def _shapes(self):
        c = self.cfg
        d, h = c.d_model, c.n_heads
        qk = c.qk_nope_dim + c.qk_rope_dim

        def norm(n):
            return {"scale": ("ones", n)}

        def mlp(f):
            return {"w_gate": ("normal", d, f), "w_up": ("normal", d, f),
                    "w_down": ("normal", f, d)}

        layers = []
        for i in range(c.n_layers):
            layer = {
                "attn_norm": norm(d),
                "mla": {"wq": ("normal", d, h * qk),
                        "wkv_a": ("normal", d,
                                  c.kv_lora_rank + c.qk_rope_dim),
                        "kv_norm": norm(c.kv_lora_rank),
                        "wkv_b": ("normal", c.kv_lora_rank,
                                  h * (c.qk_nope_dim + c.v_dim)),
                        "wo": ("normal", h * c.v_dim, d)},
                "ffn_norm": norm(d),
            }
            if i < c.first_dense_layers:
                layer["mlp"] = mlp(c.d_ff)
            else:
                e, f = c.n_experts, c.moe_d_ff
                layer["moe"] = {
                    "router": ("normal", d, c.router_experts),
                    "shared": mlp(c.n_shared_experts * f),
                    "experts": {"w_gate": ("normal", e, d, f),
                                "w_up": ("normal", e, d, f),
                                "w_down": ("normal", e, f, d)}}
            layers.append(layer)
        return {"embed": ("normal", c.vocab_size, d), "layers": layers,
                "ln_f": norm(d), "head": ("normal", d, c.vocab_size)}

    # ---- forward ----

    @staticmethod
    def _rmsnorm(x, scale):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) \
            * scale.astype(x.dtype)

    @staticmethod
    def _swiglu(p, x):
        dt = x.dtype
        return (jax.nn.silu(x @ p["w_gate"].astype(dt))
                * (x @ p["w_up"].astype(dt))) @ p["w_down"].astype(dt)

    def mscale(self) -> float:
        """YaRN's attention scale, 0.1 mscale_all_dim ln(factor) + 1."""
        c = self.cfg
        return 0.1 * c.yarn_mscale_all_dim * math.log(c.yarn_factor) + 1.0

    def inv_freq(self):
        from gloo_tpu.ops.rope import yarn_inv_freq

        c = self.cfg
        return yarn_inv_freq(c.qk_rope_dim, c.rope_theta, c.yarn_factor,
                             c.yarn_original_positions, c.yarn_beta_fast,
                             c.yarn_beta_slow)

    def _mla(self, p, x):
        from gloo_tpu.ops.attention import flash_attention
        from gloo_tpu.ops.rope import apply_rope, rope_positions

        c = self.cfg
        b, t, _ = x.shape
        h, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_dim
        dt = x.dtype
        pos, freq = rope_positions(t), self.inv_freq()
        q = (x @ p["wq"].astype(dt)).reshape(b, t, h, dn + dr)
        q = q.transpose(0, 2, 1, 3)
        kv_a = x @ p["wkv_a"].astype(dt)
        latent = self._rmsnorm(kv_a[..., :c.kv_lora_rank],
                               p["kv_norm"]["scale"])
        k_pe = apply_rope(kv_a[..., c.kv_lora_rank:], pos, inv_freq=freq)
        kv = (latent @ p["wkv_b"].astype(dt)).reshape(b, t, h, dn + dv)
        kv = kv.transpose(0, 2, 1, 3)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], pos, inv_freq=freq)],
            axis=-1) * jnp.asarray(self.mscale() ** 2, dt)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (b, h, t, dr))],
            axis=-1)
        v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, dn + dr - dv),))
        out = flash_attention(q, k, v, causal=True,
                              interpret=jax.default_backend() == "cpu")
        out = out[..., :dv].transpose(0, 2, 1, 3).reshape(b, t, h * dv)
        return out @ p["wo"].astype(dt)

    def _moe(self, p, x):
        """Shared experts plus this chip's routed experts; returns the
        output and the layer's balance loss."""
        from gloo_tpu.parallel.ep import moe
        from gloo_tpu.tpu import spmd

        c = self.cfg
        b, t, d = x.shape
        axis = c.ep_axis
        first = spmd.rank(axis) * c.n_experts // spmd.size(axis) \
            if axis is not None else 0
        e = p["experts"]
        routed, probs, top = moe(
            x.reshape(b * t, d), p["router"], e["w_gate"], e["w_up"],
            e["w_down"], first_expert=first, top_k=c.top_k, axis=axis,
            scale=c.routed_scale)
        return (self._swiglu(p["shared"], x) + routed.reshape(b, t, d),
                self.balance_loss(probs.reshape(b, t, -1),
                                  top.reshape(b, t, -1)))

    def balance_loss(self, probs, top):
        """DeepSeek-V2 §2.2.3, `seq_aux`: α Σ_i f_i P_i for each sequence,
        f_i = G / (k T) × the times expert i is chosen, P_i its mean
        score; the mean over sequences. probs (B, T, G), top (B, T, k)."""
        c = self.cfg
        g = probs.shape[-1]
        t, k = top.shape[1], top.shape[2]
        chosen = jnp.sum(top[..., None] == jnp.arange(g), axis=(1, 2),
                         dtype=jnp.float32)
        f = chosen * (g / (k * t))
        return c.aux_loss_alpha * jnp.mean(
            jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))

    def _layer(self, p, x):
        with jax.named_scope("gloo_tpu.mla"):
            x = x + self._mla(p["mla"],
                              self._rmsnorm(x, p["attn_norm"]["scale"]))
        h = self._rmsnorm(x, p["ffn_norm"]["scale"])
        if "mlp" in p:
            return x + self._swiglu(p["mlp"], h), jnp.zeros((), jnp.float32)
        y, aux = self._moe(p["moe"], h)
        return x + y, aux

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> (logits (B, T, vocab) f32, the summed
        balance loss of the MoE layers)."""
        c = self.cfg
        x = params["embed"][tokens].astype(c.dtype)
        layer = jax.checkpoint(self._layer) if c.remat else self._layer
        aux = jnp.zeros((), jnp.float32)
        for p in params["layers"]:
            x, a = layer(p, x)
            aux = aux + a
        x = self._rmsnorm(x, params["ln_f"]["scale"])
        return x.astype(jnp.float32) @ params["head"], aux

    def loss(self, params, batch):
        """batch: (tokens, targets), each (B, T) int32: the mean of
        logsumexp(logits) - logits[target], plus the balance loss."""
        tokens, targets = batch
        logits, aux = self.apply(params, tokens)
        target_logit = jnp.take_along_axis(logits, targets[..., None],
                                           axis=-1).squeeze(-1)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1)
                        - target_logit) + aux


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and bool(x) and isinstance(x[0], str)


