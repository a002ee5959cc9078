"""Flagship demo model: a causal-LM transformer written TPU-first.

Design notes (why it looks the way it does):
- bfloat16 activations with float32 parameters/logits: keeps the MXU fed
  at its native precision while preserving loss accuracy;
- shapes are static and multiples of (8, 128)-friendly sizes so XLA tiles
  matmuls onto the MXU without padding;
- pure functions over a params pytree — trivially composable with
  shard_map/pjit shardings (dp/tp splits live in gloo_tpu.parallel, not in
  the model).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 512
    d_model: int = 256
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 1024
    max_seq_len: int = 128
    dtype: Any = jnp.bfloat16
    # Use the Pallas flash-attention kernel (gloo_tpu.ops) instead of the
    # materialized-scores path; requires seq divisible by its block sizes.
    use_flash_attention: bool = False
    # Grouped-query attention: number of shared k/v heads (None = n_heads,
    # i.e. classic multi-head; 1 = multi-query).
    n_kv_heads: int | None = None
    # Rotary position embeddings on q/k instead of the learned absolute
    # table (the long-context default: positions travel with the math,
    # so sequence-parallel shards rotate by their global offsets).
    use_rope: bool = False


class Transformer:
    def __init__(self, config: TransformerConfig):
        self.cfg = config

    # ---- init ----

    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = jax.random.split(key, 2 + cfg.n_layers)

        def dense(k, fan_in, fan_out):
            scale = jnp.sqrt(1.0 / fan_in)
            return jax.random.normal(k, (fan_in, fan_out),
                                     jnp.float32) * scale

        h_kv = (cfg.n_kv_heads if cfg.n_kv_heads is not None
                else cfg.n_heads)
        if h_kv < 1 or cfg.n_heads % h_kv != 0:
            raise ValueError(
                f"n_heads {cfg.n_heads} must be a positive multiple of "
                f"n_kv_heads {h_kv}")
        kv_dim = (cfg.d_model // cfg.n_heads) * h_kv
        layers = []
        for i in range(cfg.n_layers):
            lk = jax.random.split(keys[2 + i], 6)
            layers.append({
                "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
                "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
                "wqkv": dense(lk[0], cfg.d_model,
                              cfg.d_model + 2 * kv_dim),
                "wo": dense(lk[1], cfg.d_model, cfg.d_model),
                "w_up": dense(lk[2], cfg.d_model, cfg.d_ff),
                "w_down": dense(lk[3], cfg.d_ff, cfg.d_model),
            })
        params = {
            "embed": jax.random.normal(
                keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02,
            "ln_f": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
            "layers": layers,
        }
        if not cfg.use_rope:
            # Learned absolute table only when it is actually consumed —
            # a dead entry would still ride checkpoints/optimizer state.
            params["pos"] = jax.random.normal(
                keys[1], (cfg.max_seq_len, cfg.d_model), jnp.float32) * 0.02
        return params

    # ---- forward ----

    @staticmethod
    def _rmsnorm(x, scale):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale

    def _project_qkv(self, layer, x, positions):
        """Single definition of the fused projection layout: slice offsets,
        head reshapes, GQA kv width, and RoPE — used by BOTH the full
        forward and the cached decode step so the two cannot drift (the
        incremental-vs-full parity test guards exactly this)."""
        cfg = self.cfg
        b, t, d = x.shape
        h = cfg.n_heads
        hd = d // h
        h_kv = cfg.n_kv_heads if cfg.n_kv_heads is not None else h
        kv_dim = hd * h_kv
        qkv = x @ layer["wqkv"].astype(x.dtype)
        q = qkv[..., :d].reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        k = qkv[..., d:d + kv_dim].reshape(b, t, h_kv, hd)
        k = k.transpose(0, 2, 1, 3)
        v = qkv[..., d + kv_dim:].reshape(b, t, h_kv, hd)
        v = v.transpose(0, 2, 1, 3)
        if cfg.use_rope:
            from gloo_tpu.ops.rope import apply_rope

            q = apply_rope(q, positions)
            k = apply_rope(k, positions)
        return q, k, v

    def _attention(self, layer, x):
        cfg = self.cfg
        b, t, d = x.shape
        h = cfg.n_heads
        hd = d // h
        h_kv = cfg.n_kv_heads if cfg.n_kv_heads is not None else h
        from gloo_tpu.ops.rope import rope_positions

        q, k, v = self._project_qkv(layer, x, rope_positions(t))
        if cfg.use_flash_attention and t % 8 == 0:
            from gloo_tpu.ops.attention import flash_attention

            # Adaptive tile defaults (largest_block); CPU
            # backends only run Pallas through the interpreter.
            out = flash_attention(
                q, k, v, causal=True,
                interpret=jax.default_backend() == "cpu")
        else:
            if h_kv != h:
                k = jnp.repeat(k, h // h_kv, axis=1)
                v = jnp.repeat(v, h // h_kv, axis=1)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores / jnp.sqrt(jnp.float32(hd))
            mask = jnp.tril(jnp.ones((t, t), jnp.bool_))
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                             preferred_element_type=jnp.float32)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, d).astype(x.dtype)
        return out @ layer["wo"].astype(x.dtype)

    def _mlp(self, layer, x):
        up = x @ layer["w_up"].astype(x.dtype)
        return jax.nn.gelu(up) @ layer["w_down"].astype(x.dtype)

    def apply(self, params, tokens):
        """tokens: (batch, seq) int32 -> logits (batch, seq, vocab) f32."""
        cfg = self.cfg
        t = tokens.shape[1]
        x = params["embed"][tokens]
        if not cfg.use_rope:
            x = x + params["pos"][:t]
        x = x.astype(cfg.dtype)
        for layer in params["layers"]:
            x = x + self._attention(layer, self._rmsnorm(
                x, layer["ln1"]["scale"].astype(x.dtype)))
            x = x + self._mlp(layer, self._rmsnorm(
                x, layer["ln2"]["scale"].astype(x.dtype)))
        x = self._rmsnorm(x, params["ln_f"]["scale"].astype(x.dtype))
        return (x.astype(jnp.float32) @ params["embed"].T)

    def loss(self, params, batch):
        """batch: (tokens, targets), each (batch, seq) int32.

        Each token's NLL is logsumexp(logits) - logits[target]: the same
        value as -log_softmax(logits)[target], without a second vocab-wide
        f32 tensor for the step to write and read back."""
        tokens, targets = batch
        logits = self.apply(params, tokens)
        target_logit = jnp.take_along_axis(logits, targets[..., None],
                                           axis=-1).squeeze(-1)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - target_logit)

    # ---- incremental decoding (KV cache) ----

    def init_cache(self, batch: int, max_len: int | None = None):
        """Per-layer key/value cache for incremental decoding. GQA models
        cache only n_kv_heads — the cache shrinks by the group factor,
        which is the production reason to use GQA."""
        cfg = self.cfg
        max_len = max_len or cfg.max_seq_len
        if not cfg.use_rope and max_len > cfg.max_seq_len:
            # The learned positional table has max_seq_len rows; beyond it
            # dynamic_slice would silently clamp to the last row.
            raise ValueError(
                f"cache length {max_len} exceeds max_seq_len "
                f"{cfg.max_seq_len} (learned positions)")
        hd = cfg.d_model // cfg.n_heads
        h_kv = cfg.n_kv_heads if cfg.n_kv_heads is not None else cfg.n_heads
        zeros = jnp.zeros((batch, h_kv, max_len, hd), cfg.dtype)
        return {"k": [zeros] * cfg.n_layers, "v": [zeros] * cfg.n_layers,
                "len": jnp.zeros((), jnp.int32)}

    def _decode_attention(self, layer, x, k_cache, v_cache, pos):
        """One-token attention against the cache. x: (b, 1, d); pos: ()
        current position. Returns (out, new_k_cache, new_v_cache)."""
        cfg = self.cfg
        b, _, d = x.shape
        h = cfg.n_heads
        hd = d // h
        h_kv = cfg.n_kv_heads if cfg.n_kv_heads is not None else h
        max_len = k_cache.shape[2]

        q, k, v = self._project_qkv(layer, x, pos[None])
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, 0, pos, 0))

        kx, vx = k_cache, v_cache
        if h_kv != h:
            kx = jnp.repeat(kx, h // h_kv, axis=1)
            vx = jnp.repeat(vx, h // h_kv, axis=1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kx,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        valid = jax.lax.broadcasted_iota(jnp.int32, (1, max_len), 1) <= pos
        scores = jnp.where(valid[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vx,
                         preferred_element_type=jnp.float32)
        out = out.transpose(0, 2, 1, 3).reshape(b, 1, d).astype(x.dtype)
        return out @ layer["wo"].astype(x.dtype), k_cache, v_cache

    def _step_hidden(self, params, cache, token):
        """One cached step WITHOUT the unembedding: returns the final
        hidden row (b, 1, d) and the updated cache. Prefill uses this so
        prompt tokens never pay the O(vocab) output matmul."""
        cfg = self.cfg
        pos = cache["len"]
        x = params["embed"][token][:, None, :]
        if not cfg.use_rope:
            x = x + jax.lax.dynamic_slice_in_dim(params["pos"], pos, 1)
        x = x.astype(cfg.dtype)
        new_k, new_v = [], []
        for i, layer in enumerate(params["layers"]):
            attn, kc, vc = self._decode_attention(
                layer, self._rmsnorm(x, layer["ln1"]["scale"].astype(
                    x.dtype)), cache["k"][i], cache["v"][i], pos)
            new_k.append(kc)
            new_v.append(vc)
            x = x + attn
            x = x + self._mlp(layer, self._rmsnorm(
                x, layer["ln2"]["scale"].astype(x.dtype)))
        x = self._rmsnorm(x, params["ln_f"]["scale"].astype(x.dtype))
        return x, {"k": new_k, "v": new_v, "len": pos + 1}

    def decode_step(self, params, cache, token):
        """Feed one token (b,) int32 at cache['len']; returns (logits
        (b, vocab) f32, updated cache)."""
        x, cache = self._step_hidden(params, cache, token)
        return (x.astype(jnp.float32) @ params["embed"].T)[:, 0], cache

    def generate(self, params, prompt, max_new: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 key=None):
        """Decoding: prompt (b, t_p) int32 -> (b, t_p + max_new).
        temperature == 0 (default) is greedy; > 0 samples from the
        softmax at that temperature, optionally truncated to the top_k
        logits, using `key` (required when sampling). Prefill streams
        prompt tokens through the cached step (exactly the path new
        tokens use, minus the unembedding); generation runs under
        lax.scan, so the whole loop compiles to one program."""
        if max_new == 0:
            return prompt
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if temperature > 0.0 and key is None:
            raise ValueError("sampling (temperature > 0) requires `key`")
        if key is None:
            key = jax.random.PRNGKey(0)  # unused on the greedy path
        b, t_p = prompt.shape
        cache = self.init_cache(b, t_p + max_new)

        def pick(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1)
            logits = logits / temperature
            if top_k is not None:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1)

        def prefill(cache, tok):
            _, cache = self._step_hidden(params, cache, tok)
            return cache, None

        # All but the last prompt token only warm the cache; the last one
        # produces the first generated token.
        cache, _ = jax.lax.scan(prefill, cache, prompt[:, :-1].T)
        logits, cache = self.decode_step(params, cache, prompt[:, -1])
        key, sub = jax.random.split(key)
        next_tok = pick(logits, sub)

        def step(carry, _):
            cache, tok, key = carry
            logits, cache = self.decode_step(params, cache, tok)
            key, sub = jax.random.split(key)
            new = pick(logits, sub)
            return (cache, new, key), new

        (_, _, _), later = jax.lax.scan(step, (cache, next_tok, key), None,
                                        length=max_new - 1)
        toks = jnp.concatenate([next_tok[:, None], later.T], axis=1)
        return jnp.concatenate([prompt, toks], axis=1)
