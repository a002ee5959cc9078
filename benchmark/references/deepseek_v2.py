"""DeepSeek-V2 in plain jax.numpy: the weights from the seed, and the
reference training steps that decide `correct`.

Nothing here imports gloo_tpu. `init_params` makes the weights the
benchmark hands to the program, in the layout of gloo_tpu's DeepSeekV2;
the reference makes them again from the seed. It follows DeepSeek-V2
(arXiv:2405.04434) §2.1 (MLA) and §2.2 (DeepSeekMoE, the sequence-wise
balance loss) with the departures the configuration lists under
`assumed`. It computes in float32 at `highest` precision: attention
materialized in query blocks of 1024 against the keys up to the block's
end, v at its own 128; the routed experts that are held computed densely
on every token and masked by the routing weight (no sort, no grouped
matmul, no exchange), one expert at a time in a loop (`lax.scan`). It
runs one sequence at a time and layer by layer, each layer's backward
running its forward again from the layer's input, so that the cut model
with AdamW state fits on one chip; every layer of a kind is one compiled
program, small enough for the persistent compile cache.

The held experts are the first `n_routed_experts` of the router's
`router_experts`; on `chips` chips each holds an equal consecutive block.
`quant="fp8"` is the control: every matmul operand rounded to fp8 under
a per-tensor scale (e4m3 forward, e5m2 for the cotangents).
"""

from __future__ import annotations

import functools
import json
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

# seed_words and leaf_norms are this module's interface too, as in gpt2's.
from benchmark.references.gpt2 import (_adamw, _fp8, key_from_seed,  # noqa
                                       leaf_norms, seed_words)

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024


def init_params(cfg: dict, words):
    """Normal std `init.std` (0.006, DeepSeek-V2 §3.1.2), norm scales 1."""
    d, h, f = cfg["n_embd"], cfg["n_head"], cfg["n_inner"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    fe, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = cfg["n_shared_experts"] * fe
    std = cfg["init"]["std"]
    keys = iter(jax.random.split(key_from_seed(words), 64 * cfg["n_layer"]))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def mlp(width):
        return {"w_gate": normal(d, width), "w_up": normal(d, width),
                "w_down": normal(width, d)}

    embed = normal(cfg["vocab_size"], d)
    layers = []
    for i in range(cfg["n_layer"]):
        layer = {"attn_norm": ones(d),
                 "mla": {"wq": normal(d, h * (dn + dr)),
                         "wkv_a": normal(d, r + dr), "kv_norm": ones(r),
                         "wkv_b": normal(r, h * (dn + dv)),
                         "wo": normal(h * dv, d)},
                 "ffn_norm": ones(d)}
        if i < cfg["first_dense_layers"]:
            layer["mlp"] = mlp(f)
        else:
            layer["moe"] = {
                "router": normal(d, cfg["router_experts"]),
                "shared": mlp(fs),
                "experts": {"w_gate": normal(e, d, fe),
                            "w_up": normal(e, d, fe),
                            "w_down": normal(e, fe, d)}}
        layers.append(layer)
    return {"embed": embed, "layers": layers, "ln_f": ones(d),
            "head": normal(d, cfg["vocab_size"])}


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


def _swiglu(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"], quant))
               * _mm(x, p["w_up"], quant), p["w_down"], quant)


def yarn(cfg: dict, t: int):
    """cos, sin (t, rope_dim / 2) of DeepSeek's YaRN rotary embedding, and
    the softmax scale 1/sqrt(qk width) x mscale^2. cos/sin are scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    factor, orig = y["factor"], y["original_max_position_embeddings"]

    def correction(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0

    low = max(math.floor(correction(y["beta_fast"])), 0)
    high = min(math.ceil(correction(y["beta_slow"])), dim - 1)
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / base ** (i / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001),
                   0.0, 1.0)
    inv_freq = extra / factor * ramp + extra * (1.0 - ramp)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq
    m = mscale(y["mscale"]) / mscale(y["mscale_all_dim"])
    scale = mscale(y["mscale_all_dim"]) ** 2 / math.sqrt(
        cfg["qk_nope_head_dim"] + dim)
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32), scale)


def _rope(x, cos, sin):
    """Rotate-half RoPE over x's last dim; x (..., t, d)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale, start, quant):
    """Causal attention of the queries at start .. start + len(q) - 1
    over the keys up to the last of them; (rows, heads, t, width)."""
    nq, nk = q.shape[2], k.shape[2]
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) * scale
    causal = (start + jnp.arange(nq))[:, None] >= jnp.arange(nk)[None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return _mm(p, v, quant)


def _mla(p, x, cfg, rope, quant):
    cos, sin, scale = rope
    rows, t, _ = x.shape
    h, r = cfg["n_head"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = _mm(x, p["wq"], quant).reshape(rows, t, h, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], -1)
    kv_a = _mm(x, p["wkv_a"], quant)
    latent = _rmsnorm(kv_a[..., :r], p["kv_norm"]["scale"])
    k_pe = _rope(kv_a[..., r:], cos, sin)[:, None]          # shared by heads
    kv = _mm(latent, p["wkv_b"], quant).reshape(rows, t, h, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (rows, h, t, dr))], -1)
    v = kv[..., dn:]
    block = min(QUERY_BLOCK, t)
    attend = jax.checkpoint(functools.partial(_attend, quant=quant),
                            static_argnums=(4,))
    out = jnp.concatenate([
        attend(q[:, :, s:s + block], k[:, :, :s + block],
               v[:, :, :s + block], scale, s)
        for s in range(0, t, block)], axis=2)
    out = out.transpose(0, 2, 1, 3).reshape(rows, t, h * dv)
    return _mm(out, p["wo"], quant)


def route(cfg: dict, router, x):
    """The gate: f32 softmax over every router expert, greedy top-k, the
    unnormalised scores times routed_scaling_factor. Returns probs
    (..., G), the weights and the chosen experts (..., k)."""
    probs = jax.nn.softmax(jnp.matmul(x, router, precision=HIGHEST), -1)
    w, top = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return probs, w * cfg["routed_scaling_factor"], top


def routed_experts(cfg, experts, x, weight, top, quant=None,
                   held=None):
    """Σ over the held experts e of weight(token, e) SwiGLU_e(x), each
    expert on every token, masked by its weight; `held` (E,) bool leaves
    experts out (all held by default). x (..., t, d)."""
    n = experts["w_gate"].shape[0]
    held = jnp.ones((n,), bool) if held is None else held

    @jax.checkpoint
    def one(out, e):
        w = {k: jax.lax.dynamic_index_in_dim(v, e, keepdims=False)
             for k, v in experts.items()}
        gate = jnp.where(held[e], jnp.sum(jnp.where(top == e, weight, 0.0),
                                          axis=-1), 0.0)
        return out + gate[..., None] * _swiglu(w, x, quant), None

    return jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(n))[0]


def balance_loss(cfg, probs, top):
    """α Σ_i f_i P_i per sequence, the mean over sequences (`seq_aux`)."""
    g = probs.shape[-1]
    t, k = top.shape[-2], top.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(top, g, dtype=jnp.float32), axis=(-3, -2))
    f = chosen * g / (k * t)
    return cfg["aux_loss_alpha"] * jnp.mean(
        jnp.sum(f * jnp.mean(probs, axis=-2), axis=-1))


def _capacity(top, cfg):
    """The old fixed-capacity dispatch: each expert keeps the first
    T k / G assignments of the row block, in token order."""
    g = cfg["router_experts"]
    rows, t, k = top.shape
    hot = jax.nn.one_hot(top.reshape(rows, t * k), g, dtype=jnp.int32)
    place = jnp.cumsum(hot, axis=1) * hot - hot
    keep = jnp.sum(place, axis=-1) < t * k // g
    return keep.reshape(rows, t, k)


def _block(layer, x, held, cfg, quant, fault):
    """One decoder layer on x (rows, t, d): its output and its balance
    loss (0 for the dense layer)."""
    x = x + _mla(layer["mla"], _rmsnorm(x, layer["attn_norm"]["scale"]),
                 cfg, yarn(cfg, x.shape[1]), quant)
    h = _rmsnorm(x, layer["ffn_norm"]["scale"])
    if "mlp" in layer:
        return x + _swiglu(layer["mlp"], h, quant), jnp.float32(0)
    moe = layer["moe"]
    probs, w, top = route(cfg, moe["router"], h)
    if fault == "capacity_drop":
        w = jnp.where(_capacity(top, cfg), w, 0.0)
    y = routed_experts(cfg, moe["experts"], h, w, top, quant, held)
    return (x + _swiglu(moe["shared"], h, quant) + y,
            balance_loss(cfg, probs, top))


def _head_loss(top, x, targets, quant):
    """Mean next-token cross-entropy of the final norm and the head on
    the last layer's output."""
    x = _rmsnorm(x, top["ln_f"]["scale"])
    logp = jax.nn.log_softmax(_mm(x, top["head"], quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _grad(progs, params, tokens, targets, held, rows_per_block):
    """The loss (mean next-token cross-entropy plus the MoE layers'
    balance losses) and its gradient, each summed over blocks of
    `rows_per_block` rows, and the number of blocks: the mean of equal
    blocks' means is the mean over all rows. `held` (rows, E) says which
    experts each row's tokens may reach.

    A block runs layer by layer: every layer forward, keeping its input;
    the head's loss and gradient; then every layer's backward from its
    input (its forward again, then the transpose). Each kind of layer is
    one compiled program, whatever the depth, so the programs stay small
    enough for the persistent compile cache."""
    acc = jax.tree.map(jnp.zeros_like, params)
    total = jnp.float32(0)
    n = tokens.shape[0] // rows_per_block
    for b in range(n):
        rows = slice(b * rows_per_block, (b + 1) * rows_per_block)
        tok, h = tokens[rows], held[b * rows_per_block]
        xs = [progs.embed(params["embed"], tok)]
        for layer in params["layers"]:
            x, aux = progs.forward(layer, xs[-1], h)
            xs.append(x)
            total = total + aux
        top = {k: params[k] for k in ("ln_f", "head")}
        l, acc_top, dx = progs.head(top, xs.pop(), targets[rows],
                                    {k: acc[k] for k in top})
        acc.update(acc_top)
        total = total + l
        for i in reversed(range(len(params["layers"]))):
            acc["layers"][i], dx = progs.backward(
                params["layers"][i], xs.pop(), h, dx, acc["layers"][i])
        acc["embed"] = progs.embed_back(tok, dx, acc["embed"])
    return total, acc, n


def select_rows(tokens, targets, fault, chips):
    """The rows a planted fault leaves the gradient with, and the chip
    each row's tokens start on."""
    rows = tokens.shape[0]
    chip = np.arange(rows) // max(rows // chips, 1)
    if fault == "half_batch":
        return tokens[:rows // 2], targets[:rows // 2], chip[:rows // 2]
    if fault == "no_exchange":
        keep = rows // chips
        return tokens[:keep], targets[:keep], chip[:keep]
    return tokens, targets, chip


def readings(cfg: dict, words, batches, steps: int = 3, quant=None,
             fault=None, chips: int = 1, rows_per_block: int = 1):
    """Run `steps` AdamW steps from the seed's weights on `batches` ((n,
    rows, seq + 1) int32, inputs then shifted targets) and return what
    `correct` compares: each step's loss, every leaf's norm of the first
    gradient and of the parameters' change after the last step.

    `fault` plants one of the faults a training cell can have, in the
    reference put in the program's place: `half_batch` (the gradient is
    the mean over half the rows), `no_exchange` (chip 0's rows alone,
    divided by the chip count, as if the gradient all-reduce were gone),
    `answer_altered` (each loss reported 1% high), `capacity_drop` (each
    expert keeps only the first T k / G of a row's assignments, the rest
    dropped, as a fixed-capacity dispatch does), `local_experts_only`
    (each chip's tokens reach only the experts that chip holds)."""
    progs = _programs(json.dumps(cfg, sort_keys=True), quant,
                      fault if fault == "capacity_drop" else None)
    divide = chips if fault == "no_exchange" else 1
    e = cfg["n_routed_experts"]
    params = progs.init(words)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for s in range(steps):
        seq = batches[s]
        tokens, targets, chip = select_rows(seq[:, :-1], seq[:, 1:], fault,
                                            chips)
        held = np.ones((len(chip), e), bool)
        if fault == "local_experts_only":
            held = (np.arange(e)[None] // (e // chips)) == chip[:, None]
        total, acc, n = _grad(progs, params, tokens, targets,
                              jnp.asarray(held), rows_per_block)
        params, m, v, g = progs.update(params, m, v, jnp.float32(s + 1), acc,
                                       jnp.float32(n * divide))
        del acc  # before the next step makes its own
        losses.append(float(total) / n
                      * (1.01 if fault == "answer_altered" else 1))
        if grad_norms is None:
            grad_norms = np.asarray(g)
    del m, v
    delta = np.asarray(progs.change(params, words))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": delta}


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, quant, in_loss):
    """The reference's jitted programs, one set for each variant
    (`in_loss`: the fault planted in the loss itself), so that a process
    compiles each once: init, the embedding's gather and its transpose,
    a layer's forward and its backward (one program a kind of layer), the
    head's loss and gradient, the AdamW update, the change."""
    cfg = json.loads(cfg_json)
    hp = cfg["optimizer"]
    block = functools.partial(_block, cfg=cfg, quant=quant, fault=in_loss)

    @functools.partial(jax.jit, donate_argnums=(4,))
    def backward(layer, x, held, dy, acc):
        _, pull = jax.vjp(lambda p, x: block(p, x, held), layer, x)
        grad, dx = pull((dy, jnp.float32(1)))
        return jax.tree.map(jnp.add, acc, grad), dx

    @functools.partial(jax.jit, donate_argnums=(3,))
    def head(top, x, targets, acc):
        l, (grad, dx) = jax.value_and_grad(_head_loss, argnums=(0, 1))(
            top, x, targets, quant)
        return l, jax.tree.map(jnp.add, acc, grad), dx

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, m, v, count, acc, scale):
        g = jax.tree.map(lambda a: a / scale, acc)
        params, m, v = _adamw(params, g, m, v, count, hp)
        return params, m, v, leaf_norms(g)

    @jax.jit
    def change(params, words):
        return leaf_norms(jax.tree.map(jnp.subtract, params,
                                       init_params(cfg, words)))

    return SimpleNamespace(
        init=jax.jit(functools.partial(init_params, cfg)),
        embed=jax.jit(lambda table, tokens: table[tokens]),
        embed_back=jax.jit(lambda tokens, dx, acc: acc.at[tokens].add(dx),
                           donate_argnums=(2,)),
        forward=jax.jit(block), backward=backward, head=head, update=update,
        change=change)
