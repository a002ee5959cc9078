"""GPT-2 in plain jax.numpy: the weights from the seed, and the reference
training steps that decide `correct`.

Nothing here imports gloo_tpu. `init_params` makes the weights the
benchmark hands to the program (the program's own init is not used), in
the layout of gloo_tpu's Transformer; the reference makes them again from
the seed. The reference follows the published GPT-2 block with the
departures the configuration lists under `assumed`: RMSNorm with a scale
and no biases, tied embeddings, a padded vocabulary. It computes in
float32 at `highest` precision with the attention matrix materialized,
one sequence at a time, each layer rematerialized, so it fits beside
nothing else on one chip.

`quant="fp8"` is the control: the same steps with every matmul operand
rounded to fp8 under a per-tensor scale (e4m3 forward, e5m2 for the
cotangents), the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed_words):
    """A PRNG key from two uint32 words (seed low, seed high), so that one
    compiled program serves every seed."""
    key = jax.random.key(seed_words[0])
    return jax.random.fold_in(key, seed_words[1])


def seed_words(seed: int) -> np.ndarray:
    seed = int(seed)
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64 - 1")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], dtype=np.uint32)


def init_params(cfg: dict, words):
    """GPT-2's init: normal std 0.02, positions 0.01, the two residual
    projections of each layer std 0.02 / sqrt(2 n_layer), norm scales 1."""
    d, f, n_layer = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg["init"]["std"]
    res_std = std / math.sqrt(2 * n_layer) if cfg["init"]["residual_scaled"] \
        else std
    keys = jax.random.split(key_from_seed(words), 2 + n_layer)

    def normal(k, shape, s):
        return jax.random.normal(k, shape, jnp.float32) * s

    layers = []
    for i in range(n_layer):
        lk = jax.random.split(keys[2 + i], 4)
        layers.append({
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "wqkv": normal(lk[0], (d, 3 * d), std),
            "wo": normal(lk[1], (d, d), res_std),
            "w_up": normal(lk[2], (d, f), std),
            "w_down": normal(lk[3], (f, d), res_std),
        })
    return {
        "embed": normal(keys[0], (cfg["vocab_size"], d), std),
        "pos": normal(keys[1], (cfg["n_positions"], d),
                      cfg["init"]["pos_std"]),
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
        "layers": layers,
    }


# ---- fp8 rounding for the control ----------------------------------------

def _round_scaled(x, dtype):
    """Round x to `dtype` under a per-tensor scale that maps max|x| to the
    format's largest finite value, and back to float32."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _round_scaled(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_round_scaled(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


# ---- the model -----------------------------------------------------------

def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


def _gelu(x):
    # GPT-2's gelu_new (the tanh approximation).
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(layer, x, n_head, quant):
    r, t, d = x.shape
    hd = d // n_head
    h = _rmsnorm(x, layer["ln1"]["scale"])
    qkv = _mm(h, layer["wqkv"], quant)
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(r, t, n_head, hd)
               .transpose(0, 2, 1, 3) for i in range(3))
    scores = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm(probs, v, quant).transpose(0, 2, 1, 3).reshape(r, t, d)
    x = x + _mm(att, layer["wo"], quant)
    h = _rmsnorm(x, layer["ln2"]["scale"])
    return x + _mm(_gelu(_mm(h, layer["w_up"], quant)), layer["w_down"],
                   quant)


def loss(params, tokens, targets, n_head: int, quant=None):
    """Mean next-token cross-entropy over (rows, seq) tokens."""
    t = tokens.shape[1]
    x = params["embed"][tokens] + params["pos"][:t]
    block = jax.checkpoint(functools.partial(_block, n_head=n_head,
                                             quant=quant))
    for layer in params["layers"]:
        x = block(layer, x)
    x = _rmsnorm(x, params["ln_f"]["scale"])
    logits = _mm(x, params["embed"].T, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def _rows_grad(params, tokens, targets, n_head, quant, rows_per_block):
    """Loss and gradient over all rows, a block of rows at a time: the
    mean of equal blocks' means is the mean over all rows."""
    n = tokens.shape[0] // rows_per_block
    tb = tokens.reshape(n, rows_per_block, -1)
    yb = targets.reshape(n, rows_per_block, -1)
    vg = jax.value_and_grad(functools.partial(loss, n_head=n_head,
                                              quant=quant))

    def body(acc, blk):
        l, g = vg(params, blk[0], blk[1])
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(body, zero, (tb, yb))
    return l / n, jax.tree.map(lambda a: a / n, g)


def _adamw(params, grads, m, v, count, hp):
    """optax.adamw's arithmetic, written out: Adam moments with bias
    correction, decoupled weight decay on every leaf, constant lr."""
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count

    def new(p, a, b):
        u = (a / c1) / (jnp.sqrt(b / c2) + hp["eps"]) + hp["weight_decay"] * p
        return p - hp["lr"] * u

    return jax.tree.map(new, params, m, v), m, v


def leaf_norms(tree):
    """The L2 norm of every leaf, in jax.tree.leaves order, in float32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def select_rows(tokens, targets, fault, chips):
    """The rows a planted fault leaves the gradient with: half the batch
    (`half_batch`), or chip 0's rows alone (`no_exchange`)."""
    rows = tokens.shape[0]
    if fault == "half_batch":
        return tokens[:rows // 2], targets[:rows // 2]
    if fault == "no_exchange":
        return tokens[:rows // chips], targets[:rows // chips]
    return tokens, targets


def readings(cfg: dict, words, batches, steps: int = 3, quant=None,
             fault=None, chips: int = 1, rows_per_block: int = 1):
    """Run `steps` AdamW steps from the seed's weights on `batches`
    (a (n, rows, seq + 1) int32 device array, inputs then shifted
    targets), one after the other, and return what `correct` compares:
    each step's loss, every leaf's norm of the first gradient and of the
    parameters' change after the last step.

    `fault` plants one of the faults a training cell can have, in the
    reference put in the program's place: `half_batch` (the gradient is
    the mean over half the rows), `no_exchange` (each chip keeps its own
    rows' gradient, divided by the chip count as the program divides the
    sum it no longer gets), `answer_altered` (each loss reported 1% high
    where the step produces it)."""
    init, step, change = _programs(json.dumps(cfg, sort_keys=True), quant,
                                   chips if fault == "no_exchange" else 1,
                                   rows_per_block)
    params = init(words)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for s in range(steps):
        seq = batches[s]
        tokens, targets = select_rows(seq[:, :-1], seq[:, 1:], fault, chips)
        params, m, v, l, g = step(params, m, v, jnp.float32(s + 1), tokens,
                                  targets)
        losses.append(float(l) * (1.01 if fault == "answer_altered" else 1))
        if grad_norms is None:
            grad_norms = np.asarray(g)
    del m, v
    delta = np.asarray(change(params, words))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": delta}


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, quant, divide: int, rows_per_block: int):
    """The reference's jitted init, step and change, one set for each
    variant, so that a process compiles each once."""
    cfg = json.loads(cfg_json)
    hp = cfg["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, tokens, targets):
        l, g = _rows_grad(params, tokens, targets, cfg["n_head"], quant,
                          rows_per_block)
        g = jax.tree.map(lambda a: a / divide, g)
        params, m, v = _adamw(params, g, m, v, count, hp)
        return params, m, v, l, leaf_norms(g)

    @jax.jit
    def change(params, words):
        return leaf_norms(jax.tree.map(jnp.subtract, params,
                                       init_params(cfg, words)))

    return jax.jit(functools.partial(init_params, cfg)), step, change
