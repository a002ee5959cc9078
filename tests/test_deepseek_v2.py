"""DeepSeek-V2's block and the dropless expert layer against the plain
reference (`benchmark/references/deepseek_v2.py`), at small widths on
virtual CPU devices: MLA through the flash kernel (interpreted), YaRN's
frequencies, the expert layer on one and four devices with every or some
experts held and with skewed routing, the share test, the ragged
all-to-all's emulation, and the DDP step with expert leaves split."""

import copy
import functools
import json
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
optax = pytest.importorskip("optax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
from jax.test_util import check_grads  # noqa: E402

from benchmark.references import deepseek_v2 as ref  # noqa: E402
from gloo_tpu.models import DeepSeekV2, DeepSeekV2Config  # noqa: E402
from gloo_tpu.parallel import make_ddp_train_step, moe  # noqa: E402
from gloo_tpu.tpu import spmd  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(n_layer=2, n_embd=32, n_head=2, n_inner=48, vocab_size=64,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=16,
             v_head_dim=16, moe_intermediate_size=8, n_routed_experts=8,
             router_experts=16, num_experts_per_tok=3,
             init={"std": 0.2})


def _cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dsv2-lite.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg.update(SMALL, **over)
    return cfg


def _model(cfg, axis=None, dtype=jnp.float32):
    y = cfg["rope_scaling"]
    return DeepSeekV2(DeepSeekV2Config(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        first_dense_layers=cfg["first_dense_layers"], d_ff=cfg["n_inner"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_experts=cfg["n_routed_experts"],
        router_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"],
        aux_loss_alpha=cfg["aux_loss_alpha"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], yarn_factor=y["factor"],
        yarn_original_positions=y["original_max_position_embeddings"],
        yarn_mscale_all_dim=y["mscale_all_dim"], dtype=dtype, ep_axis=axis))


def _params(cfg, seed=5):
    return ref.init_params(cfg, ref.seed_words(seed))


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def test_yarn_frequencies_and_mscale_closed_form():
    """DeepSeek's YaRN at the published settings: the correction dims for
    β_fast 32 and β_slow 1 at 4096 original positions are 10 and 23; dims
    below keep 1/θ^(2i/64), dims above are divided by 40, a linear ramp
    between; the softmax scale's mscale is 0.1 · 0.707 · ln 40 + 1."""
    model = _model(_cfg(qk_rope_head_dim=64))
    i = np.arange(32)
    extra = 1.0 / 10000.0 ** (2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    expected = extra * (1 - ramp) + extra / 40 * ramp
    np.testing.assert_allclose(np.asarray(model.inv_freq()), expected,
                               rtol=1e-6)
    cos, sin, scale = ref.yarn(_cfg(qk_rope_head_dim=64,
                                    qk_nope_head_dim=128), 8)
    np.testing.assert_allclose(np.asarray(cos),
                               np.cos(np.arange(8)[:, None] * expected),
                               rtol=1e-5, atol=1e-6)
    assert model.mscale() == pytest.approx(1.2608038)
    assert scale == pytest.approx(1.2608038 ** 2 / math.sqrt(128 + 64))


def test_mla_block_matches_reference_forward_and_grads():
    """The model's MLA (flash kernel in interpret mode, v padded to the
    qk width, q pre-scaled by mscale²) against the reference's attention
    at v's own width, f32: output and every weight's gradient."""
    cfg = _cfg()
    model = _model(cfg)
    p = _params(cfg)["layers"][0]["mla"]
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg["n_embd"]))
    rope = ref.yarn(cfg, 32)

    def prog(p):
        return jnp.sum(model._mla(p, x) ** 2)

    def plain(p):
        return jnp.sum(ref._mla(p, x, cfg, rope, None) ** 2)

    np.testing.assert_allclose(np.asarray(model._mla(p, x)),
                               np.asarray(ref._mla(p, x, cfg, rope, None)),
                               rtol=2e-4, atol=2e-5)
    got, want = jax.grad(prog)(p), jax.grad(plain)(p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def _moe_program(cfg, chips, first=0):
    """`moe` on `chips` devices, experts split over them, first held
    expert `first`: f(x, router, w_gate, w_up, w_down) -> (y, probs)."""
    k = cfg["num_experts_per_tok"]

    def local(x, router, wg, wu, wd):
        base = first + spmd.rank("data") * wg.shape[0]
        y, probs, _ = moe(x, router, wg, wu, wd, first_expert=base,
                          top_k=k, axis="data")
        return y, probs

    return jax.shard_map(
        local, mesh=_mesh(chips),
        in_specs=(P("data"), P(), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data")))


def _moe_reference(cfg, first=0):
    """The reference's dense masked sum over the same held experts."""
    def f(x, router, wg, wu, wd):
        probs, w, top = ref.route(cfg, router, x)
        experts = {"w_gate": wg, "w_up": wu, "w_down": wd}
        return ref.routed_experts(cfg, experts, x, w, top - first), probs
    return f


def _poisoned_ragged_dot(monkeypatch):
    """`lax.ragged_dot` as a TPU v5e runs it: the rows no group covers, in
    its output and in its transpose for the rows, hold NaN rather than
    the CPU's zeros."""
    plain = jax.lax.ragged_dot

    def poison(v, groups):
        past = jnp.arange(v.shape[0]) >= groups.sum()
        return jnp.where(past[:, None], jnp.nan, v)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, groups):
        return poison(plain(lhs, rhs, groups), groups)

    def ragged_dot_fwd(lhs, rhs, groups):
        return ragged_dot(lhs, rhs, groups), (lhs, rhs, groups)

    def ragged_dot_bwd(res, g):
        lhs, rhs, groups = res
        _, vjp = jax.vjp(lambda a, b: plain(a, b, groups), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return poison(d_lhs, groups), d_rhs, None

    ragged_dot.defvjp(ragged_dot_fwd, ragged_dot_bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)


ROUTES = {
    # name: (router experts held, first held, what the routing does)
    "all_held": (16, 0, None),   # on one chip every row of the buffer is held
    "first_8": (8, 0, None),
    "from_4": (8, 4, None),
    "none_held": (8, 16, None),  # experts 16-23: no assignment is held
    "uneven": (8, 4, "ramp"),    # the router favours low ids
    "nan_past_groups": (8, 0, "nan"),
}


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("route", list(ROUTES))
def test_expert_layer_matches_dense_reference(chips, route, monkeypatch):
    """Every token's routed experts' weighted sum, with all 16 router
    experts held or 8 of them (0-7, 4-11, none), with groups of very
    different sizes, and with the grouped matmul leaving NaN past its
    groups as a TPU does: the program's sort, ragged exchange, grouped
    matmul and combine give the reference's dense masked sum, its router
    probabilities, and the gradients of both with respect to x, the router
    and all three expert weights. With nothing held the routed part and
    every gradient through it are exactly 0."""
    held, first, how = ROUTES[route]
    cfg = _cfg(n_routed_experts=held)
    moe_p = _params(cfg)["layers"][1]["moe"]
    e = moe_p["experts"]
    x = np.array(jax.random.normal(jax.random.key(2),
                                   (chips * 24, cfg["n_embd"])))
    router = np.array(moe_p["router"])
    if how == "ramp":
        x[:, 0] = 3.0
        router[0] = np.linspace(0.5, -0.5, router.shape[1])
    if how == "nan":
        _poisoned_ragged_dot(monkeypatch)
    args = (jnp.asarray(x), jnp.asarray(router), e["w_gate"], e["w_up"],
            e["w_down"])
    ct = jax.random.normal(jax.random.key(7), x.shape)
    ct_probs = jax.random.normal(jax.random.key(8),
                                 (x.shape[0], router.shape[1]))

    def loss(f, with_probs=True):
        def value(*a):
            y, probs = f(*a)
            return jnp.sum(y * ct) + with_probs * jnp.sum(probs * ct_probs)
        return jax.jit(jax.grad(value, argnums=range(5)))

    program = _moe_program(cfg, chips, first)
    reference = _moe_reference(cfg, first)
    (got, got_probs), (want, want_probs) = (jax.jit(f)(*args)
                                            for f in (program, reference))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_probs, want_probs, rtol=1e-5, atol=1e-7)
    for a, b, name in zip(loss(program)(*args), loss(reference)(*args),
                          ["x", "router", "w_gate", "w_up", "w_down"]):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max(), err_msg=name)
    if how == "ramp":
        _, _, top = ref.route(cfg, jnp.asarray(router), jnp.asarray(x))
        sizes = np.bincount(np.asarray(top).ravel(), minlength=16)[4:12]
        assert sizes.max() >= 4 * max(sizes.min(), 1), sizes
    if route == "none_held":
        assert not np.asarray(got).any()
        assert not any(np.asarray(g).any()
                       for g in loss(program, with_probs=False)(*args))


def test_expert_layer_skewed_routing_drops_nothing():
    """Every token of every chip routed to chip 0's two experts: chip 0
    receives 4 x T x 2 rows, the worst case its buffer is sized for, and
    still gives the reference's result, where a capacity of T k / G a
    chip and expert would have dropped all but 1 in 8 of them."""
    cfg = _cfg(num_experts_per_tok=2)
    moe_p = _params(cfg)["layers"][1]["moe"]
    d, g = cfg["n_embd"], cfg["router_experts"]
    router = np.zeros((d, g), np.float32)
    router[0, :2] = [8.0, 7.0]              # feature 0 picks experts 0, 1
    router[1:, 2:] = np.asarray(moe_p["router"])[1:, 2:] * 0.01
    x = np.array(jax.random.normal(jax.random.key(3), (4 * 16, d)))
    x[:, 0] = 4.0
    _, _, top = ref.route(cfg, jnp.asarray(router), jnp.asarray(x))
    assert set(np.unique(np.asarray(top))) == {0, 1}
    e = moe_p["experts"]
    args = (jnp.asarray(x), jnp.asarray(router), e["w_gate"], e["w_up"],
            e["w_down"])
    got = jax.jit(_moe_program(cfg, 4))(*args)[0]
    want = _moe_reference(cfg)(*args)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert np.abs(want).min(axis=1).max() > 0


@pytest.mark.parametrize("part", ["combine", "sort_rows"])
def test_permutation_vjps_check_grads(part):
    """The custom VJPs of the combine and of the rows' sort against finite
    differences at tiny width, 5 of 12 assignments held: the combine with
    respect to the sorted rows and the weights, the sort with respect to
    x through the rows that are held (the others are never read back)."""
    from gloo_tpu.parallel.ep import _combine, _sort_rows

    t, k, d, held = 4, 3, 5, 4
    key = jnp.asarray([0, 4, 2, 4, 4, 1, 3, 4, 4, 0, 4, 4])   # 4: not held
    mine = (key < held).reshape(t, k)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order).reshape(t, k)
    live = jnp.arange(t * k) < mine.sum()
    rng = np.random.RandomState(0)
    if part == "combine":
        f = functools.partial(_combine, order=order, inverse=inverse,
                              mine=mine)
        args = (jnp.asarray(rng.randn(t * k, d), jnp.float32),
                jnp.where(mine, jnp.asarray(rng.rand(t, k), jnp.float32), 0))
    else:
        def f(x):
            rows = _sort_rows(x, order, inverse, mine)
            return jnp.where(live[:, None], rows, 0.0)
        args = (jnp.asarray(rng.randn(t, d), jnp.float32),)
    check_grads(f, args, order=1, modes=("rev",), eps=0.5)


def test_share_test_eight_shares_make_the_uncut_layer():
    """Guide §4's share test: at 64 router experts, eight shares of 8
    held experts each (the program's layer told which it holds) plus the
    shared experts counted once equal the uncut 64-expert layer of the
    reference."""
    cfg = _cfg(router_experts=64, n_routed_experts=64,
               num_experts_per_tok=6)
    moe_p = _params(cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.key(4), (24, cfg["n_embd"]))
    shared = np.asarray(ref._swiglu(moe_p["shared"], x, None))
    total = shared.copy()
    for s in range(8):
        part = {k: v[8 * s:8 * s + 8] for k, v in moe_p["experts"].items()}
        y, _, _ = moe(x, moe_p["router"], part["w_gate"], part["w_up"],
                      part["w_down"], first_expert=8 * s, top_k=6)
        total += np.asarray(y)
    _, w, top = ref.route(cfg, moe_p["router"], x)
    uncut = shared + np.asarray(ref.routed_experts(cfg, moe_p["experts"], x,
                                                   w, top))
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pattern", ["random", "empty", "full"])
def test_ragged_alltoall_emulation_is_a_permutation(pattern):
    """Rows grouped by (destination, slot) land on their destination
    slot-major, sources in order inside a slot, zeros after; the reverse
    puts every row back. `empty`: chips 1 and 3 send nothing; `full`:
    every chip sends all its rows to chip 2's slot 1."""
    n, s, r = 4, 2, 12
    rng = np.random.RandomState(0)
    counts = rng.randint(0, 3, (n, n, s))
    if pattern == "empty":
        counts[[1, 3]] = 0
    if pattern == "full":
        counts[:] = 0
        counts[:, 2, 1] = r
    rows = np.zeros((n, r, 3), np.float32)
    for i in range(n):
        tags = [[i, j * 10 + l, c] for j in range(n) for l in range(s)
                for c in range(counts[i, j, l])]
        rows[i, :len(tags)] = np.reshape(tags, (-1, 3))

    def f(x, c):
        got, recv = spmd.ragged_alltoall(x[0], c[0], "data")
        back = spmd.ragged_alltoall_reverse(got, c[0], "data", r)
        return got[None], recv[None], back[None]

    got, recv, back = map(np.asarray, jax.jit(jax.shard_map(
        f, mesh=_mesh(n), in_specs=(P("data"), P("data")),
        out_specs=(P("data"),) * 3))(rows, counts.astype(np.int32)))
    assert got.shape == (n, n * r, 3)
    for j in range(n):
        want = np.asarray([[i, j * 10 + l, c] for l in range(s)
                           for i in range(n) for c in range(counts[i, j, l])],
                          np.float32).reshape(-1, 3)
        np.testing.assert_array_equal(got[j, :len(want)], want)
        assert not got[j, len(want):].any()
        np.testing.assert_array_equal(recv[j], counts[:, j])
    for i in range(n):
        sent = counts[i].sum()
        np.testing.assert_array_equal(back[i, :sent], rows[i, :sent])
        assert not back[i, sent:].any()


def test_ddp_step_with_split_experts_gives_the_mean_loss_gradient():
    """make_ddp_train_step over 4 devices with the routed experts on
    P("data"), 2 a device: one SGD step at lr 1 moves every leaf, split or
    replicated, by the gradient of the mean loss over the whole batch
    that one device computes with every expert at hand; the split leaves
    come back split."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.key(6), (8, 33), 0,
                                cfg["vocab_size"])
    batch = (tokens[:, :-1], tokens[:, 1:])
    mesh = _mesh(4)
    model = _model(cfg, axis="data")
    specs = model.param_specs("data")
    opt = optax.sgd(1.0)
    step = make_ddp_train_step(model.loss, opt, mesh, param_specs=specs)
    new, _, loss = step(params, opt.init(params), batch)
    whole = _model(cfg)
    want_loss, grads = jax.value_and_grad(whole.loss)(params, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    moved = jax.tree.map(lambda a, b: np.asarray(a - b), params, new)
    for path, m in jax.tree_util.tree_leaves_with_path(moved):
        g = np.asarray(functools.reduce(lambda t, k: t[k.key if hasattr(
            k, "key") else k.idx], path, grads))
        np.testing.assert_allclose(m, g, rtol=2e-3, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    expert = new["layers"][1]["moe"]["experts"]["w_gate"]
    assert expert.sharding.spec == P("data")
