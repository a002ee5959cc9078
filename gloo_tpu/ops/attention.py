"""Pallas flash attention (single device).

The MXU-side companion to the collective kernels: attention computed
without materializing the (T, T) score matrix. The grid walks
(batch*heads, query-block, key-block) with the key-block dimension
innermost; the online-softmax state (accumulator, running max, running
denominator) lives in VMEM scratch that persists across the sequential
grid steps, so only ONE (block_q, d) query tile and ONE (block_k, d)
key/value tile are resident at a time — sequence length is bounded by
HBM, not VMEM. Same math as the cross-chip ring attention in
gloo_tpu.parallel.sp, applied at the tile level.

Causal masking: key blocks entirely above the diagonal skip their
compute, and a clamped kv index map repeats the last valid tile on dead
grid steps so the pipeline elides their fetches; tiles straddling the
diagonal pay the mask, fully-valid interior tiles run mask-free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The names of the flash forward's two residuals, out and logsumexp; the
# policy that keeps them (and only them) through `jax.checkpoint`.
FLASH_OUT = "gloo_tpu.flash.out"
FLASH_LSE = "gloo_tpu.flash.lse"
save_flash_residuals = jax.checkpoint_policies.save_only_these_names(
    FLASH_OUT, FLASH_LSE)


def _score_tile_global(q_ref, k_ref, q_base, k_base, block_q, block_k,
                       causal, scale):
    """THE tile computation: scaled scores with the causal mask applied,
    with the tile's rows at q_base.. and columns at k_base.. in the full
    sequence (bases may be dynamic SMEM scalars for ring-rotated blocks).
    Every kernel — forward, backward, step — must go through this single
    definition: the backward kernels recompute softmax from the forward's
    saved logsumexp, so any drift silently skews gradients.

    The dot runs in the inputs' native dtype (bf16 inputs hit the MXU at
    its native rate) with f32 accumulation. `scale` is folded into the q
    tile — a (block_q, d) multiply — rather than the (block_q, block_k)
    scores: the kernels are VPU-bound, so every per-score-element op
    counts. Returns the scaled q tile (backward kernels contract against
    it, so dK inherits the scale for free)."""
    q = q_ref[0] * scale
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        q_pos = q_base + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_base + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
    return q, s


def _score_tile(q_ref, k_ref, qi, kb, block_q, block_k, causal, scale):
    """Local-sequence view of _score_tile_global (block indices, not
    positions)."""
    return _score_tile_global(q_ref, k_ref, qi * block_q, kb * block_k,
                              block_q, block_k, causal, scale)


def _softmax_tile(s, lse):
    # Masked entries hold -inf and lse is finite (every query row sees at
    # least its diagonal key globally), so exp(-inf - lse) underflows to
    # exactly 0 — no explicit guard needed on the VPU-bound hot path.
    return jnp.exp(s - lse)


def _online_step(s, v, m, l, acc):
    """One online-softmax update shared by the forward and step kernels.

    Handles m == -inf (initial state / fully masked rows so far) via the
    m_safe/corr guards; masked score entries are -inf and their exp
    underflows to 0 against the finite m_safe, so no per-element guard is
    spent on them."""
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + p.sum(axis=1, keepdims=True)
    # p contracts on the MXU in v's dtype (matches the reference oracle,
    # which also casts softmax weights to the input dtype).
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, block_q: int, block_k: int, causal: bool,
                  scale: float):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked):
        _, s = _score_tile(q_ref, k_ref, qi, kb, block_q, block_k, masked,
                           scale)
        acc_ref[...], m_ref[...], l_ref[...] = _online_step(
            s, v_ref[0], m_ref[...], l_ref[...], acc_ref[...])

    if causal:
        # Split by tile kind: only tiles straddling the diagonal pay the
        # iota/compare/select mask; interior (fully valid) tiles — the
        # vast majority — run mask-free, and fully-masked tiles are
        # skipped outright (their fetches are elided by the clamped kv
        # index map in flash_attention).
        active = kb * block_k <= qi * block_q + block_q - 1
        interior = (kb + 1) * block_k - 1 <= qi * block_q

        @pl.when(active & jnp.logical_not(interior))
        def _():
            update(True)

        @pl.when(interior)
        def _():
            update(False)
    else:
        update(False)

    @pl.when(kb == num_k_blocks - 1)
    def _():
        o_ref[0, ...] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        # logsumexp per query row (the backward pass's softmax residual).
        lse_ref[0, ...] = (m_ref[...] +
                           jnp.log(jnp.maximum(l_ref[...], 1e-30)))


def _reference_attention(q, k, v, causal: bool):
    """Materialized-scores attention — the test parity oracle only (the
    VJP runs the dedicated Pallas backward kernels)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d))
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), jnp.bool_)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma_axes"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = None,
                    block_k: int = None, interpret: bool = False,
                    vma_axes=()):
    """Attention over (batch, heads, seq, head_dim) without materializing
    the score matrix. seq must be divisible by the block sizes; head_dim
    should be a multiple of 128 for full MXU tiles.

    block_q/block_k default to the largest divisors of seq up to
    1024/1024: the kernel's cost is dominated by per-grid-step overhead,
    not the matmuls, so big tiles win (an early v5e block sweep; its
    numbers predate the current kernel and are not a measurement of it).

    Supports grouped-query attention: k/v may carry h_kv heads with
    h % h_kv == 0. Both directions map each query head to its shared kv
    head in the BlockSpec index maps — kv tiles are NEVER replicated in
    memory; the backward's per-query-head dK/dV partials are group-summed
    in f32 before the single downcast.

    Differentiable with flash-memory in BOTH directions: the custom VJP
    runs dedicated backward kernels (dQ; dK/dV) that recompute the
    softmax tiles from the saved logsumexp rows — no (T, T)
    materialization anywhere in training.

    The forward's residuals out and logsumexp carry the names FLASH_OUT
    and FLASH_LSE (`checkpoint_name`, the identity outside
    `jax.checkpoint`). A caller that rematerializes a layer around this
    call with `policy=save_flash_residuals` keeps the two and recomputes
    the rest, so its backward runs the forward kernel once, not twice;
    the cost is both held from the forward to the backward: out at q's
    size and dtype, and the f32 (b*h, seq, 1) logsumexp, which TPU
    tiling pads to 128 lanes, i.e. b*h*seq*512 bytes.

    Inside shard_map the outputs vary over vma_axes; by default those are
    the axes q, k and v vary over, so a caller such as a model under
    make_ddp_train_step need not name them."""
    if not vma_axes:
        vma_axes = tuple(sorted(jax.typeof(q).vma | jax.typeof(k).vma
                                | jax.typeof(v).vma))
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    if v.shape[1] != h_kv:
        raise ValueError(
            f"k has {h_kv} heads but v has {v.shape[1]}")
    if h % h_kv != 0:
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {h_kv}")
    group = h // h_kv
    if block_q is None:
        block_q = largest_block(t, 1024)
    if block_k is None:
        block_k = largest_block(t, 1024)
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError(
            f"seq {t} must be divisible by block sizes {block_q}/{block_k}")
    scale = 1.0 / (d ** 0.5)

    bh = b * h
    qf = q.reshape(bh, t, d)
    kf = k.reshape(b * h_kv, t, d)
    vf = v.reshape(b * h_kv, t, d)

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale)

    @jax.custom_vjp
    def op(qf, kf, vf):
        return run_kernel(qf, kf, vf)[0]

    def fwd(qf, kf, vf):
        out, lse = run_kernel(qf, kf, vf)
        out = checkpoint_name(out, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        return out, (qf, kf, vf, out, lse)

    def bwd(residuals, g):
        qf, kf, vf, out, lse = residuals
        return _flash_backward(qf, kf, vf, out, lse, g.astype(qf.dtype),
                               causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               kv_group=group, vma_axes=vma_axes)

    op.defvjp(fwd, bwd)

    if causal:
        # Key blocks fully above the diagonal are masked out; clamping
        # their block index to the last in-range block makes consecutive
        # dead steps request the SAME tile, so the pipeline elides the
        # fetch — without this the HBM traffic for a causal forward is 2x
        # what the math needs.
        def kv_index(i, j, kb):
            last = ((j + 1) * block_q - 1) // block_k
            return (i // group, jnp.minimum(kb, last), 0)
    else:
        def kv_index(i, j, kb):
            return (i // group, kb, 0)

    def run_kernel(qf, kf, vf):
        return pl.pallas_call(
            kernel,
            interpret=interpret,
            grid=(bh, t // block_q, t // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), kv_index,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), kv_index,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                             memory_space=pltpu.VMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((bh, t, d), q.dtype,
                                     vma=frozenset(vma_axes)),
                jax.ShapeDtypeStruct((bh, t, 1), jnp.float32,
                                     vma=frozenset(vma_axes)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),  # accumulator
                pltpu.VMEM((block_q, 1), jnp.float32),  # running max
                pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                # Large tiles (the measured optimum) exceed the default
                # 16 MB scoped-vmem budget; v5e/v5p have 128 MB VMEM.
                vmem_limit_bytes=100 * 1024 * 1024),
        )(qf, kf, vf)

    return op(qf, kf, vf).reshape(b, h, t, d)


def largest_block(t: int, cap: int = 128) -> int:
    """Largest divisor of t that is a multiple of 8 and at most `cap`
    (block-size helper for arbitrary multiple-of-8 sequence lengths)."""
    best = 8
    for candidate in range(8, cap + 1, 8):
        if t % candidate == 0:
            best = candidate
    return best


# ---------------------------------------------------------------------------
# Backward kernels: dQ (query-block major) and dK/dV (key-block major).
# ---------------------------------------------------------------------------

def _flash_backward(qf, kf, vf, out, lse, g, *, causal: bool, block_q: int,
                    block_k: int, interpret: bool, kv_group: int = 1,
                    vma_axes=()):
    """Local (single-block) backward via the FUSED one-pass kernel: scores
    and dp are computed once per tile pair and feed dQ, dK, and dV
    together (5 matmuls per tile instead of the two-pass split's 7 — dQ
    accumulates in a resident f32 output block while the grid walks
    key-major). kf/vf may carry bh // kv_group heads (GQA); the
    per-query-head dK/dV partials come back in f32 and are group-summed
    BEFORE the single downcast, matching the f32 accumulation of the
    ungrouped path."""
    # delta[i] = rowsum(dO * O): cheap elementwise pass outside pallas.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq, dk, dv = flash_attention_bwd_fused(
        qf, kf, vf, g, delta, lse, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, kv_group=kv_group,
        vma_axes=vma_axes)
    dk = group_sum_kv(dk, kv_group)
    dv = group_sum_kv(dv, kv_group)
    return dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            block_q: int, block_k: int, causal: bool,
                            scale: float):
    """One-pass backward (local sequence, static offsets): grid
    (bh, key-block, query-block), both inner dims sequential. Each tile
    pair computes s / p / dp / ds ONCE and feeds all three gradients:
    dV/dK accumulate in per-key-block scratch, dQ accumulates into the
    full (t_q, d) f32 output block, which stays resident in VMEM for the
    whole batch-head group and is scaled once at the end."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    num_k_blocks = pl.num_programs(1)
    num_q_blocks = pl.num_programs(2)

    @pl.when((kb == 0) & (qi == 0))
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(masked):
        q, s = _score_tile(q_ref, k_ref, qi, kb, block_q, block_k, masked,
                           scale)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p = _softmax_tile(s, lse_ref[0])
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = qi * block_q
        dq_ref[0, pl.dslice(row, block_q), :] = (
            dq_ref[0, pl.dslice(row, block_q), :] +
            jax.lax.dot_general(ds.astype(k.dtype), k,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    if causal:
        active = qi * block_q + block_q - 1 >= kb * block_k
        interior = (kb + 1) * block_k - 1 <= qi * block_q

        @pl.when(active & jnp.logical_not(interior))
        def _():
            update(True)

        @pl.when(interior)
        def _():
            update(False)
    else:
        update(False)

    @pl.when(qi == num_q_blocks - 1)
    def _():
        # q comes back from _score_tile already scaled, so ds^T q is dK
        # directly; dQ accumulated against UNscaled k and takes the scale
        # once at the very end.
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((kb == num_k_blocks - 1) & (qi == num_q_blocks - 1))
    def _():
        dq_ref[...] = dq_ref[...] * scale


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma_axes", "kv_group"))
def flash_attention_bwd_fused(q, k, v, do, delta, lse, causal: bool = True,
                              block_q: int = None, block_k: int = None,
                              interpret: bool = False, vma_axes=(),
                              kv_group: int = 1):
    """Fused one-pass flash backward over the local sequence (the
    jax.grad path; ring steps keep flash_attention_bwd_step, whose dQ and
    dK/dV separate cleanly across rotation hops).

    q, do: (bh, t, d); k, v: (bh // kv_group, t, d); delta/lse:
    (bh, t, 1) f32. Returns (dq, dk, dv) f32, dk/dv per-QUERY-head
    partials when kv_group > 1 (caller group-sums). Causal dead tiles
    skip compute with their q-side fetches elided by clamped index maps;
    interior tiles run mask-free.

    VMEM note: the full (t, d) f32 dQ block stays resident (t=16k, d=128
    -> 8 MB), which the 100 MB scoped budget comfortably holds to
    ~100k-token sequences."""
    bh, t, d = q.shape
    if bh % kv_group != 0 or k.shape[0] != bh // kv_group:
        raise ValueError(
            f"k head count {k.shape[0]} != bh {bh} / kv_group {kv_group}")
    if block_q is None:
        block_q = largest_block(t, 1024)
    if block_k is None:
        block_k = largest_block(t, 1024)
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError("tile sizes must divide the sequence length")
    scale = 1.0 / (d ** 0.5)
    vma = frozenset(vma_axes)

    if causal:
        def q_index(i, kb, j):
            first = (kb * block_k) // block_q
            return (i, jnp.maximum(j, first), 0)
    else:
        def q_index(i, kb, j):
            return (i, j, 0)

    kernel = functools.partial(_flash_bwd_fused_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        interpret=interpret,
        grid=(bh, t // block_k, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, kb, j: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, kb, j: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), q_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, t, d), lambda i, kb, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(q, k, v, do, delta, lse)


# ---------------------------------------------------------------------------
# Flash step with carried state: the inner kernel for ring attention.
# ---------------------------------------------------------------------------

def _flash_step_kernel(q_ref, k_ref, v_ref, acc_in, m_in, l_in, q_off_ref,
                       k_off_ref, acc_out, m_out, l_out, *, block_q: int,
                       block_k: int, causal: bool, scale: float):
    """One flash update: fold a (t_kv, d) key/value block into carried
    online-softmax state. Offsets place the local tiles in the GLOBAL
    sequence so causal masking works across ring-rotated blocks."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        acc_out[0, ...] = acc_in[0]
        m_out[0, ...] = m_in[0]
        l_out[0, ...] = l_in[0]

    _, s = _score_tile_global(q_ref, k_ref, q_off_ref[0] + qi * block_q,
                              k_off_ref[0] + kb * block_k, block_q, block_k,
                              causal, scale)
    acc_out[0, ...], m_out[0, ...], l_out[0, ...] = _online_step(
        s, v_ref[0], m_out[0], l_out[0], acc_out[0])
    del num_k_blocks


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma_axes", "kv_group"))
def flash_attention_step(q, k, v, acc, m, l, q_offset, k_offset,
                         causal: bool = True, block_q: int = None,
                         block_k: int = None, interpret: bool = False,
                         vma_axes=(), kv_group: int = 1):
    """Fold one key/value block into carried flash state.

    q: (bh, t_q, d); k, v: (bh, t_kv, d); acc: (bh, t_q, d) float32;
    m, l: (bh, t_q, 1) float32; q_offset/k_offset: () int32 global
    positions of the tiles. Returns updated (acc, m, l). Used by
    gloo_tpu.parallel.sp.ring_flash_attention, where the ring rotation
    supplies a different k/v block (and k_offset) per step. Inside
    shard_map with vma checking, pass vma_axes=(axis,). kv_group > 1
    (GQA): k/v carry bh // kv_group heads, shared via the index map.
    """
    bh, tq, d = q.shape
    tkv = k.shape[1]
    if bh % kv_group != 0 or k.shape[0] != bh // kv_group:
        raise ValueError(
            f"k head count {k.shape[0]} != bh {bh} / kv_group {kv_group}")
    if block_q is None:
        block_q = largest_block(tq, 512)
    if block_k is None:
        block_k = largest_block(tkv, 1024)
    if tq % block_q != 0 or tkv % block_k != 0:
        raise ValueError("tile sizes must divide the block shapes")
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_step_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale)
    q_off = jnp.reshape(q_offset.astype(jnp.int32), (1,))
    k_off = jnp.reshape(k_offset.astype(jnp.int32), (1,))
    return pl.pallas_call(
        kernel,
        interpret=interpret,
        grid=(bh, tq // block_q, tkv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, kb: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, kb: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tq, d), jnp.float32,
                                 vma=frozenset(vma_axes)),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32,
                                 vma=frozenset(vma_axes)),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32,
                                 vma=frozenset(vma_axes)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(q, k, v, acc, m, l, q_off, k_off)


def group_sum_kv(partials, kv_group: int):
    """Fold per-query-head f32 dK/dV partials down to kv heads: flat query
    head bi*h + hi pairs with kv head bi*h_kv + hi//group, so consecutive
    runs of kv_group rows share one kv head."""
    if kv_group == 1:
        return partials
    bh, tkv, d = partials.shape
    return partials.reshape(bh // kv_group, kv_group, tkv, d).sum(1)


def _flash_bwd_dq_step_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref,
                              lse_ref, q_off_ref, k_off_ref, dq_ref, acc_ref,
                              *, block_q: int, block_k: int, causal: bool,
                              scale: float):
    """dQ contribution of ONE key/value block (global offsets), for the
    ring backward: softmax is recomputed from the forward's global
    logsumexp, so each block's dQ piece is independently correct and the
    ring loop just sums them. (The local jax.grad path uses the fused
    one-pass kernel below, where the static causal tile split lives.)"""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked):
        _, s = _score_tile_global(q_ref, k_ref, q_off_ref[0] + qi * block_q,
                                  k_off_ref[0] + kb * block_k, block_q,
                                  block_k, masked, scale)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p = _softmax_tile(s, lse_ref[0])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        active = (k_off_ref[0] + kb * block_k <=
                  q_off_ref[0] + qi * block_q + block_q - 1)

        @pl.when(active)
        def _():
            update(True)
    else:
        update(False)

    @pl.when(kb == num_k_blocks - 1)
    def _():
        dq_ref[0, ...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_step_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref,
                               lse_ref, q_off_ref, k_off_ref, dk_ref, dv_ref,
                               dk_acc, dv_acc, *, block_q: int, block_k: int,
                               causal: bool, scale: float):
    """dK/dV of the currently-held key/value block w.r.t. the LOCAL
    queries only (global offsets). In the ring backward these partials
    ride the rotation with their block and sum to the full gradient once
    the block returns home."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    num_q_blocks = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(masked):
        q, s = _score_tile_global(q_ref, k_ref, q_off_ref[0] + qi * block_q,
                                  k_off_ref[0] + kb * block_k, block_q,
                                  block_k, masked, scale)
        v = v_ref[0]
        do = do_ref[0]
        p = _softmax_tile(s, lse_ref[0])
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        active = (q_off_ref[0] + qi * block_q + block_q - 1 >=
                  k_off_ref[0] + kb * block_k)

        @pl.when(active)
        def _():
            update(True)
    else:
        update(False)

    @pl.when(qi == num_q_blocks - 1)
    def _():
        # q comes back from _score_tile_global already scaled, so the
        # ds^T q contraction yields dK directly; dV needs no scale.
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma_axes", "kv_group"))
def flash_attention_bwd_step(q, k, v, do, delta, lse, q_offset, k_offset,
                             causal: bool = True, block_q: int = None,
                             block_k: int = None, interpret: bool = False,
                             vma_axes=(), kv_group: int = 1):
    """Backward mirror of flash_attention_step: gradients through one
    key/value block at a global position.

    q, do: (bh, t_q, d); k, v: (bh, t_kv, d); delta = rowsum(dO * O) and
    lse = m + log(l), both (bh, t_q, 1) float32 from the completed
    forward. Returns (dq_partial, dk, dv): dq_partial sums across blocks
    to the full dQ; dk/dv are this block's gradients w.r.t. the local
    queries only. Used by gloo_tpu.parallel.sp.ring_flash_attention's
    VJP (reference backward split: gloo has no device plane; torch ring
    attention recipes shard this the same way).

    kv_group > 1 (GQA): k/v carry bh // kv_group heads, read through the
    i // kv_group index map (never replicated in memory); dk/dv are still
    per-QUERY-head f32 partials — the caller group-sums them.
    """
    bh, tq, d = q.shape
    tkv = k.shape[1]
    if bh % kv_group != 0 or k.shape[0] != bh // kv_group:
        raise ValueError(
            f"k head count {k.shape[0]} != bh {bh} / kv_group {kv_group}")
    if block_q is None:
        block_q = largest_block(tq, 512)
    if block_k is None:
        block_k = largest_block(tkv, 1024)
    if tq % block_q != 0 or tkv % block_k != 0:
        raise ValueError("tile sizes must divide the block shapes")
    scale = 1.0 / (d ** 0.5)
    q_off = jnp.reshape(q_offset.astype(jnp.int32), (1,))
    k_off = jnp.reshape(k_offset.astype(jnp.int32), (1,))
    vma = frozenset(vma_axes)

    def dq_kv_index(i, j, kb):
        return (i // kv_group, kb, 0)

    dq_kernel = functools.partial(_flash_bwd_dq_step_kernel, block_q=block_q,
                                  block_k=block_k, causal=causal, scale=scale)
    dq = pl.pallas_call(
        dq_kernel,
        interpret=interpret,
        grid=(bh, tq // block_q, tkv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), dq_kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), dq_kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(q, k, v, do, delta, lse, q_off, k_off)

    def dkv_q_index(i, kb, j):
        return (i, j, 0)

    dkv_kernel = functools.partial(_flash_bwd_dkv_step_kernel,
                                   block_q=block_q, block_k=block_k,
                                   causal=causal, scale=scale)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        interpret=interpret,
        grid=(bh, tkv // block_k, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), dkv_q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, kb, j: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda i, kb, j: (i // kv_group, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), dkv_q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), dkv_q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), dkv_q_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tkv, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, tkv, d), jnp.float32, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(q, k, v, do, delta, lse, q_off, k_off)
    return dq, dk, dv
