"""Sequence/context parallelism: ring attention over the device mesh.

Long sequences are sharded along time; K/V blocks rotate around the ring
(ppermute over ICI) while each device accumulates attention for its local
queries with an online-softmax (flash-style) update. Communication volume
matches the reference's chunked-ring schedule shape (SURVEY.md §5: the
ring allreduce IS a ring sequence-parallel schedule over chunks) — here
expressed as a jit-compiled XLA program.

Call inside shard_map with the time axis sharded:
    q, k, v: (batch, heads, t_local, head_dim) per device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from gloo_tpu.tpu import spmd


def ring_attention(q, k, v, axis: str, causal: bool = True):
    n = spmd.size(axis)
    my = spmd.rank(axis)
    b, h, t_local, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    q32 = q.astype(jnp.float32)
    pos_q = my * t_local + lax.broadcasted_iota(jnp.int32, (t_local, 1), 0)

    def step(i, carry):
        k_blk, v_blk, out, m, l = carry
        src = lax.rem(my - i + n, n)  # which shard's K/V we hold now
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            pos_k = src * t_local + lax.broadcasted_iota(
                jnp.int32, (1, t_local), 1)
            mask = pos_k <= pos_q  # (t_local, t_local)
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        # Guard fully-masked rows (no attendable keys yet): keep m finite.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe)
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        correction = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        correction = jnp.where(jnp.isfinite(m), correction, 0.0)
        l_new = l * correction + p.sum(axis=-1, keepdims=True)
        out_new = out * correction + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        # Rotate K/V to the right neighbor for the next step.
        with jax.named_scope("gloo_tpu.sp.ring_shift"):
            k_next = spmd.shift(k_blk, axis, 1)
            v_next = spmd.shift(v_blk, axis, 1)
        return k_next, v_next, out_new, m_new, l_new

    out0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    m0 = jnp.full((b, h, t_local, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_local, 1), jnp.float32)
    # Fresh zeros are device-invariant; the loop carry becomes varying over
    # the ring axis after one step, so pre-mark them to keep carry types
    # stable under shard_map's vma checking.
    out0, m0, l0 = (lax.pcast(a, (axis,), to="varying")
                    for a in (out0, m0, l0))
    _, _, out, m, l = lax.fori_loop(0, n, step, (k, v, out0, m0, l0))
    out = out / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _ring_flash_forward(q, k, v, axis, causal, block_q, block_k, interpret):
    """Forward ring loop; returns (out in q.dtype, logsumexp rows)."""
    from gloo_tpu.ops.attention import flash_attention_step

    n = spmd.size(axis)
    my = spmd.rank(axis)
    b, h, t_local, d = q.shape
    h_kv = k.shape[1]
    if h % h_kv != 0:
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {h_kv}")
    group = h // h_kv
    qf = q.reshape(b * h, t_local, d)

    def step(i, carry):
        k_blk, v_blk, acc, m, l = carry
        src = lax.rem(my - i + n, n)
        acc, m, l = flash_attention_step(
            qf, k_blk.reshape(b * h_kv, t_local, d),
            v_blk.reshape(b * h_kv, t_local, d), acc, m, l,
            q_offset=my * t_local, k_offset=src * t_local, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            vma_axes=(axis,), kv_group=group)
        with jax.named_scope("gloo_tpu.sp.ring_shift"):
            k_next = spmd.shift(k_blk, axis, 1)
            v_next = spmd.shift(v_blk, axis, 1)
        return k_next, v_next, acc, m, l

    def zeros(shape, fill=0.0):
        return lax.pcast(jnp.full(shape, fill, jnp.float32), (axis,),
                         to="varying")

    acc0 = zeros((b * h, t_local, d))
    m0 = zeros((b * h, t_local, 1), -jnp.inf)
    l0 = zeros((b * h, t_local, 1))
    _, _, acc, m, l = lax.fori_loop(0, n, step, (k, v, acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe).reshape(b, h, t_local, d).astype(q.dtype)
    return out, m + jnp.log(l_safe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis, causal, block_q, block_k, interpret):
    return _ring_flash_forward(q, k, v, axis, causal, block_q, block_k,
                               interpret)[0]


def _ring_flash_fwd(q, k, v, axis, causal, block_q, block_k, interpret):
    out, lse = _ring_flash_forward(q, k, v, axis, causal, block_q, block_k,
                                   interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis, causal, block_q, block_k, interpret, res, g):
    """Second ring pass. Softmax tiles are recomputed from the forward's
    global logsumexp, so each (queries, rotated block) pair yields an
    independently-correct gradient piece: dQ pieces sum locally; dK/dV
    pieces are accumulated into buffers that rotate WITH their key/value
    block, so each block's gradient arrives home exactly when the block
    does."""
    from gloo_tpu.ops.attention import flash_attention_bwd_step, group_sum_kv

    q, k, v, out, lse = res
    n = spmd.size(axis)
    my = spmd.rank(axis)
    b, h, t_local, d = q.shape
    h_kv = k.shape[1]
    group = h // h_kv
    bh = b * h
    bh_kv = b * h_kv
    qf = q.reshape(bh, t_local, d)
    gf = g.astype(jnp.float32).reshape(bh, t_local, d)
    delta = jnp.sum(gf * out.astype(jnp.float32).reshape(bh, t_local, d),
                    axis=-1, keepdims=True)

    def step(i, carry):
        k_blk, v_blk, dk_c, dv_c, dq = carry
        src = lax.rem(my - i + n, n)
        dq_p, dk_p, dv_p = flash_attention_bwd_step(
            qf, k_blk.reshape(bh_kv, t_local, d),
            v_blk.reshape(bh_kv, t_local, d), gf, delta, lse,
            q_offset=my * t_local, k_offset=src * t_local, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            vma_axes=(axis,), kv_group=group)
        dk_p = group_sum_kv(dk_p, group)
        dv_p = group_sum_kv(dv_p, group)
        return (spmd.shift(k_blk, axis, 1), spmd.shift(v_blk, axis, 1),
                spmd.shift(dk_c + dk_p, axis, 1),
                spmd.shift(dv_c + dv_p, axis, 1), dq + dq_p)

    def zeros(shape):
        return lax.pcast(jnp.zeros(shape, jnp.float32), (axis,),
                         to="varying")

    _, _, dk, dv, dq = lax.fori_loop(
        0, n, step,
        (k, v, zeros((bh_kv, t_local, d)), zeros((bh_kv, t_local, d)),
         zeros((bh, t_local, d))))
    return (dq.reshape(b, h, t_local, d).astype(q.dtype),
            dk.reshape(b, h_kv, t_local, d).astype(k.dtype),
            dv.reshape(b, h_kv, t_local, d).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis: str, causal: bool = True,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """Ring attention with a Pallas flash inner kernel: K/V blocks rotate
    over ICI (ppermute) while each device folds the arriving block into
    carried online-softmax state tile-by-tile on the MXU — the standard
    long-context recipe (cross-chip ring x on-chip flash), with no
    (t_local, t_local) materialization either.

    Shapes as ring_attention: q, k, v are (batch, heads, t_local, d) per
    device inside shard_map; k/v may carry fewer heads (GQA — shared via
    index maps, never replicated; the smaller blocks also shrink the ICI
    rotation traffic by the group factor). Differentiable: the custom VJP
    runs a second ring pass with dedicated Pallas backward kernels (dQ
    local; per-block dK/dV partials group-summed in f32, riding the
    rotation home with their block).

    interpret=True requires check_vma=False on the enclosing shard_map:
    the Pallas HLO interpreter's block indexing mixes varying and
    invariant operands, which vma checking rejects (JAX limitation; the
    compiled TPU path works under the default check_vma=True)."""
    return _ring_flash(q, k, v, axis, causal, block_q, block_k, interpret)


def ulysses_attention(q, k, v, axis: str, causal: bool = True,
                      attn_fn=None, interpret: bool = False):
    """DeepSpeed-Ulysses-style sequence parallelism: two all-to-alls swap
    the sharded dimension from sequence to heads, so each device runs
    FULL-sequence attention for a subset of heads, then a final
    all-to-all restores sequence sharding. The complement to the ring
    recipes: all_to_all rides ICI once per direction instead of n-1
    ppermute steps, at the cost of requiring heads % group size == 0.
    (Reference positioning: SURVEY.md §2.10 — gloo supplies alltoall as
    the primitive these recipes are built from.)

    q, k, v: (batch, heads, t_local, d) per device inside shard_map.
    The attention over the gathered full sequence DEFAULTS to the Pallas
    flash kernel — the configuration long-context users actually run —
    with the shard_map varying-axis bookkeeping handled internally
    (vma_axes=(axis,) threads through the kernel's out_shapes, so the
    compiled TPU path works under the default check_vma=True).
    interpret=True forces the Pallas interpreter for the DEFAULT flash
    path (it is auto-enabled on CPU backends and ignored when attn_fn is
    supplied — a custom attn_fn owns its own interpret choice); that
    mode needs check_vma=False on the enclosing shard_map (HLO
    interpreter limitation, as for ring_flash_attention). Pass attn_fn
    (signature attn_fn(q, k, v, causal)) to substitute a different
    full-sequence attention, e.g. the materialized-scores oracle.
    """
    n = spmd.size(axis)
    b, h, t_local, d = q.shape
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by group size {n}")
    if attn_fn is None:
        from gloo_tpu.ops.attention import flash_attention

        # CPU backends only run Pallas through the interpreter (the
        # 8-device test/dryrun meshes); real TPU backends compile.
        use_interpret = interpret or jax.default_backend() == "cpu"

        def attn_fn(qh, kh, vh, causal):
            return flash_attention(qh, kh, vh, causal=causal,
                                   interpret=use_interpret,
                                   vma_axes=(axis,))

    # (b, h, t_local, d) -> (b, h/n, t_global, d): scatter heads, gather
    # sequence. all_to_all splits/concats one axis; heads is axis 1,
    # sequence axis 2.
    with jax.named_scope("gloo_tpu.sp.ulysses_exchange"):
        qh, kh, vh = (spmd.alltoall(x, axis, split_axis=1, concat_axis=2)
                      for x in (q, k, v))
    out = attn_fn(qh, kh, vh, causal)
    # (b, h/n, t_global, d) -> (b, h, t_local, d): inverse exchange.
    with jax.named_scope("gloo_tpu.sp.ulysses_exchange"):
        return spmd.alltoall(out, axis, split_axis=2, concat_axis=1)
