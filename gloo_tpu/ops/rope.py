"""Rotary position embeddings (RoPE) with explicit positions.

Positions are an argument, not an assumption: under sequence parallelism
each device holds t_local rows of a longer sequence, so the correct
rotation uses GLOBAL positions (rank * t_local + row). Pairing this with
gloo_tpu.parallel.sp: apply_rope(q, my * t_local + iota) on the queries
and the SAME global positions on each k block BEFORE it enters the ring,
and the rotated blocks stay correctly embedded as they travel (RoPE is
applied to values, not indices, so rotation does not disturb it).

TPU notes: pure elementwise ops — XLA fuses the rotation into the
surrounding matmul prologue; no kernel needed. The half-split layout
(rotate_half) is used, matching the convention of most open models.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax


def rope_angles(positions, head_dim: int, theta: float = 10000.0,
                inv_freq=None):
    """(..., t) int positions -> (..., t, head_dim // 2) angles."""
    if head_dim % 2 != 0:
        raise ValueError(f"head_dim {head_dim} must be even for RoPE")
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_positions: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's frequencies (Peng et al. 2023) as DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding` computes them: the dims that turn
    fewer than `beta_slow` times over the original context are
    interpolated (divided by `factor`), those that turn more than
    `beta_fast` times keep their frequency, and a linear ramp mixes the
    dims between."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    extra = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_table(positions, head_dim: int, theta: float = 10000.0,
               inv_freq=None):
    """(cos, sin) of `rope_angles`, f32, each (..., t, head_dim // 2): made
    once, one table rotates every layer alike (`rotate`)."""
    ang = rope_angles(positions, head_dim, theta, inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """Rotate x: (..., t, head_dim) by its positions: (t,) or broadcastable
    to x's leading dims + (t,); `inv_freq` (head_dim // 2,) in place of
    theta's. Returns x's dtype."""
    return rotate(x, rope_table(positions, x.shape[-1], theta, inv_freq))


def rotate(x, table):
    """Rotate x: (..., t, head_dim) by a `rope_table`. Returns x's
    dtype."""
    cos, sin = table
    d = x.shape[-1]
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def rope_positions(t: int, offset=0):
    """Global positions for a local block of length t starting at offset
    (e.g. offset = rank * t_local under sequence parallelism)."""
    return offset + lax.iota(jnp.int32, t)
