"""Rotary position embeddings (full and partial-rotary variants), with
YaRN frequency scaling (arXiv:2309.00071) as DeepSeek-V2 configures it."""
from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor: 0.1 * mscale * ln(factor) + 1
    (1 where the context is not stretched)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, dim: int, theta: float):
    """(low, high): the rotary pair indices (of ``dim // 2``) below which
    the frequencies are kept (extrapolated) and above which they are
    divided by the factor (interpolated); ``beta_fast`` and ``beta_slow``
    rotations over the original context bound them."""
    def pair(rotations):
        return (dim * math.log(yarn.original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(pair(yarn.beta_fast))
    high = math.ceil(pair(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(head_dim: int, theta: float, rotary_pct: float = 1.0,
               yarn=None):
    """Inverse frequencies for the rotary fraction of the head dim; with
    ``yarn`` (a ``models.base.YarnCfg``) blended between the original
    and the factor-interpolated ones by a linear ramp over
    :func:`yarn_correction_range`."""
    rot = int(head_dim * rotary_pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if yarn is not None:
        low, high = yarn_correction_range(yarn, rot, theta)
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / yarn.factor * ramp + inv * (1.0 - ramp)
    return inv, rot


def apply_rope(x, positions, theta: float, rotary_pct: float = 1.0,
               yarn=None):
    """x: (B, T, n_heads, head_dim); positions: (B, T) int32. Rotates the
    pairs (2i, 2i + 1) of the rotary fraction by position * inv_freq[i]."""
    inv, rot = rope_freqs(x.shape[-1], theta, rotary_pct, yarn)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, T, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)
