"""Rotary position embeddings (RoPE), Llama-3 style.

Supports plain RoPE, Llama-3's frequency scaling for long context and
YaRN's (arXiv:2309.00071, as the ``deepseek_v3`` modelling code has it).
Computed in float32; applied as interleaved-free "rotate half" over the
head dimension (the GPT-NeoX convention Llama uses).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 500000.0,
                     scaling: dict | None = None) -> jnp.ndarray:
    """Inverse frequencies [head_dim // 2], optionally Llama-3 scaled.

    ``scaling`` (Llama-3.1 long-context): {"factor": 8, "low_freq_factor": 1,
    "high_freq_factor": 4, "original_max_position": 8192}.
    """
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling:
        factor = float(scaling.get("factor", 8.0))
        low = float(scaling.get("low_freq_factor", 1.0))
        high = float(scaling.get("high_freq_factor", 4.0))
        orig = float(scaling.get("original_max_position", 8192))
        wavelen = 2.0 * jnp.pi / inv
        # high-frequency (short wavelength) components keep full rotation;
        # low-frequency components are slowed by `factor`; in between,
        # smooth interpolation (Llama-3.1 recipe).
        smooth = jnp.clip((orig / wavelen - low) / (high - low), 0.0, 1.0)
        inv = jnp.where(wavelen < orig / high, inv,
                        jnp.where(wavelen > orig / low, inv / factor,
                                  (1 - smooth) * inv / factor + smooth * inv))
    return inv


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 at or below 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, scaling: dict
                     ) -> jnp.ndarray:
    """Inverse frequencies [head_dim // 2] under YaRN: each pair's
    frequency is blended between the plain one (``extrapolation``: pairs
    that turn more than ``beta_fast`` times in the original context
    keep it) and the plain one over ``factor`` (``interpolation``:
    pairs that turn less than ``beta_slow`` times), by a linear ramp
    between the two pairs' indices (``find_correction_range``, floor and
    ceiling unless ``truncate`` is false).

    ``scaling``: {"factor", "original_max_position_embeddings",
    "beta_fast" (32), "beta_slow" (1)}. The temperature is not applied
    here: cos and sin are scaled by ``mscale / mscale_all_dim`` ratios
    the caller owns (:func:`yarn_mscale`)."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = correction_dim(float(scaling.get("beta_fast") or 32))
    high = correction_dim(float(scaling.get("beta_slow") or 1))
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    half = jnp.arange(head_dim // 2, dtype=jnp.float32)
    plain = 1.0 / (theta ** (2.0 * half / head_dim))
    ramp = jnp.clip((half - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, heads, head_dim] by position.

    ``positions`` is [..., seq] (absolute token positions, so paged /
    continued decode just passes the running offset).
    """
    # explicit lift of inv_freq [D/2] to positions' rank + 1: the test
    # harness runs jax_numpy_rank_promotion='raise'
    pos = positions[..., :, None].astype(jnp.float32)
    angles = pos * inv_freq.reshape((1,) * (pos.ndim - 1) + (-1,))  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
