"""Rotary position embeddings (RoPE)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     dtype=jnp.float32):
    """Precompute (cos, sin) tables of shape (max_len, head_dim // 2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array = None) -> jax.Array:
    """Rotate pairs of channels. ``x``: (..., seq, heads, head_dim);
    ``cos``/``sin``: (max_len, head_dim//2); ``positions``: (..., seq) offsets
    (defaults to arange, used for decode-time offsets)."""
    seq = x.shape[-3]
    if positions is None:
        cos_t = cos[:seq]
        sin_t = sin[:seq]
        # (seq, hd/2) -> broadcast over heads
        cos_t = cos_t[..., :, None, :]
        sin_t = sin_t[..., :, None, :]
    else:
        cos_t = cos[positions][..., :, None, :]
        sin_t = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos_t - x2 * sin_t, x2 * cos_t + x1 * sin_t], axis=-1)
    return rotated.astype(x.dtype)


# ------------------------------------------------------------------ YaRN
#
# Peng et al., "YaRN: Efficient Context Window Extension of Large Language
# Models" (2023), as the published DeepSeek-V2 code applies it: every
# rotary frequency is a blend of the trained one and that one over
# ``factor``, by a linear ramp between two "correction" dims, so that the
# fast dims keep their wavelength and the slow ones are interpolated.


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 for ``factor <= 1``): the
    attention temperature YaRN adds. DeepSeek-V2 multiplies its softmax
    scale by the square of ``yarn_mscale(factor, mscale_all_dim)`` and its
    cos/sin tables by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """The ``head_dim // 2`` rotary frequencies under YaRN."""
    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original_max_len
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # as published: no division by zero
    extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    # ramp 0: the trained frequency; ramp 1: interpolated by ``factor``.
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_at(positions: jax.Array, inv_freq: jax.Array, scale: float = 1.0):
    """``(cos, sin)`` of shape ``positions.shape + (head_dim // 2,)`` at
    the given positions, with no table: a model whose window is 160k
    positions would carry 40 MB of it through every program."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 interleaved: bool = False) -> jax.Array:
    """Rotate ``x`` (..., head_dim) by ``cos``/``sin`` (..., head_dim //
    2), which broadcast against it. Pairs are ``(i, i + head_dim / 2)``;
    with ``interleaved`` they are ``(2i, 2i + 1)`` and the result comes
    back de-interleaved (all first members, then all second), which is
    what the published DeepSeek-V2 code does: a dot product of two
    vectors rotated alike does not see the order."""
    x = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
