"""Rotary positions and per-head RMS normalisation of queries and keys, for
the attention layers of a ``layer_types`` decoder (models/hybrid_lm.py).
Both run in float32 and hand back the dtype they were given."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)


def apply_rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over all of the head width. x: (B, T, H,
    Dh) at positions 0..T-1; pair (i, i + Dh/2) turns by ``t * theta^(-2i/Dh)``."""
    T, Dh = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)
