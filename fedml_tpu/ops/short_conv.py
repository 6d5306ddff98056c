"""Gated short convolution: the token mixer of the conv layers of a
``layer_types`` decoder (models/hybrid_lm.py).

``[b, c, x] = split3(u W_in)``; ``z = b * x``; a depthwise causal
convolution over the last ``L`` positions, ``y_t = sum_j w[:, j] * z_{t-j}``
with ``z_{<0} = 0``; the operator's output is ``(c * y) W_out``. This module
holds what lies between the two projections, as plain shifted adds that XLA
fuses into one pass over the (B, T, 3D) projection: bound by HBM bytes
(read b, c, x, write c * y), no matmul. It runs under the scope
``short_conv.core`` so that a device trace can name its time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_taps(z: jax.Array, taps: jax.Array) -> jax.Array:
    """``y_t = sum_j taps[:, j] * z_{t-j}``, ``z_{<0} = 0``: a depthwise
    causal convolution as shifted adds. z: (B, T, D); taps: (D, L)."""
    T = z.shape[1]
    taps = taps.astype(z.dtype)
    y = z * taps[:, 0]
    for j in range(1, taps.shape[1]):
        back = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :T]
        y = y + back * taps[:, j]
    return y


def gated_short_conv(bcx: jax.Array, taps: jax.Array) -> jax.Array:
    """bcx: (B, T, 3D), the input projection; taps: (D, L), tap ``j`` weighs
    the position ``j`` back. Returns ``c * conv(b * x)``, (B, T, D)."""
    with jax.named_scope("short_conv.core"):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        return c * causal_taps(b * x, taps)
