"""Multi-weight 2D convolution: the packed-lane conv path.

Why this exists: the packed-lane cohort executor (``simulation/fed_sim.py``)
vmaps the whole local-update over the lane axis, so every conv sees
*per-lane weights*. XLA lowers a weight-batched conv to a grouped
convolution, whose thin per-group channels starve the 128-wide MXU (how
much is not measured on this chip). The reference has no analogue
(its clients train sequentially in Python — ``simulation/sp/fedavg/
my_model_trainer_classification.py:15``); this is a TPU-native problem and
gets a TPU-native fix:

- ``conv2d_im2col``: convolution as explicit patch extraction (strided
  slices, no conv primitive) + ``einsum``. Under ``vmap`` with batched
  weights this becomes a *batched matmul* — MXU-native, no grouped-conv
  lowering. The cost is patch materialization in HBM (9x activation
  traffic for 3x3), so it is the fallback, not the fast path.
- ``conv2d_pallas``: a fused pallas kernel that builds the im2col patch
  matrix in VMEM per block and feeds one dense ``[M, kh*kw*Ci] @
  [kh*kw*Ci, Co]`` matmul per grid cell — dense-matmul MXU rates with no
  patch HBM traffic. ``jax.vmap`` of a ``pallas_call`` prepends a grid
  axis, so the lane-batched case IS the batched-multi-weight kernel; a
  ``custom_vjp`` supplies pallas backward kernels (dx = flipped-kernel
  conv reusing the forward kernel; dw = patch^T @ dy with grid
  accumulation).

The ``Conv`` flax module is a drop-in for ``nn.Conv`` (same param name
"kernel", same auto-naming, NHWC, SAME/VALID) that dispatches per
``impl`` and per conv shape. 1x1 convs always take the direct-einsum path
(a 1x1 conv *is* a matmul; under vmap that is a batched matmul, never a
grouped conv).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# --- pure-JAX im2col ------------------------------------------------------


def _same_pads(size: int, k: int, s: int) -> Tuple[int, int]:
    out = -(-size // s)  # ceil
    pad = max(0, (out - 1) * s + k - size)
    return pad // 2, pad - pad // 2


def extract_patches(x: jnp.ndarray, kh: int, kw: int, stride: int,
                    padding: str) -> jnp.ndarray:
    """[B, H, W, C] -> [B, Ho, Wo, kh*kw*C] via strided slices + concat.

    Feature order is (dy, dx, ci) — matching ``w.reshape(kh*kw*ci, co)``
    for ``w`` of shape [kh, kw, ci, co]. No convolution primitive is
    involved, so vmapping over a weight axis elsewhere cannot force a
    grouped-conv lowering here.
    """
    b, h, w, c = x.shape
    if padding == "SAME":
        (pt, pb), (pl, pr) = _same_pads(h, kh, stride), _same_pads(w, kw, stride)
        x = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
        h, w = h + pt + pb, w + pl + pr
    elif padding != "VALID":
        raise ValueError(f"padding must be SAME or VALID, got {padding!r}")
    ho, wo = (h - kh) // stride + 1, (w - kw) // stride + 1
    taps = []
    for dy in range(kh):
        for dx in range(kw):
            taps.append(jax.lax.slice(
                x,
                (0, dy, dx, 0),
                (b, dy + (ho - 1) * stride + 1, dx + (wo - 1) * stride + 1, c),
                (1, stride, stride, 1)))
    return jnp.concatenate(taps, axis=-1)


def conv2d_im2col(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                  padding: str = "SAME") -> jnp.ndarray:
    """Conv as patches @ weight-matrix. [B,H,W,Ci] x [kh,kw,Ci,Co]."""
    kh, kw, ci, co = w.shape
    if kh == kw == 1:
        if stride > 1:
            x = x[:, ::stride, ::stride, :]
        return jnp.einsum("bhwc,co->bhwo", x, w[0, 0])
    p = extract_patches(x, kh, kw, stride, padding)
    return jnp.einsum("bhwk,ko->bhwo", p, w.reshape(kh * kw * ci, co))


# --- pallas fused kernel --------------------------------------------------


def _pick_block_b(b: int, h: int, w: int, ci: int, kk: int, co: int,
                  itemsize: int = 2) -> int:
    """Largest power-of-two batch block whose working set fits ~8 MB VMEM
    (padded lane estimates: trailing dims round up to 128 lanes).
    ``itemsize`` is the element byte width of the actual dtype — f32 inputs
    have twice the bf16 working set and must pick smaller blocks."""
    def lanes(n):
        return -(-n // 128) * 128

    for bt in (64, 32, 16, 8, 4, 2, 1):
        if bt > b or b % bt:
            continue
        est = itemsize * (
            bt * (h + 2) * (w + 2) * lanes(ci)        # input block
            + bt * h * w * lanes(kk * ci)             # patch matrix
            + bt * h * w * lanes(co)                  # output block
        )
        if est <= 8 * 1024 * 1024:
            return bt
    return 1


def _build_patches(x_ref, p_ref, *, kh, kw, ho, wo, stride):
    """Fill the VMEM patch scratch [Bt*Ho*Wo, kh*kw*Ci] from the padded
    input block via static-offset stores. (A jnp.concatenate over the
    shifted taps is the natural spelling, but Mosaic refuses to concat
    vectors whose sublane offsets differ — each dy shift changes the
    offset — so the patch matrix is materialized through the ref.)"""
    xb = x_ref[...]                      # [Bt, Hp, Wp, Ci]
    bt, _, _, ci = xb.shape
    for dy in range(kh):
        for dx in range(kw):
            t = jax.lax.slice(
                xb,
                (0, dy, dx, 0),
                (bt, dy + (ho - 1) * stride + 1, dx + (wo - 1) * stride + 1, ci),
                (1, stride, stride, 1))
            off = (dy * kw + dx) * ci
            p_ref[:, off:off + ci] = t.reshape(bt * ho * wo, ci)


def _fwd_kernel(x_ref, w_ref, o_ref, p_ref, *, kh, kw, ho, wo, stride,
                out_dtype):
    _build_patches(x_ref, p_ref, kh=kh, kw=kw, ho=ho, wo=wo, stride=stride)
    bt = x_ref.shape[0]
    wm = w_ref[...].reshape(kh * kw * x_ref.shape[3], -1)
    acc = jnp.dot(p_ref[...], wm, preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(bt, ho, wo, -1).astype(out_dtype)


def _dw_kernel(x_ref, dy_ref, o_ref, p_ref, *, kh, kw, ho, wo, stride):
    _build_patches(x_ref, p_ref, kh=kh, kw=kw, ho=ho, wo=wo, stride=stride)
    bt = x_ref.shape[0]
    g = dy_ref[...].reshape(bt * ho * wo, -1)
    acc = jnp.dot(p_ref[...].T, g, preferred_element_type=jnp.float32)
    # accumulate across the batch-block grid axis
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        o_ref[...] += acc


def _pad_same(x, kh, kw, stride):
    (pt, pb), (pl_, pr) = _same_pads(x.shape[1], kh, stride), _same_pads(x.shape[2], kw, stride)
    return jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))


def _supported(x_shape, w_shape, stride, padding) -> bool:
    if len(w_shape) != 4:
        return False
    kh, kw, _, _ = w_shape
    return (padding == "SAME" and stride == 1 and kh == kw == 3
            and len(x_shape) == 4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d_pallas(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                  padding: str = "SAME") -> jnp.ndarray:
    """Fused im2col conv (3x3, stride 1, SAME). See module docstring.

    vmap over a leading weight axis turns this into the batched
    multi-weight kernel (pallas prepends the mapped axis to the grid).
    """
    return _conv2d_pallas_impl(x, w, stride, padding)


def _conv2d_pallas_impl(x, w, stride, padding):
    if not _supported(x.shape, w.shape, stride, padding):
        raise ValueError(
            "conv2d_pallas supports only 3x3 kernels, stride 1, SAME padding "
            f"on 4-D NHWC inputs; got w.shape={tuple(w.shape)}, "
            f"stride={stride}, padding={padding!r}, x.ndim={len(x.shape)}. "
            "Use Conv(impl=...) for automatic fallback on unsupported shapes.")
    b, h, ww, ci = x.shape
    kh, kw, _, co = w.shape
    ho, wo = h, ww  # stride-1 SAME
    xp = _pad_same(x, kh, kw, stride)
    bt = _pick_block_b(b, h, ww, ci, kh * kw, co,
                       itemsize=jnp.dtype(x.dtype).itemsize)
    kern = functools.partial(_fwd_kernel, kh=kh, kw=kw, ho=ho, wo=wo,
                             stride=stride, out_dtype=x.dtype)
    return pl.pallas_call(
        kern,
        grid=(b // bt,),
        in_specs=[
            pl.BlockSpec((bt, xp.shape[1], xp.shape[2], ci),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, ci, co), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, ho, wo, co), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, co), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt * ho * wo, kh * kw * ci), x.dtype)],
    )(xp, w)


def _conv2d_pallas_fwd(x, w, stride, padding):
    return _conv2d_pallas_impl(x, w, stride, padding), (x, w)


def _conv2d_pallas_bwd(stride, padding, res, g):
    x, w = res
    b, h, ww, ci = x.shape
    kh, kw, _, co = w.shape
    # dx: conv of g with the spatially-flipped, channel-transposed kernel —
    # reuses the forward kernel (still 3x3 stride-1 SAME)
    w_flip = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2)
    dx = _conv2d_pallas_impl(g, w_flip, stride, padding).astype(x.dtype)
    # dw: patches(x)^T @ g, accumulated across batch blocks on the grid
    xp = _pad_same(x, kh, kw, stride)
    bt = _pick_block_b(b, h, ww, ci, kh * kw, co,
                       itemsize=jnp.dtype(x.dtype).itemsize)
    kern = functools.partial(_dw_kernel, kh=kh, kw=kw, ho=h, wo=ww,
                             stride=stride)
    dw_flat = pl.pallas_call(
        kern,
        grid=(b // bt,),
        in_specs=[
            pl.BlockSpec((bt, xp.shape[1], xp.shape[2], ci),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bt, h, ww, co), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((kh * kw * ci, co), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kh * kw * ci, co), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt * h * ww, kh * kw * ci), x.dtype)],
    )(xp, g)
    return dx, dw_flat.reshape(kh, kw, ci, co).astype(w.dtype)


conv2d_pallas.defvjp(_conv2d_pallas_fwd, _conv2d_pallas_bwd)


# --- flax module ----------------------------------------------------------


class Conv(nn.Module):
    """Drop-in ``nn.Conv`` subset (NHWC, no dilation) with a selectable
    compute path. Auto-named "Conv_i" like ``nn.Conv`` so param trees are
    identical across impls.

    impl:
      - "xla":    ``lax.conv_general_dilated`` (XLA's native conv; best
                  unvmapped, grouped-conv penalty under weight-vmap)
      - "im2col": patches + einsum (batched matmul under weight-vmap;
                  pays patch HBM traffic)
      - "pallas": fused VMEM im2col kernel for 3x3/s1/SAME (+ the 1x1
                  einsum path); other shapes fall back to im2col
    """

    features: int
    kernel_size: Sequence[int] = (3, 3)
    strides: Union[int, Sequence[int]] = 1
    padding: str = "SAME"
    use_bias: bool = False
    dtype: jnp.dtype = jnp.float32
    impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        if isinstance(self.strides, int):
            s = self.strides
        else:
            if len(set(self.strides)) != 1:
                raise ValueError(
                    f"Conv supports only isotropic strides, got {self.strides}"
                    " — use nn.Conv for rectangular strides")
            s = self.strides[0]
        ci = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (kh, kw, ci, self.features), jnp.float32)
        w = kernel.astype(self.dtype)
        x = x.astype(self.dtype)
        if kh == kw == 1:
            y = conv2d_im2col(x, w, s, self.padding)  # 1x1 == matmul
        elif self.impl == "pallas" and _supported(x.shape, w.shape, s, self.padding):
            y = conv2d_pallas(x, w, s, self.padding)
        elif self.impl in ("im2col", "pallas"):
            y = conv2d_im2col(x, w, s, self.padding)
        else:
            y = jax.lax.conv_general_dilated(
                x, w, (s, s), self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y
