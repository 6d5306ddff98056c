"""Pallas fused stochastic-quantize + wire-pack for the codec hot path.

The compressed update plane's q8/q4 stage (comm/codec.py) is three passes
over every compressible leaf: hash-derived uniforms + per-256-chunk pow2
scales + clip/floor (``stochastic_quantize``), a dequantize multiply, and a
separate nibble/byte pack for the wire. All of it is memory-bound
elementwise work on a (C, m) cohort stack — prime fusion territory. This
kernel does the whole stage in ONE pass per tile: counter-hash uniforms
(lowbias32, the exact mixing chain of ``codec._mix32_arr``), chunk absmax
-> pow2 scale, stochastic floor, int8/int4 byte emission, and the
decode-side multiply, all while the tile sits in VMEM.

Layout: the (C, mpad) stack is viewed as (C * mpad/256, 256) — ONE QUANT
CHUNK PER ROW (a free row-major reshape in HBM). A chunk's absmax is then a
lane reduction and its scale a (rows, 1) column that broadcasts along
lanes, which is what the VPU does natively; the earlier (C, block_m) tiling
needed an in-kernel (bc, nchunk, 256) reshape, ``jnp.repeat`` and a
stride-2 lane gather that Mosaic does not lower. The grid is 1-D over
blocks of ``_BLOCK_ROWS`` chunk rows, every block independent (uniforms
come from the global element index, scales never cross a row), so Mosaic
pipelines blocks back-to-back with no carried scratch.

Bit-exactness is the load-bearing invariant: pow2 scales make every op in
the pipeline exact arithmetic except the single ``floor(v/s + u)``, so the
packed bytes must equal the numpy wire path (``UpdateCodec._encode_leaf``)
byte-for-byte and the decoded stack must equal the unfused XLA path
(``codec._quant_roundtrip_jnp``) bit-for-bit. The kernel computes the
frexp/ldexp scale with pure integer exponent arithmetic, matching XLA's
frexp semantics (subnormal absmax -> flushed scale, inf -> 2^-eb, nan/zero
-> 1.0); chunks whose absmax is subnormal are outside the numpy parity
contract (numpy keeps subnormal scales where XLA flushes — a pre-existing
property of the unfused path, pinned by tests).

On non-TPU backends the default dispatch is the jittable jnp reference
(same arithmetic, no Pallas) — interpret mode (``interpret=True``) exists
for the parity suite, which pins kernel == reference bit equality on CPU.
On a TPU the kernel runs compiled.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mirrors codec._QCHUNK / codec._EB — the codec asserts the values agree at
# wiring time so the two modules cannot drift silently.
QCHUNK = 256
_EB = {8: 6, 4: 2}
_BOUND = {8: 127, 4: 7}

# Most chunk rows one program takes: its working set (values + uniforms +
# levels + decode + bytes, ~8 f32 planes of 512 KB with the pipeline's
# double buffers) sits well inside Mosaic's default scoped VMEM (16 MiB on
# the v5e).
_MAX_BLOCK_ROWS = 512
# interpret mode (non-TPU) unrolls every grid step into the jaxpr — fine
# for parity-test shapes, catastrophic at cohort scale. Past this many
# steps the interpret path takes the jnp reference; kernel/reference bit
# parity makes the switch invisible.
_INTERPRET_GRID_CAP = 4096


def _mix32(x):
    """lowbias32 finalizer on uint32 arrays — the exact constants of
    ``codec._mix32_arr`` (asserted equal at import of the codec wiring)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _uniform_from_idx(idx_u32, base_u32):
    """Hash (element index XOR row key) -> f32 uniform in [0, 1). The
    24-bit value goes through int32 on its way to f32 (bit-identical, and
    Mosaic has no uint32 -> float32 cast)."""
    h = _mix32(idx_u32 ^ base_u32)
    h24 = jax.lax.bitcast_convert_type(h >> jnp.uint32(8), jnp.int32)
    return h24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _pow2_scale_bits(amax, eb: int):
    """Per-chunk power-of-two scale 2^(frexp_exp(amax) - eb) via int32
    exponent arithmetic — bit-identical to XLA's frexp/ldexp pair
    (``codec._pow2_scales`` under jnp) without relying on Mosaic support
    for those ops: subnormal absmax takes XLA's frexp exponent of -149 (so
    the ldexp result flushes to 0), inf maps to exponent 0, and zero/nan
    absmax yield scale 1.0."""
    # amax >= 0 (or nan with a clear sign bit), so the arithmetic shift
    # reads the biased exponent directly
    be = jax.lax.bitcast_convert_type(amax, jnp.int32) >> 23
    ea = jnp.where(be == 255, 0, jnp.where(be == 0, -149, be - 126))
    e2 = ea - eb
    s_norm = jax.lax.bitcast_convert_type((e2 + 127) << 23, jnp.float32)
    s = jnp.where(e2 >= -126, s_norm, jnp.float32(0.0))
    return jnp.where(amax > 0, s, jnp.float32(1.0))


def _quant_rows(v, key, chunk0, bits: int):
    """Shared per-tile arithmetic on chunk rows: (..., R, 256) f32 values
    (one quant chunk per row), uint32 ``key`` broadcastable against them
    (the client's row key) and ``chunk0``, the index within the client's
    leaf of the tile's first chunk -> (levels int32 in [-bound, bound]
    (..., R, 256), scales f32 (..., R, 1))."""
    nd = v.ndim
    idx = ((chunk0 + jax.lax.broadcasted_iota(jnp.int32, v.shape, nd - 2))
           * QCHUNK + jax.lax.broadcasted_iota(jnp.int32, v.shape, nd - 1))
    u = _uniform_from_idx(
        jax.lax.bitcast_convert_type(idx, jnp.uint32), key)
    s = _pow2_scale_bits(jnp.max(jnp.abs(v), axis=-1, keepdims=True),
                         _EB[bits])
    bound = jnp.float32(_BOUND[bits])
    q = jnp.clip(jnp.floor(v / s + u), -bound, bound)
    return q.astype(jnp.int32), s


def _pack_nibbles(q_i32):
    """int32 levels in [-7, 7], shape (R, 2k) -> two-per-byte uint8 (R, k)
    (bias +8, first element high nibble) — the byte layout of native
    ``pack_i4``. The even/odd lane gather is a matmul against a 0/16/1
    selection matrix: every operand is a small integer, exact in bf16 with
    f32 accumulation, and the MXU is the one unit that moves data across
    lanes with a stride."""
    n = q_i32.shape[1]
    src = jax.lax.broadcasted_iota(jnp.int32, (n, n // 2), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (n, n // 2), 1)
    sel = jnp.where(src == 2 * dst, 16.0,
                    jnp.where(src == 2 * dst + 1, 1.0, 0.0))
    packed = jnp.dot((q_i32 + 8).astype(jnp.bfloat16),
                     sel.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint8)


def _column_to_row(col):
    """(R, 1) f32 scales -> (1, R), so they leave the kernel lane-dense (an
    (R, 1) output is padded to 128 lanes in HBM — 128x its bytes). The
    sublane-to-lane move is an MXU contraction against a one-hot row:
    every scale is 0, 1 or a normal power of two, exact in bf16."""
    rows = col.shape[0]
    wide = jnp.broadcast_to(col, (rows, 128)).astype(jnp.bfloat16)
    pick = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1) == 0)
    out = jax.lax.dot_general(
        pick.astype(jnp.bfloat16), wide, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out[:1]


def _quantize_pack_kernel(h_ref, v_ref, packed_ref, s_ref, dec_ref, *,
                          bits: int, block_rows: int):
    """Grid (C, chunks / block_rows): one client's block of chunk rows per
    program. h (C,) uint32 row keys sit in SMEM (scalar prefetch); v is
    (1, R, 256) f32; outputs packed (1, R, 256) int8 [q8] or (1, R, 128)
    uint8 [q4], s (1, 1, 1, R) f32, dec (1, R, 256) f32."""
    key = h_ref[pl.program_id(0)]
    chunk0 = pl.program_id(1) * block_rows
    q, s = _quant_rows(v_ref[0], key, chunk0, bits)
    s_ref[0, 0] = _column_to_row(s)
    # wire path stores int8 and multiplies back in f32; same values here
    dec_ref[0] = q.astype(jnp.float32) * s
    if bits == 8:
        packed_ref[0] = q.astype(jnp.int8)
    else:
        packed_ref[0] = _pack_nibbles(q)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _block_rows_for(nchunk: int) -> int:
    """Chunk rows per program: the fewest blocks of at most
    ``_MAX_BLOCK_ROWS`` rows, each a multiple of 32 (the int8 sublane
    tile), so a leaf of any size pads by less than 32 rows per block."""
    nblk = -(-nchunk // _MAX_BLOCK_ROWS)
    return _round_up(-(-nchunk // nblk), 32)


def quant_shapes_ok(C: int, m: int) -> bool:
    """True when the fused kernel's tiling handles (C, m): any non-empty
    stack (the per-program working set is bounded by ``_MAX_BLOCK_ROWS``,
    not by the shape)."""
    return C >= 1 and m >= 1


def row_keys(seed: int, round_u32, cids_u32, leaf_hash: int):
    """Per-row base keys: the ``codec.stochastic_key`` mixing chain with the
    traced round/client ids entering as uint32 arrays (identical to the
    unfused ``codec._quant_roundtrip_jnp`` preamble)."""
    h = jnp.uint32((int(seed) ^ 0x9E3779B9) & 0xFFFFFFFF)
    h = _mix32(h ^ jnp.asarray(round_u32).astype(jnp.uint32))
    h = _mix32(h ^ jnp.asarray(cids_u32).astype(jnp.uint32))
    h = _mix32(h ^ jnp.uint32(leaf_hash))
    return h


def fused_quantize_pack(vals, bits: int, seed: int, round_u32, cids_u32,
                        leaf_hash: int = 0, *,
                        interpret: Optional[bool] = None,
                        use_kernel: bool = True,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass stochastic quantize + wire pack + decode over a cohort stack.

    ``vals`` is the (C, m) f32 value stack (one row per client),
    ``round_u32``/``cids_u32`` the traced round scalar and (C,) client-id
    vector. Returns ``(packed, scales, dec)``:

    - ``packed`` — the wire bytes, per row: (C, m) int8 for q8, or
      (C, ceil(m/2)) uint8 nibble-packed for q4. Row ``i`` equals the numpy
      wire path's ``rec["q"]`` for client ``cids[i]`` byte-for-byte
      (``pack_i4``'s odd-tail pad nibble falls out for free: a padded zero
      element stochastically floors to level 0 = biased nibble 8).
    - ``scales`` — (C, ceil(m/256)) f32 per-chunk pow2 scales
      (== ``rec["s"]``).
    - ``dec`` — (C, m) f32 decoded values, bit-identical to the unfused
      ``codec._quant_roundtrip_jnp``.

    ``use_kernel=False`` (or shapes outside :func:`quant_shapes_ok`) takes
    the jittable jnp reference — same arithmetic, no Pallas.
    """
    vals = jnp.asarray(vals, jnp.float32)
    C, m = vals.shape
    h = row_keys(seed, round_u32, cids_u32, leaf_hash)
    if not (use_kernel and quant_shapes_ok(C, m)):
        return _reference_quantize_pack(vals, bits, h)
    if interpret is None:
        # Non-TPU production dispatch takes the bit-identical jnp reference:
        # interpret mode emulates the kernel step by step and is far slower
        # than plain XLA. The parity suite opts in with interpret=True.
        if jax.default_backend() != "tpu":
            return _reference_quantize_pack(vals, bits, h)
        interpret = False

    nchunk = _round_up(m, QCHUNK) // QCHUNK
    rows = _block_rows_for(nchunk)
    npad = _round_up(nchunk, rows)
    grid = (C, npad // rows)
    if interpret and grid[0] * grid[1] > _INTERPRET_GRID_CAP:
        return _reference_quantize_pack(vals, bits, h)
    vp = vals if npad * QCHUNK == m else jnp.zeros(
        (C, npad * QCHUNK), jnp.float32).at[:, :m].set(vals)
    packed_dt = jnp.int8 if bits == 8 else jnp.uint8
    packed_w = QCHUNK if bits == 8 else QCHUNK // 2

    def blk(width):
        return pl.BlockSpec((1, rows, width), lambda c, j, h_ref: (c, j, 0))

    packed, scales, dec = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, bits=bits, block_rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[blk(QCHUNK)],
            out_specs=[
                blk(packed_w),
                pl.BlockSpec((1, 1, 1, rows),
                             lambda c, j, h_ref: (c, j, 0, 0)),
                blk(QCHUNK)]),
        out_shape=[
            jax.ShapeDtypeStruct((C, npad, packed_w), packed_dt),
            jax.ShapeDtypeStruct((C, npad // rows, 1, rows), jnp.float32),
            jax.ShapeDtypeStruct((C, npad, QCHUNK), jnp.float32),
        ],
        interpret=interpret,
    )(h, vp.reshape(C, npad, QCHUNK))
    nbytes = m if bits == 8 else (m + 1) // 2
    return (packed.reshape(C, -1)[:, :nbytes],
            scales.reshape(C, npad)[:, :nchunk], dec.reshape(C, -1)[:, :m])


def _reference_quantize_pack(vals, bits: int, h):
    """Jittable jnp reference: identical arithmetic to the kernel (and to
    ``codec._quant_roundtrip_jnp`` on the decode side), one expression per
    stage instead of one VMEM pass."""
    C, m = vals.shape
    mpad = _round_up(m, QCHUNK)
    vp = jnp.zeros((C, mpad), jnp.float32).at[:, :m].set(vals)
    q, s = _quant_rows(vp.reshape(C, mpad // QCHUNK, QCHUNK),
                       h[:, None, None], 0, bits)
    dec = (q.astype(jnp.float32) * s).reshape(C, mpad)[:, :m]
    if bits == 8:
        return q.astype(jnp.int8).reshape(C, mpad)[:, :m], s[..., 0], dec
    b = (q + 8).reshape(C, mpad // 2, 2)
    packed = ((b[:, :, 0] << 4) | b[:, :, 1]).astype(jnp.uint8)
    return packed[:, :(m + 1) // 2], s[..., 0], dec
