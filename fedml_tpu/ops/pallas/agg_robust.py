"""Pallas Gram-tile kernel for the fused sanitize+Krum robust-agg path.

The unfused defense pipeline reads the stacked cohort three times: once for
``sanitize_stacked``'s non-finite/norm stats, once to materialize the
where-zeroed "clean" copy, and once for ``pairwise_sq_dists``'s Gram
matmul over that copy. The fused path (``core.robust.fused_sanitize_krum``)
collapses the expensive plane to one pass: each tile of
``Z @ Z.T`` is computed here from the RAW (nan-sanitized) stack — the
clean copy is never materialized — and quarantine masking is applied
algebraically afterwards with exact ``where`` masks: zeroing a row of a
matmul operand cannot change any OTHER element's bits (element (i, j)
reads only rows i and j), so ``sanitize -> zero copy -> Gram`` and
``Gram -> mask`` produce identical distance bits.

The kernel deliberately emits ONLY the Gram plane. An earlier revision
also emitted the per-leaf squared-norm segments from column slices of the
fused row tiles, but XLA's reduction order for a strided row-slice sum is
shape-dependent — a ``sum(square(x[:, 40:64]), axis=1)`` over an (8, 64)
VMEM tile and the oracle's contiguous per-leaf ``(C, 24)`` sum disagreed
by 1 ULP on some widths. Those O(C*D) statistics are therefore computed by
the orchestration layer with the oracle's own expressions on the oracle's
own shapes (structural identity => identical bits on every backend),
while the O(C^2*D) Gram plane — whose cross-form bit-determinism
(vmap row matmul == lax.map row tiles == this kernel's dot_general tiles)
the parity suite pins down — stays fused.

Grid is (C/8, C/block_j) with full-D operand tiles (no contraction
tiling — a split-K accumulator would change the reduction order and break
bit parity), so VMEM bounds D: two double-buffered row tiles of at least 8
rows each must fit, which on the v5e's 128 MiB stops at D = 917,376 f32
columns (ResNet-56's flattened update is 855,770). The j tile takes up to
128 rows where the width leaves room and narrows to 8 as D grows. Past the
bound there is no kernel: the compiled dispatch raises
:class:`GramKernelShapeError` rather than computing something else under
the kernel's name. On non-TPU backends the default dispatch is the jnp
reference — interpret mode (``interpret=True``) exists for the parity
suite, not production.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of the i tile: one f32 sublane group. The j tile takes up to
# _MAX_BLOCK_J rows (one MXU side) where VMEM allows.
_BLOCK_I = 8
_MAX_BLOCK_J = 128

# what the operand and output windows may take of the v5e's 128 MiB of
# VMEM; Mosaic's default scoped limit (16 MiB) is raised to what a shape
# needs, never past this. A part with less VMEM refuses at compile time.
_VMEM_CAP = 112 * 1024 * 1024
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024

# interpret mode (non-TPU) unrolls every grid step into the jaxpr — fine
# for parity-test shapes, catastrophic for a cohort-scale grid (a 10k
# cohort is 1250 x 79 steps). Past this many steps the interpret path
# takes the reference instead; the kernel-vs-reference bit parity the
# tests pin makes the switch invisible.
_INTERPRET_GRID_CAP = 4096


class GramKernelShapeError(ValueError):
    """The compiled Gram kernel was asked for a (C, D) stack whose full-D
    row tiles do not fit VMEM. Raised instead of falling back: a caller
    that asked for the kernel on a TPU must not get the reference quietly.
    Turn ``agg_kernels`` off for such a model."""


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _window_bytes(block_j: int, D: int) -> int:
    """Mosaic's VMEM windows for one program, double-buffered: the
    (block_j, D) and (8, D) operand tiles with D padded to whole 128-lane
    vregs, and the (block_j, 8) output slab padded to 128 lanes."""
    return 2 * 4 * ((block_j + _BLOCK_I) * _round_up(D, 128) + block_j * 128)


def _block_j(C: int, D: int) -> int:
    """Rows of the j tile for a (C, D) stack: the fewest j blocks of at
    most ``_MAX_BLOCK_J`` rows (fewer where D leaves less VMEM), each a
    multiple of 8. 0 when not even 8 rows fit."""
    dp = _round_up(D, 128)
    # _window_bytes(rows, D) <= _VMEM_CAP, solved for rows
    fits = (_VMEM_CAP // 8 - _BLOCK_I * dp) // (dp + 128) // 8 * 8
    widest = min(_MAX_BLOCK_J, fits)
    if widest < 8:
        return 0
    nblk = -(-C // widest)
    return _round_up(-(-C // nblk), 8)


def robust_shapes_ok(C: int, D: int) -> bool:
    """True when the Gram kernel's tiling handles a (C, D) cohort stack."""
    return C >= 1 and D >= 1 and _block_j(C, D) > 0


def _gram_kernel(b_ref, a_ref, gram_ref):
    """Grid (C/8, C/block_j). b is a (block_j, D) and a an (8, D) row tile
    of the sanitized flat stack; the program writes b @ a.T, the TRANSPOSE
    of gram tile (i, j), as a (block_j, 8) slab of the (C/8, C, 8) output.

    Why transposed: Mosaic wants the last two block dims to be multiples
    of (8, 128) or the whole array dims, so an (8, 8) tile of a (C, C)
    plane is refused while a (block_j, 8) slab of a (.., C, 8) array is
    not; and on XLA:CPU (interpret mode) a dot whose minor output dim
    stays 8 keeps the reference's accumulation order, which a 128-wide
    output does not — the parity suite's bit equality depends on it."""
    gram_ref[0] = jax.lax.dot_general(
        b_ref[...], a_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def fused_gram(flat, *, interpret: Optional[bool] = None,
               use_kernel: bool = True) -> jax.Array:
    """(C, C) f32 Gram matrix ``flat @ flat.T`` of a (C, D) cohort stack,
    in (block_j, 8) Pallas slabs.

    ``flat`` must already be finite (the caller applies ``nan_to_num``,
    mirroring ``pairwise_sq_dists``). Bit-identical to the vmap/tiled
    matmul forms ``pairwise_sq_dists`` lowers to — pinned by the parity
    suite. Cohorts are padded to whole j blocks with zero rows (pad
    outputs are sliced away; zero rows cannot perturb real elements'
    bits).

    Which path runs: ``use_kernel=False`` and the non-TPU default take the
    jittable jnp reference. The compiled kernel (a TPU's default, or
    ``interpret=False``) raises :class:`GramKernelShapeError` for a shape
    outside :func:`robust_shapes_ok`; interpret mode, which only the
    parity suite asks for, takes the reference there instead.
    """
    flat = jnp.asarray(flat, jnp.float32)
    C, D = flat.shape
    if not use_kernel:
        return _reference_gram(flat)
    if interpret is None:
        # Non-TPU production dispatch takes the bit-identical jnp reference:
        # interpret mode emulates the kernel step by step and is far slower
        # than plain XLA. The parity suite opts in with interpret=True.
        if jax.default_backend() != "tpu":
            return _reference_gram(flat)
        interpret = False
    if not robust_shapes_ok(C, D):
        if interpret:
            return _reference_gram(flat)
        raise GramKernelShapeError(
            f"fused_gram: a ({C}, {D}) stack does not fit the compiled "
            f"kernel — two double-buffered full-width row tiles need "
            f"{_window_bytes(8, max(D, 1)) >> 20} MiB of VMEM against "
            f"{_VMEM_CAP >> 20} MiB; run this model with agg_kernels off")

    bj = _block_j(C, D)
    cpad = _round_up(C, bj)
    grid = (cpad // _BLOCK_I, cpad // bj)
    if interpret and grid[0] * grid[1] > _INTERPRET_GRID_CAP:
        return _reference_gram(flat)
    fp = flat if cpad == C else jnp.concatenate(
        [flat, jnp.zeros((cpad - C, D), jnp.float32)], axis=0)
    slabs = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bj, D), lambda i, j: (j, 0)),
            pl.BlockSpec((_BLOCK_I, D), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bj, _BLOCK_I), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (cpad // _BLOCK_I, cpad, _BLOCK_I), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            _DEFAULT_SCOPED_VMEM, _window_bytes(bj, D) + (2 << 20))),
        interpret=interpret,
    )(fp, fp)
    # slabs[i, c, r] = <flat[c], flat[8 i + r]> = gram[c, 8 i + r]
    gram = slabs.transpose(1, 0, 2).reshape(cpad, cpad)
    return gram[:C, :C]


def _reference_gram(flat):
    """Jittable jnp reference: ``pairwise_sq_dists``'s exact untiled form."""
    return jax.vmap(lambda r: flat @ r)(flat)
