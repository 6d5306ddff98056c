"""Pallas Gram-tile kernel for the fused sanitize+Krum robust-agg path.

The unfused defense pipeline reads the stacked cohort three times: once for
``sanitize_stacked``'s non-finite/norm stats, once to materialize the
where-zeroed "clean" copy, and once for ``pairwise_sq_dists``'s Gram
matmul over that copy. The fused path (``core.robust.fused_sanitize_krum``)
collapses the expensive plane to one pass: each (block_c, block_c) tile of
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

Grid is (C/block_c, C/block_c) with full-D operand tiles (no contraction
tiling — a split-K accumulator would change the reduction order and break
bit parity), so the VMEM guard bounds D; oversized shapes take the
jittable reference, which is the same arithmetic in plain jnp. On non-TPU
backends the default dispatch is the reference too — interpret mode
(``interpret=True``) exists for the parity suite, not production.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows of the two operand tiles: one f32 sublane group against one MXU side
_BLOCK_I = 8
_BLOCK_J = 128

# double-buffered full-D operand tiles plus the gram tile, inside Mosaic's
# default scoped VMEM (16 MiB on the v5e) with room for the matmul's own
# temporaries
_VMEM_BUDGET = 8 * 1024 * 1024

# interpret mode (non-TPU) unrolls every grid step into the jaxpr — fine
# for parity-test shapes, catastrophic for a cohort-scale grid (a 10k
# cohort is 1250^2 steps). Past this many steps the interpret path takes
# the reference instead; the kernel-vs-reference bit parity the tests pin
# makes the switch invisible.
_INTERPRET_GRID_CAP = 4096


def robust_shapes_ok(C: int, D: int) -> bool:
    """True when the Gram kernel's tiling handles a (C, D) cohort stack."""
    if C < 1 or D < 1:
        return False
    return 2 * 4 * ((_BLOCK_I + _BLOCK_J) * D
                    + _BLOCK_I * _BLOCK_J) <= _VMEM_BUDGET


def _gram_kernel(b_ref, a_ref, gram_ref):
    """Grid (C/8, C/128). b is a (128, D) and a an (8, D) row tile of the
    sanitized flat stack; the program writes b @ a.T, the TRANSPOSE of
    gram tile (i, j), as a (128, 8) slab of the (C/8, C, 8) output.

    Why transposed: Mosaic wants the last two block dims to be multiples
    of (8, 128) or the whole array dims, so an (8, 8) tile of a (C, C)
    plane is refused while a (128, 8) slab of a (.., C, 8) array is not;
    and on XLA:CPU (interpret mode) a dot whose minor output dim stays 8
    keeps the reference's accumulation order, which a 128-wide output
    does not — the parity suite's bit equality depends on it."""
    gram_ref[0] = jax.lax.dot_general(
        b_ref[...], a_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def fused_gram(flat, *, interpret: Optional[bool] = None,
               use_kernel: bool = True) -> jax.Array:
    """(C, C) f32 Gram matrix ``flat @ flat.T`` of a (C, D) cohort stack,
    in (128, 8) Pallas slabs.

    ``flat`` must already be finite (the caller applies ``nan_to_num``,
    mirroring ``pairwise_sq_dists``). Bit-identical to the vmap/tiled
    matmul forms ``pairwise_sq_dists`` lowers to — pinned by the parity
    suite. Cohorts are padded to a multiple of 128 with zero rows (pad
    outputs are sliced away; zero rows cannot perturb real elements'
    bits). Shapes outside :func:`robust_shapes_ok` (or
    ``use_kernel=False``) take the jittable jnp reference.
    """
    flat = jnp.asarray(flat, jnp.float32)
    C, D = flat.shape
    if not (use_kernel and robust_shapes_ok(C, D)):
        return _reference_gram(flat)
    if interpret is None:
        # Non-TPU production dispatch takes the bit-identical jnp reference:
        # interpret mode emulates the kernel step by step and is far slower
        # than plain XLA. The parity suite opts in with interpret=True.
        if jax.default_backend() != "tpu":
            return _reference_gram(flat)
        interpret = False

    cpad = -(-C // _BLOCK_J) * _BLOCK_J
    grid = (cpad // _BLOCK_I, cpad // _BLOCK_J)
    if interpret and grid[0] * grid[1] > _INTERPRET_GRID_CAP:
        return _reference_gram(flat)
    fp = flat if cpad == C else jnp.concatenate(
        [flat, jnp.zeros((cpad - C, D), jnp.float32)], axis=0)
    slabs = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_J, D), lambda i, j: (j, 0)),
            pl.BlockSpec((_BLOCK_I, D), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, _BLOCK_J, _BLOCK_I),
                               lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (cpad // _BLOCK_I, cpad, _BLOCK_I), jnp.float32),
        interpret=interpret,
    )(fp, fp)
    # slabs[i, c, r] = <flat[c], flat[8 i + r]> = gram[c, 8 i + r]
    gram = slabs.transpose(1, 0, 2).reshape(cpad, cpad)
    return gram[:C, :C]


def _reference_gram(flat):
    """Jittable jnp reference: ``pairwise_sq_dists``'s exact untiled form."""
    return jax.vmap(lambda r: flat @ r)(flat)
