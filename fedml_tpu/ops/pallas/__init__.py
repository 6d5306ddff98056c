"""Pallas TPU kernels for the hot ops (see /opt guide; pallas_guide.md)."""

from .agg_quant import fused_quantize_pack, quant_shapes_ok
from .agg_robust import GramKernelShapeError, fused_gram, robust_shapes_ok
from .flash_attention import flash_attention, flash_shapes_ok, flash_vmem_ok

__all__ = [
    "GramKernelShapeError",
    "flash_attention",
    "flash_shapes_ok",
    "flash_vmem_ok",
    "fused_gram",
    "fused_quantize_pack",
    "quant_shapes_ok",
    "robust_shapes_ok",
]
