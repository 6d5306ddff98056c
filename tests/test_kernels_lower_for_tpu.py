"""Every pallas_call site lowers for the TPU — checked on the CPU.

``jit(f).trace(*shapes).lower(lowering_platforms=("tpu",))`` with
``interpret=False`` runs the Pallas -> Mosaic lowering without a chip: it
is where a BlockSpec that breaks the (8, 128) rule or a cast Mosaic lacks
is refused. (What libtpu's Mosaic compiler then makes of the kernel —
scoped VMEM above all — only ``chip_smoke.py`` on the chip can say.) It
keeps a kernel PR from spending chip minutes on an error this finds in
seconds."""

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.ops.conv import conv2d_pallas
from fedml_tpu.ops.pallas import (
    GramKernelShapeError,
    flash_attention,
    fused_gram,
    fused_quantize_pack,
    robust_shapes_ok,
)


def _mosaic_calls(fn, *shapes) -> int:
    lowered = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# a wide cohort; MNIST-LR's flattened update; the flagship's cohort at
# ResNet-56's — the widest the full-row tiles hold (8-row j tile)
@pytest.mark.parametrize("C,D", [(16, 4096), (1000, 4096), (1000, 7850),
                                 (10, 855770)])
def test_fused_gram_lowers(C, D):
    assert _mosaic_calls(lambda f: fused_gram(f, interpret=False),
                         _sds((C, D), jnp.float32)) == 1


def test_fused_gram_past_its_width_is_an_error_not_the_reference():
    """No kernel exists past the VMEM bound: the compiled dispatch says so
    by type; only interpret mode (the parity suite's) takes the reference."""
    wide = _sds((10, 1_000_000), jnp.float32)
    assert not robust_shapes_ok(*wide.shape)
    with pytest.raises(GramKernelShapeError, match="agg_kernels off"):
        jax.eval_shape(lambda f: fused_gram(f, interpret=False), wide)
    assert jax.eval_shape(lambda f: fused_gram(f, interpret=True),
                          wide).shape == (10, 10)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m", [4096, 65536])
def test_fused_quantize_pack_lowers(bits, m):
    C = 1000
    assert _mosaic_calls(
        lambda v, r, c: fused_quantize_pack(v, bits, 13, r, c, 7,
                                            interpret=False),
        _sds((C, m), jnp.float32), _sds((), jnp.uint32),
        _sds((C,), jnp.uint32)) == 1


@pytest.mark.parametrize("Dh", [64, 128])
def test_flash_forward_and_backward_lower(Dh, monkeypatch):
    # the kernel picks interpret mode from the backend it is traced on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _sds((1, 4096, 2, Dh), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True).astype(jnp.float32).sum()

    # forward, dq, dk/dv
    assert _mosaic_calls(jax.grad(loss, (0, 1, 2)), x, x, x) == 3


@pytest.mark.parametrize("hw,c", [(32, 16), (16, 32), (8, 64)])
def test_conv2d_pallas_lowers_at_resnet56_stages(hw, c):
    """Forward + both gradients, alone and under the lane vmap that makes
    it the multi-weight kernel."""
    def loss(x, w):
        return (conv2d_pallas(x, w, 1, "SAME").astype(jnp.float32) ** 2).sum()

    x, w = _sds((64, hw, hw, c), jnp.bfloat16), _sds((3, 3, c, c), jnp.bfloat16)
    assert _mosaic_calls(jax.grad(loss, (0, 1)), x, w) == 3
    lanes = lambda s: _sds((2,) + s.shape, s.dtype)  # noqa: E731
    assert _mosaic_calls(jax.vmap(jax.grad(loss, (0, 1))),
                         lanes(x), lanes(w)) == 3
