"""Pallas flash attention: numerics vs dense, causal masking, gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.attention import multihead_attention
from fedml_tpu.ops.pallas import flash_attention, flash_shapes_ok


def _qkv(B=2, T=256, H=2, Dh=64, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, Dh)), jnp.float32)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    dense = multihead_attention(q, k, v, causal=causal, impl="dense")
    flash = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(T=128)

    def loss_flash(q, k, v):
        # non-uniform cotangent so dq/dk/dv all get exercised beyond sum()
        out = flash_attention(q, k, v, causal)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape) * 0.01)).sum()

    def loss_dense(q, k, v):
        out = multihead_attention(q, k, v, causal=causal, impl="dense")
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape) * 0.01)).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_gradients_long_context_T1024():
    """review #8 done-criterion: grad-vs-dense allclose at T=1024 and the
    (T, T) buffer absent from the compiled flash backward."""
    q, k, v = _qkv(B=1, T=1024, H=1, Dh=64, seed=3)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True).sum()

    def loss_dense(q, k, v):
        return multihead_attention(q, k, v, causal=True, impl="dense").sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    # memory assertion: no (1024, 1024) intermediate anywhere in the flash
    # grad program; the dense grad program must contain one (sanity check
    # that the probe actually detects the buffer).
    flash_hlo = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2))).lower(
        q, k, v).as_text()
    dense_hlo = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2))).lower(
        q, k, v).as_text()
    assert "1024x1024" not in flash_hlo
    assert "1024x1024" in dense_hlo


def test_auto_impl_memory_aware():
    """Dispatch goes flash below the T=4096 speed crossover whenever one
    layer's saved dense probabilities would cross 512 MB (the MFU-bench
    lesson: 12 layers x 2.15 GB of probs at B=16 H=16 T=2048 = 26 GB)."""
    from fedml_tpu.ops.attention import auto_attention_impl

    assert auto_attention_impl(4, 8, 2048, 64) == "dense"    # 268 MB: speed
    assert auto_attention_impl(16, 16, 2048, 64) == "flash"  # 2.1 GB/layer
    assert auto_attention_impl(1, 1, 8192, 64) == "flash"    # past crossover
    # memory wants flash but shapes refuse (lane-hostile Dh) -> dense
    assert auto_attention_impl(16, 16, 2048, 48) == "dense"


def test_auto_dispatch_guard():
    assert flash_shapes_ok(256, 64)
    assert flash_shapes_ok(1024, 128)
    assert not flash_shapes_ok(100, 64)   # ragged T
    assert not flash_shapes_ok(256, 48)   # lane-hostile Dh


def test_shapes_gate_is_t_independent():
    """The K-blocked kernel's VMEM use is O(block * Dh), so the gate no
    longer depends on T (the round-2 full-K/V cap at T~12k is gone) —
    only block divisibility and lane-friendly Dh matter."""
    from fedml_tpu.ops.pallas import flash_shapes_ok, flash_vmem_ok

    assert flash_shapes_ok(12288, 64, itemsize=2)
    assert flash_shapes_ok(16384, 64, itemsize=2)   # round-2 measured fail
    assert flash_shapes_ok(65536, 64, itemsize=2)   # long context single chip
    assert flash_shapes_ok(16384, 64, itemsize=4)   # f32 no longer halves T
    assert not flash_shapes_ok(12288 + 100, 64)     # block divisibility
    assert not flash_shapes_ok(12288, 48)           # lane-unfriendly Dh
    assert flash_vmem_ok(65536, 64) and flash_vmem_ok(65536, 128)


def test_auto_block_is_lane_legal():
    """Blocks must be multiples of 128 (Mosaic lane dim) that divide T."""
    from fedml_tpu.ops.pallas.flash_attention import auto_block

    assert auto_block(8192) == 1024
    assert auto_block(1024) == 512    # measured: T<=1024 prefers T//2
    assert auto_block(12288) == 1024
    assert auto_block(640) == 128     # 320 divides but is lane-illegal
    assert auto_block(384) == 128
    assert auto_block(100) is None
    for T in (256, 384, 640, 896, 2048, 12288):
        b = auto_block(T)
        assert b % 128 == 0 and T % b == 0


def test_shapes_gate_rejects_oversized_explicit_blocks():
    """flash_shapes_ok must veto block sizes the VMEM budget can't hold
    (2048 blocks fail to compile on the v5e)."""
    from fedml_tpu.ops.pallas import flash_shapes_ok

    assert flash_shapes_ok(8192, 64, block_q=1024, block_k=1024)
    assert not flash_shapes_ok(8192, 64, block_q=2048, block_k=2048)


def test_auto_dispatch_warns_on_long_dense_fallback(caplog):
    """An untileable long T falls back to dense LOUDLY (O(T^2) HBM)."""
    import logging

    import jax.numpy as jnp

    from fedml_tpu.ops.attention import multihead_attention

    q = jnp.zeros((1, 8192 + 8, 1, 64), jnp.bfloat16)  # 8200: no 128-divisor
    with caplog.at_level(logging.WARNING):
        multihead_attention(q, q, q)
    assert "DENSE O(T^2)" in caplog.text


def test_auto_dispatch_uses_flash_at_long_t(caplog):
    """T=16384 — the round-2 dense-fallback length — now dispatches to the
    K-blocked flash kernel with no VMEM warning."""
    import logging

    import jax.numpy as jnp

    from fedml_tpu.ops.attention import multihead_attention

    q = jnp.zeros((1, 16384, 1, 64), jnp.bfloat16)
    with caplog.at_level(logging.WARNING):
        out = multihead_attention(q, q, q)
    assert out.shape == q.shape
    assert "DENSE" not in caplog.text  # no dense fallback = flash engaged
