"""Pallas flash attention: numerics vs dense, causal masking, gradients."""

import sys

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


# (T, Dh): T = 128 runs one square block; at T = 256 and 512 the default
# blocks are T itself, a diagonal block of two and four stripes; Dh = 64
# puts two heads in a lane tile, Dh = 128 one
SHAPES = [(128, 64), (256, 64), (512, 64), (256, 128), (512, 128)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T,Dh", SHAPES[1:])
def test_flash_matches_dense(causal, T, Dh):
    q, k, v = _qkv(T=T, Dh=Dh, H=256 // Dh)
    dense = multihead_attention(q, k, v, causal=causal, impl="dense")
    flash = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


@pytest.mark.parametrize("causal,T,Dh,fused", [
    *[(causal, 128, 64, True) for causal in (False, True)],
    *[(True, T, Dh, fused) for T, Dh in SHAPES[1:] for fused in (True, False)],
    (False, 512, 128, False)])
def test_flash_gradients_match_dense(causal, T, Dh, fused, monkeypatch):
    """dq, dk, dv against dense at blocks of T: one square at T = 128, a
    striped diagonal block beyond, through the fused backward and the split
    pair (the limit patched as ``test_flash_backward_fused_and_split_agree``
    patches it)."""
    import sys

    mod = sys.modules["fedml_tpu.ops.pallas.flash_attention"]
    H = 128 // Dh
    if not fused:  # room for the split pair's tiles, none for the fused dq
        monkeypatch.setattr(mod, "_VMEM_LIMIT", mod._bwd_vmem(
            T, T, T, 128, H, 4, fused=False))
    jax.clear_caches()
    q, k, v = _qkv(B=1, T=T, H=H, Dh=Dh)
    w = jnp.cos(jnp.arange(q.size).reshape(q.shape) * 0.01)

    def loss_flash(q, k, v):
        # non-uniform cotangent so dq/dk/dv all get exercised beyond sum()
        return (flash_attention(q, k, v, causal, T, T) * w).sum()

    def loss_dense(q, k, v):
        return (multihead_attention(q, k, v, causal=causal, impl="dense")
                * w).sum()

    grad = jax.grad(loss_flash, argnums=(0, 1, 2))
    assert str(jax.make_jaxpr(grad)(q, k, v)).count("pallas_call") == (
        2 if fused else 3)
    gf = grad(q, k, v)
    jax.clear_caches()
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_gradients_long_context_T1024():
    """review #8 done-criterion: grad-vs-dense allclose at T=1024 and the
    (T, T) buffer absent from the compiled flash backward."""
    q, k, v = _qkv(B=1, T=1024, H=1, Dh=64, seed=3)

    def loss_flash(q, k, v):
        # 512 blocks, so that a (1024, 1024) tensor could only be the scores
        # (the forward's default block at this length is the whole of T)
        return flash_attention(q, k, v, True, 512, 512).sum()

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
    """Off the chip the kernel is interpreted, so the speed rule stays at
    T >= 4096 there; the memory rule sends a layer whose saved dense
    probabilities would cross 512 MB to flash on every platform (the
    MFU-bench lesson: 12 layers x 2.15 GB of probs at B=16 H=16 T=2048 =
    26 GB)."""
    from fedml_tpu.ops.attention import auto_attention_impl

    assert jax.default_backend() == "cpu"
    assert auto_attention_impl(4, 8, 2048, 64) == "dense"    # 268 MB: speed
    assert auto_attention_impl(8, 16, 1024, 64) == "dense"   # the LM cell
    assert auto_attention_impl(16, 16, 2048, 64) == "flash"  # 2.1 GB/layer
    assert auto_attention_impl(1, 1, 8192, 64) == "flash"    # past crossover
    # memory wants flash but shapes refuse (lane-hostile Dh) -> dense
    assert auto_attention_impl(16, 16, 2048, 48) == "dense"


# (B, H, T, Dh): the LM cell; FedNLP's longest; ViT-B/16 at 224; a length
# past every rule; the memory rule; a lane-hostile head
@pytest.mark.parametrize("shape,on_tpu,off_tpu", [
    ((8, 16, 1024, 64), "flash", "dense"),
    ((4, 8, 2048, 64), "flash", "dense"),
    ((8, 16, 512, 64), "dense", "dense"),
    ((8, 12, 197, 64), "dense", "dense"),
    ((1, 1, 8192, 64), "flash", "flash"),
    ((16, 16, 2048, 64), "flash", "flash"),
    ((8, 16, 1024, 48), "dense", "dense"),
])
def test_auto_impl_follows_platform_and_shape(shape, on_tpu, off_tpu,
                                              monkeypatch):
    """One rule over platform and shape: on a TPU backend the kernel takes
    T >= 1024 (PR 27's sweep); off it the rule does not widen."""
    from fedml_tpu.ops.attention import auto_attention_impl

    assert auto_attention_impl(*shape) == off_tpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert auto_attention_impl(*shape) == on_tpu
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert auto_attention_impl(*shape) == off_tpu


@pytest.fixture(scope="module")
def lm_cell_grads():
    """The LM cell's attention (T=1024, Dh=64, bf16, causal) at a small
    B x H, interpreted, at the blocks ``auto_block`` adopts: output and the
    three gradients, beside dense run in float32 on the same bf16 values."""
    rng = np.random.default_rng(5)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, 1024, 2, 64)),
                              jnp.bfloat16) for _ in range(4))
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def run(attn, cast):
        out, vjp = jax.vjp(attn, cast(q), cast(k), cast(v))
        return (out, *vjp(cast(w)))

    got = run(lambda q, k, v: flash_attention(q, k, v, True), lambda t: t)
    want = run(lambda q, k, v: multihead_attention(
        q, k, v, causal=True, impl="dense"), f32)
    return [(np.asarray(f32(g)), np.asarray(r)) for g, r in zip(got, want)]


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
def test_flash_bf16_at_the_lm_cell_shape(lm_cell_grads, which):
    """bf16 operands into the MXU, float32 softmax state: within bf16's
    rounding (2**-8 relative) of dense in float32, on every tensor."""
    got, want = lm_cell_grads[["out", "dq", "dk", "dv"].index(which)]
    assert got.shape == want.shape == (1, 1024, 2, 64)
    assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max()
    # and not by chance of scale: the mean error is far inside it
    assert np.abs(got - want).mean() <= 2.0 ** -8 * np.abs(want).mean()


@pytest.mark.parametrize("H,Dh,hp", [(16, 64, 2), (12, 64, 2), (8, 128, 1),
                                     (1, 64, 1), (3, 64, 3), (4, 256, 1)])
def test_heads_per_step_fills_whole_lane_tiles(H, Dh, hp):
    from fedml_tpu.ops.pallas.flash_attention import heads_per_step

    assert heads_per_step(H, Dh) == hp
    assert H % hp == 0 and ((hp * Dh) % 128 == 0 or hp == H)


@pytest.mark.parametrize("H,Dh", [(3, 64), (2, 128), (4, 64)])
def test_flash_keeps_heads_apart(H, Dh):
    """Heads that share a lane tile (and an odd count that shares one
    block) come out as if each ran alone."""
    q, k, v = _qkv(B=1, T=256, H=H, Dh=Dh, seed=9)
    together = flash_attention(q, k, v, True)
    for h in range(H):
        alone = flash_attention(q[:, :, h:h + 1], k[:, :, h:h + 1],
                                v[:, :, h:h + 1], True)
        np.testing.assert_allclose(np.asarray(together[:, :, h:h + 1]),
                                   np.asarray(alone), atol=2e-6)


@pytest.mark.parametrize("fused", [True, False])
def test_flash_backward_fused_and_split_agree(fused, monkeypatch):
    """Where the whole sequence's dq does not fit VMEM it has its own
    kernel: same gradients."""
    import sys

    mod = sys.modules["fedml_tpu.ops.pallas.flash_attention"]
    if not fused:  # room for the 128 x 256 tiles, none for 512 rows of dq
        monkeypatch.setattr(mod, "_VMEM_LIMIT", mod._bwd_vmem(
            512, 128, 256, 128, 2, 4, fused=False))
    # the jitted launcher's cache does not see the limit: trace anew, and
    # leave no trace made under a patched limit behind
    jax.clear_caches()
    q, k, v = _qkv(B=1, T=512, H=2, Dh=64, seed=4)
    w = jnp.cos(jnp.arange(q.size).reshape(q.shape) * 0.01)
    flash = jax.grad(lambda q, k, v: (flash_attention(q, k, v, True, 128, 256)
                                      * w).sum(), (0, 1, 2))
    assert str(jax.make_jaxpr(flash)(q, k, v)).count("pallas_call") == (
        2 if fused else 3)
    gf = flash(q, k, v)
    jax.clear_caches()
    gd = jax.grad(lambda q, k, v: (multihead_attention(
        q, k, v, causal=True, impl="dense") * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_dispatch_counter_advances_once_per_traced_call_site(monkeypatch):
    """``fedml_attention_dispatch_total{impl, seq_len}`` counts where
    ``multihead_attention`` resolves ``impl``, whatever the rule says: at
    trace time, so a compiled call adds nothing."""
    from fedml_tpu.core.telemetry import get_registry
    from fedml_tpu.ops import attention

    def count(T=128):
        return tuple(get_registry().counter(
            "fedml_attention_dispatch_total", impl=impl, seq_len=T).value
            for impl in ("dense", "flash"))

    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    dense0, flash0 = count()

    @jax.jit
    def three_sites(q):
        a = multihead_attention(q, q, q, causal=True)          # auto: dense
        with monkeypatch.context() as m:
            m.setattr(attention, "auto_attention_impl", lambda *s: "flash")
            a = multihead_attention(a, a, a, causal=True)      # auto: flash
        # named, not resolved: nothing to count
        return multihead_attention(a, a, a, causal=True, impl="flash")

    three_sites(q)
    assert count() == (dense0 + 1, flash0 + 1)
    three_sites(q)  # compiled: nothing is traced again
    assert count() == (dense0 + 1, flash0 + 1)
    multihead_attention(q, q, q, causal=True)  # eager: no program, no count
    assert count() == (dense0 + 1, flash0 + 1)
    # a shape the kernel cannot take is a dispatch like any other, under its
    # own length: ViT-B/16's 197 tokens, and the eight that the trainer's
    # jitted ``model.init`` is traced over
    for T in (197, 8):
        before = count(T)
        short = jnp.zeros((1, T, 2, 64), jnp.float32)
        jax.jit(lambda x: multihead_attention(x, x, x))(short)
        assert count(T) == (before[0] + 1, before[1])
    assert count() == (dense0 + 1, flash0 + 1)


def test_diagonal_counter_counts_once_per_traced_call_site():
    """``fedml_flash_diagonal_total{pass, impl, seq_len}``: ``striped`` where a
    causal call's diagonal block is two stripes tall or more, ``square``
    under that; once per call site per trace (the forward where the caller
    traces it, the backward where its rule is traced), nothing at run time,
    nothing for a call that masks nothing."""
    from fedml_tpu.core.telemetry import get_registry
    from fedml_tpu.ops.pallas.flash_attention import STRIPE

    def count(T):
        return {(p, impl): get_registry().counter(
            "fedml_flash_diagonal_total", impl=impl, seq_len=T,
            **{"pass": p}).value
            for p in ("fwd", "bwd") for impl in ("striped", "square")}

    def after(T, fn, *args):
        before = count(T)
        fn(*args)
        return {key: n - before[key] for key, n in count(T).items()
                if n != before[key]}

    q = jnp.zeros((1, 2 * STRIPE, 2, 64), jnp.float32)
    two_sites = jax.jit(lambda q: flash_attention(
        flash_attention(q, q, q, True), q, q, True))
    assert after(2 * STRIPE, two_sites, q) == {("fwd", "striped"): 2}
    assert after(2 * STRIPE, two_sites, q) == {}          # compiled
    grad = jax.jit(jax.grad(lambda q: flash_attention(q, q, q, True).sum()))
    assert after(2 * STRIPE, grad, q) == {("fwd", "striped"): 1,
                                          ("bwd", "striped"): 1}
    assert after(2 * STRIPE, jax.jit(lambda q: flash_attention(
        q, q, q, False)), q) == {}                          # no diagonal
    assert after(2 * STRIPE, flash_attention, q, q, q, True) == {}  # eager
    short = jnp.zeros((1, STRIPE, 2, 64), jnp.float32)      # one stripe tall
    assert after(STRIPE, grad, short) == {("fwd", "square"): 1,
                                          ("bwd", "square"): 1}


def test_flash_striped_share_reads_the_lm_cell(monkeypatch):
    """``benchmark/layer_metrics/flash_striped_share.lm.json`` through its
    reader, at the GPT-2 cell's traffic: nothing before a causal call at
    ``seq_len`` is traced (as on the parent, which has no such counter), 100
    once the cell's attention is (a forward block of 1024 and backward
    blocks of 512, all striped); the step is traced, never run."""
    import importlib.util
    import json
    import os
    import sys

    from fedml_tpu.core import telemetry

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    reg = telemetry.MetricsRegistry(enabled=True)
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    monkeypatch.setattr(sys.modules["fedml_tpu.ops.pallas.flash_attention"],
                        "get_registry", lambda: reg)
    spec = importlib.util.spec_from_file_location(
        "bench_program_counter",
        os.path.join(bench, "readers", "program_counter.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with open(os.path.join(bench, "layer_metrics",
                           "flash_striped_share.lm.json")) as f:
        metric = json.load(f)
    with open(os.path.join(bench, "traffic", "t1024_b8_pretrain.json")) as f:
        ctx = {"traffic": json.load(f)}
    assert metric["reader"] == "program_counter"
    assert reader.read(ctx, **metric["args"]) is None
    q = jax.ShapeDtypeStruct((1, ctx["traffic"]["seq_len"], 2, 64),
                             jnp.bfloat16)
    jax.jit(jax.grad(lambda q: flash_attention(q, q, q, True).astype(
        jnp.float32).sum())).trace(q)
    assert reader.read(ctx, **metric["args"]) == 100.0


def test_auto_dispatch_guard():
    assert flash_shapes_ok(256, 64)
    assert flash_shapes_ok(1024, 128)
    assert not flash_shapes_ok(100, 64)   # ragged T
    assert not flash_shapes_ok(256, 48)   # lane-hostile Dh


def test_shapes_gate_is_t_independent():
    """The forward's and the split backward's VMEM use is O(block * W), so
    the gate no longer depends on T (the round-2 full-K/V cap at T~12k is
    gone) — only block divisibility and lane-friendly Dh matter."""
    from fedml_tpu.ops.pallas import flash_shapes_ok, flash_vmem_ok

    assert flash_shapes_ok(12288, 64, itemsize=2)
    assert flash_shapes_ok(16384, 64, itemsize=2)   # round-2 measured fail
    assert flash_shapes_ok(65536, 64, itemsize=2)   # long context single chip
    assert flash_shapes_ok(16384, 64, itemsize=4)   # f32 no longer halves T
    assert not flash_shapes_ok(12288 + 100, 64)     # block divisibility
    assert not flash_shapes_ok(12288, 48)           # lane-unfriendly Dh
    assert flash_vmem_ok(65536, 64) and flash_vmem_ok(65536, 128)


def test_auto_block_is_lane_legal():
    """Blocks must be multiples of 128 (Mosaic lane dim) that divide T; each
    pass caps them at what PR 27's sweep found fastest."""
    from fedml_tpu.ops.pallas.flash_attention import (
        BWD_BLOCK,
        FWD_BLOCK,
        auto_block,
    )

    assert auto_block(8192) == 1024
    assert auto_block(1024) == 1024
    assert auto_block(1024, 512) == 512
    assert auto_block(12288) == 1024
    assert auto_block(640) == 128     # 320 divides but is lane-illegal
    assert auto_block(384) == 128
    assert auto_block(100) is None
    for T in (256, 384, 640, 896, 2048, 12288):
        for largest in (FWD_BLOCK, BWD_BLOCK):
            b = auto_block(T, largest)
            assert b % 128 == 0 and T % b == 0 and b <= largest


def test_shapes_gate_rejects_oversized_explicit_blocks():
    """flash_shapes_ok must veto block sizes the VMEM budget can't hold
    (2048 blocks fail to compile on the v5e; so do 1024 ones in float32,
    in the split backward)."""
    from fedml_tpu.ops.pallas import flash_shapes_ok

    assert flash_shapes_ok(8192, 64, block_q=1024, block_k=1024)
    assert not flash_shapes_ok(8192, 64, block_q=2048, block_k=2048)
    assert not flash_shapes_ok(8192, 64, block_q=1024, block_k=1024,
                               itemsize=4)


# what Mosaic did with each at its default 16 MiB, compiled ahead of time for
# the v5e at B = 2 and two head groups (PR 27, PERF.md section 6): the fused
# backward at 512 squares, (T, H, Dh, itemsize) -> compiled
FUSED_COMPILED_FOR_V5E = [
    (8192, 4, 64, 2, True), (12288, 4, 64, 2, False),
    (4096, 4, 64, 4, True), (8192, 4, 64, 4, False),
    (8192, 2, 128, 2, True), (16384, 2, 128, 2, False),
    (4096, 2, 128, 4, True), (8192, 2, 128, 4, False),
    (4096, 2, 256, 2, True), (8192, 2, 256, 2, False),
    (2048, 2, 256, 4, False), (4096, 2, 256, 4, False)]


@pytest.mark.parametrize("T,H,Dh,itemsize,compiled", FUSED_COMPILED_FOR_V5E)
def test_fused_backward_is_taken_only_where_mosaic_took_it(T, H, Dh, itemsize,
                                                           compiled):
    """The rule is bytes, not a length: dq's whole-sequence scratch and
    output grow with T, the tile's width and the dtype."""
    from fedml_tpu.ops.pallas.flash_attention import (
        _VMEM_LIMIT,
        _bwd_vmem,
        heads_per_step,
    )

    hp = heads_per_step(H, Dh)
    need = _bwd_vmem(T, 512, 512, hp * Dh, hp, itemsize, fused=True)
    assert (need <= _VMEM_LIMIT) == compiled


@pytest.mark.parametrize("H,Dh,itemsize,blocks", [
    (16, 64, 2, (1024, 512)), (16, 64, 4, (1024, 512)),
    (8, 128, 4, (1024, 512)), (4, 256, 2, (1024, 512)),
    (3, 64, 2, (512, 512)),    # one 192-lane tile: 1024 squares were refused
    (5, 64, 4, (256, 256)),
    (25, 64, 2, (None, None))])  # GPT-2 XL's heads: one 1600-lane tile
def test_auto_blocks_fit_the_tile_that_the_heads_make(H, Dh, itemsize, blocks):
    from fedml_tpu.ops.pallas import flash_shapes_ok
    from fedml_tpu.ops.pallas.flash_attention import _auto_blocks

    assert _auto_blocks(4096, H, Dh, itemsize) == blocks
    assert flash_shapes_ok(4096, Dh, itemsize=itemsize, heads=H) == (
        None not in blocks)


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


# (T, window, block_q, block_k, fused): the default blocks of T = 512 and
# 1024 capped to half the band (128 under 256 and 384); 128 squares under
# windows of 256 and 384, whose lower-edge blocks straddle the band and whose
# diagonal blocks run apart from it; unequal blocks; 256 squares under a band
# of 512 through the split backward's dq kernel; a window of T or more, the
# plain causal call
WINDOWED = [(512, 256, None, None, True), (512, 256, 128, 128, True),
            (512, 384, 128, 128, True), (1024, 384, None, None, True),
            (1024, 256, 128, 128, True), (1024, 512, 256, 256, False),
            (512, 512, None, None, True), (512, 600, 256, 256, True)]


@pytest.mark.parametrize("T,window,block_q,block_k,fused", WINDOWED)
def test_windowed_flash_matches_the_dense_band(T, window, block_q, block_k,
                                               fused, monkeypatch):
    """The output and dq, dk, dv of a causal band ``0 <= i - j < window``
    against the dense path's band, forward, the fused backward and the split
    pair (the limit patched as ``test_flash_gradients_match_dense`` patches
    it); a window of T or more is the causal call itself, bit for bit."""
    mod = sys.modules["fedml_tpu.ops.pallas.flash_attention"]
    H, Dh = 2, 64
    if not fused:  # room for the split pair's tiles, none for the fused dq
        monkeypatch.setattr(mod, "_VMEM_LIMIT", mod._bwd_vmem(
            T, block_q, block_k, 128, H, 4, fused=False))
    jax.clear_caches()
    q, k, v = _qkv(B=1, T=T, H=H, Dh=Dh, seed=window)
    w = jnp.cos(jnp.arange(q.size).reshape(q.shape) * 0.01)

    def run(attn):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out, *vjp(w))

    got = run(lambda q, k, v: flash_attention(q, k, v, True, block_q, block_k,
                                              window=window))
    jax.clear_caches()
    want = run(lambda q, k, v: multihead_attention(
        q, k, v, causal=True, impl="dense", window=window))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    if window >= T:
        plain = run(lambda q, k, v: flash_attention(q, k, v, True, block_q,
                                                    block_k))
        for a, b in zip(got, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:  # the band is not the triangle
        assert not np.allclose(np.asarray(got[0]), np.asarray(
            multihead_attention(q, k, v, causal=True, impl="dense")),
            atol=1e-3)
    grad = jax.grad(lambda q: flash_attention(
        q, k, v, True, block_q, block_k, window=window).sum())
    assert str(jax.make_jaxpr(grad)(q)).count("pallas_call") == (
        2 if fused else 3)
    jax.clear_caches()


def test_a_window_is_a_causal_band():
    q, k, v = _qkv(B=1, T=256)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, window=128)


@pytest.mark.parametrize("window,block", [(128, None), (384, 256)])
def test_a_band_that_holds_no_two_blocks_is_refused(window, block):
    """The kernels take blocks that two of fit the band, so that no pair of
    blocks crosses both of its edges: a band of 128 holds no two of the
    smallest, and explicit 256 squares do not fit one of 384."""
    q, k, v = _qkv(B=1, T=1024)
    with pytest.raises(ValueError, match="no two blocks"):
        flash_attention(q, k, v, True, block, block, window=window)


def test_auto_dispatch_sends_a_band_too_narrow_for_the_kernels_to_dense():
    from fedml_tpu.ops.attention import auto_attention_impl

    assert auto_attention_impl(1, 2, 8192, 128) == "flash"
    assert auto_attention_impl(1, 2, 8192, 128, window=256) == "flash"
    assert auto_attention_impl(1, 2, 8192, 128, window=128) == "dense"
    assert auto_attention_impl(1, 2, 8192, 128, window=8192) == "flash"


def test_window_counter_counts_once_per_traced_call_site():
    """``fedml_flash_window_total{pass, seq_len, window}``: the band each
    call runs (``none``: the triangle, or no mask at all), where its blocks
    are resolved, once per call site per trace, as the diagonal's counter;
    a window of T or more runs, and counts, as ``none``."""
    from fedml_tpu.core.telemetry import get_registry

    def count(T):
        return {(p, w): get_registry().counter(
            "fedml_flash_window_total", seq_len=T, window=w,
            **{"pass": p}).value
            for p in ("fwd", "bwd") for w in ("none", "256")}

    def after(T, fn, *args):
        before = count(T)
        fn(*args)
        return {key: n - before[key] for key, n in count(T).items()
                if n != before[key]}

    q = jnp.zeros((1, 512, 2, 64), jnp.float32)
    sites = jax.jit(jax.grad(lambda q: (
        flash_attention(q, q, q, True, window=256)
        + flash_attention(q, q, q, True) + flash_attention(q, q, q, False)
        + flash_attention(q, q, q, True, window=512)).sum()))
    assert after(512, sites, q) == {("fwd", "256"): 1, ("bwd", "256"): 1,
                                    ("fwd", "none"): 3, ("bwd", "none"): 3}
    assert after(512, sites, q) == {}                       # compiled
    assert after(512, flash_attention, q, q, q, True) == {}  # eager


@pytest.mark.parametrize("metric,reads", [("flash_window_share.st21", 75.0),
                                          ("flash_striped_share.st21", 100.0)])
def test_flash_window_share_reads_the_st21_cell(monkeypatch, metric, reads):
    """``benchmark/layer_metrics/<metric>.json`` through its reader, at the
    SmallThinker cell's traffic: nothing before a call at ``seq_len`` is
    traced (as on the parent, which has no window counter), then, once one
    period's attention is (the global layer's calls and three windowed
    layers', forward and backward; traced, never run): 75 windowed, and
    every diagonal striped, the windowed layers' as the global one's."""
    import importlib.util
    import json
    import os

    from fedml_tpu.core import telemetry

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    reg = telemetry.MetricsRegistry(enabled=True)
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    monkeypatch.setattr(sys.modules["fedml_tpu.ops.pallas.flash_attention"],
                        "get_registry", lambda: reg)
    spec = importlib.util.spec_from_file_location(
        "bench_program_counter",
        os.path.join(bench, "readers", "program_counter.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(bench, "traffic", "t16384_b1_pretrain.json")) as f:
        ctx = {"traffic": json.load(f)}
    with open(os.path.join(bench, "configs", "smallthinker_21b_a3b.json")) as f:
        cfg = json.load(f)
    assert reader.read(ctx, **spec["args"]) is None
    q = jax.ShapeDtypeStruct((1, ctx["traffic"]["seq_len"], 2, 128),
                             jnp.bfloat16)

    def period(q):
        for windowed in cfg["sliding_window_layout"]:
            q = flash_attention(q, q, q, True, window=(
                cfg["sliding_window_size"] if windowed else None))
        return q.astype(jnp.float32).sum()

    jax.jit(jax.grad(period)).trace(q)
    assert reader.read(ctx, **spec["args"]) == reads
