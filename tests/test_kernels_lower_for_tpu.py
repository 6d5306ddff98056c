"""Every pallas_call site lowers for the TPU — checked on the CPU.

``jit(f).trace(*shapes).lower(lowering_platforms=("tpu",))`` with
``interpret=False`` runs the Pallas -> Mosaic lowering without a chip: it
is where a BlockSpec that breaks the (8, 128) rule or a cast Mosaic lacks
is refused. (What libtpu's Mosaic compiler then makes of the kernel —
scoped VMEM above all — only ``chip_smoke.py`` on the chip can say.) It
keeps a kernel PR from spending chip minutes on an error this finds in
seconds."""

import importlib

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


def _flash_loss(q, k, v):
    return flash_attention(q, k, v, True).astype(jnp.float32).sum()


# where the whole sequence's dq fits the VMEM cap: forward + the one backward
# kernel; past it: forward, dk/dv, dq. (8, 1024, 16, 64) is the LM cell's
# attention, T = 16384 at a 128-lane tile the SmallThinker cell's length.
@pytest.mark.parametrize("shape,dtype,calls", [
    ((1, 4096, 2, 64), "bfloat16", 2), ((1, 4096, 2, 128), "bfloat16", 2),
    ((8, 1024, 16, 64), "bfloat16", 2), ((1, 8192, 2, 64), "bfloat16", 2),
    ((1, 8192, 2, 64), "float32", 2), ((1, 16384, 2, 64), "bfloat16", 2),
    ((1, 16384, 1, 128), "bfloat16", 2), ((1, 131072, 1, 128), "bfloat16", 3),
    ((1, 98304, 2, 64), "float32", 3)])
def test_flash_forward_and_backward_lower(shape, dtype, calls, monkeypatch):
    # the kernel picks interpret mode from the backend it is traced on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _sds(shape, jnp.dtype(dtype))
    assert _mosaic_calls(jax.grad(_flash_loss, (0, 1, 2)), x, x, x) == calls


def _eqns(jaxpr):
    """Every equation of a jaxpr, through nested jaxprs (``pl.when``'s
    branches, jitted launchers, a pallas_call's kernel)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _pallas_calls(fn, *shapes):
    return [eqn for eqn in _eqns(jax.make_jaxpr(fn)(*shapes).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def test_fused_backward_keeps_its_key_blocks_on_one_core(monkeypatch):
    """dq's scratch outlives the key blocks of grid axis 2: the fused kernel
    declares that axis ``arbitrary``, so a chip with two TensorCores does
    not split it; the forward and the split pair (past the VMEM cap) keep
    it ``parallel``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def semantics(T):
        x = _sds((1, T, 2, 64), jnp.bfloat16)
        return [call.params["compiler_params"]["mosaic_tpu"].dimension_semantics
                for call in _pallas_calls(jax.grad(_flash_loss, (0, 1, 2)),
                                          x, x, x)]

    free = ("parallel", "parallel", "parallel", "arbitrary")
    fused = [free, ("parallel", "parallel", "arbitrary", "arbitrary")]
    assert semantics(1024) == fused
    assert semantics(16384) == fused
    assert semantics(131072) == [free, free, free]


def test_flash_layers_share_one_lowering_of_each_kernel(monkeypatch):
    """A stack of equal layers, rematerialised as the LM step's are, lowers
    each kernel once and calls it from every layer: Mosaic lowering is
    Python-side work at every process start, cache or no cache (PR 27:
    96 lowerings were 21 s of the LM cell's set-up)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _sds((1, 1024, 2, 64), jnp.bfloat16)

    def loss(x):
        for _ in range(4):
            x = jax.checkpoint(lambda t: flash_attention(t, t, t, True))(x)
        return x.astype(jnp.float32).sum()

    # forward, the recompute's forward (its outputs differ), backward
    assert _mosaic_calls(jax.grad(loss), x) <= 3


@pytest.mark.parametrize("over_cap", [False, True])
def test_flash_backward_splits_at_explicit_blocks_over_its_cap(monkeypatch,
                                                               over_cap):
    """1024 blocks asked for by hand: the fused backward passes Mosaic's
    default scoped VMEM from T = 2048, so it asks for its count and the
    margin (which compiled for the v5e ahead of time: PERF.md section 6);
    with the cap under that count, dq takes its own kernel."""
    mod = importlib.import_module("fedml_tpu.ops.pallas.flash_attention")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = _sds((1, 2048, 2, 64), jnp.bfloat16)
    need = mod._fused_bwd_vmem(x, 1024, 1024)
    assert mod._VMEM_LIMIT < need <= mod._VMEM_CAP
    if over_cap:
        monkeypatch.setattr(mod, "_VMEM_CAP", need - 1)
    jax.clear_caches()  # the jitted launchers' caches do not see the cap

    def loss(q, k, v):
        return flash_attention(q, k, v, True, 1024, 1024).astype(
            jnp.float32).sum()

    assert _mosaic_calls(jax.grad(loss, (0, 1, 2)), x, x, x) == (
        3 if over_cap else 2)
    jax.clear_caches()


def _kernel_dots(fn, *shapes):
    """(lhs dtype, rhs dtype, preferred dtype) of every dot_general inside
    the pallas_calls of ``fn``."""
    return [(*(str(v.aval.dtype) for v in eqn.invars),
             str(eqn.params["preferred_element_type"]))
            for call in _pallas_calls(fn, *shapes)
            for eqn in _eqns(call.params["jaxpr"])
            if eqn.primitive.name == "dot_general"]


# contractions a head: 2 forward + 5 in the fused backward; with the
# split pair (the cap patched under the fused count) 2 + 4 (dk/dv) + 3
# (dq). Each causal case and each sub-tile of a diagonal block traces its
# own, so these are floors.
@pytest.mark.parametrize("T,split,dots_a_head", [
    (1024, False, 2 + 5), (16384, False, 2 + 5), (16384, True, 2 + 4 + 3)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_feeds_the_mxu_the_dtype_it_was_given(T, split, dots_a_head,
                                                    dtype, monkeypatch):
    """No float32 upcast of an MXU operand at bf16 inputs, in any of the
    kernels: every contraction takes q/k/v/dO, p and ds in the input dtype
    and accumulates float32."""
    mod = importlib.import_module("fedml_tpu.ops.pallas.flash_attention")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if split:
        monkeypatch.setattr(mod, "_VMEM_CAP", mod._VMEM_LIMIT)
    jax.clear_caches()  # the jitted launchers' caches do not see the cap
    x = _sds((1, T, 2, 64), jnp.dtype(dtype))
    found = _kernel_dots(jax.grad(_flash_loss, (0, 1, 2)), x, x, x)
    jax.clear_caches()
    assert len(found) >= 2 * dots_a_head
    assert set(found) == {(dtype, dtype, "float32")}


# (B, T, H, Dh, window) at each cell's flash shape, bf16: GPT-2, LFM2 (32
# query heads of 64), Nemotron (32 of 128), Ouro (16 of 128); and the
# SmallThinker cell's global and windowed layers (28 of 128 at T = 16384),
# whose fused backward alone passes Mosaic's default
CELL_FLASH_SHAPES = {
    "gpt2": (8, 1024, 16, 64, None), "lfm2": (2, 8192, 32, 64, None),
    "nemo3": (1, 8192, 32, 128, None), "ouro": (1, 4096, 16, 128, None),
    "st21_global": (1, 16384, 28, 128, None),
    "st21_window": (1, 16384, 28, 128, 4096)}


@pytest.mark.parametrize("cell", list(CELL_FLASH_SHAPES))
def test_scoped_vmem_is_raised_only_past_mosaics_default(cell, monkeypatch):
    """No kernel's compiler params ask for scoped VMEM of their own
    (``vmem_limit_bytes`` None) but the fused backward's where its count
    passes Mosaic's default: there the count and the margin. So the other
    cells' kernels run under the default."""
    mod = importlib.import_module("fedml_tpu.ops.pallas.flash_attention")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    *shape, window = CELL_FLASH_SHAPES[cell]
    x = _sds(tuple(shape), jnp.bfloat16)
    calls = _pallas_calls(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True, window=window).astype(jnp.float32).sum(), (0, 1, 2)),
        x, x, x)
    limits = [call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
              for call in calls]
    need = mod._fused_bwd_vmem(x, 512, 512)
    raised = [need + mod._VMEM_MARGIN] if need > mod._VMEM_LIMIT else []
    assert len(calls) == 2
    assert [n for n in limits if n is not None] == raised
    assert bool(raised) == cell.startswith("st21")


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


def _expert_layer(N=1024, D=2048, F=1536, held=8):
    """``dropless_moe`` at LFM2-24B-A2B's widths (8 of 64 experts held,
    top-4) over N tokens, and the shapes of its arguments."""
    from fedml_tpu.ops.moe import dropless_moe

    def layer(x, gate, bias, w1, w3, w2):
        return dropless_moe(x, gate, bias, w1, w3, w2, top_k=4,
                            experts_held=(0, held))[0]

    return layer, (
        _sds((N, D), jnp.bfloat16), _sds((D, 64), jnp.float32),
        _sds((64,), jnp.float32), _sds((held, D, F), jnp.float32),
        _sds((held, D, F), jnp.float32), _sds((held, F, D), jnp.float32))


def _mosaic_callers(fn, *shapes) -> dict:
    """``tpu_custom_call``s of the lowered program by the jitted launcher
    that holds each (a launcher lowered again under another jaxpr, as
    remat's recompute makes of it, is ``name_<n>``: counted with its name)."""
    import collections
    import re

    text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    held_by, calls = None, collections.Counter()
    for line in text.splitlines():
        func = re.match(r"\s*func\.func (?:private |public )?@(\w+)\(", line)
        if func:
            held_by = re.sub(r"_\d+$", "", func.group(1))
        calls[held_by] += line.count("tpu_custom_call")
    return {name: n for name, n in calls.items() if n}


def test_dropless_experts_and_grouped_flash_lower(monkeypatch):
    """The routed-expert layer at LFM2-24B-A2B's widths (8 of 64 experts
    held, top-4): the grouped products forward and their transposes
    backward, the row moves both ways; the flash kernels behind 32 query
    heads on 8 KV heads."""
    from fedml_tpu.ops.attention import multihead_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer, shapes = _expert_layer()

    def loss(*args):
        return layer(*args).astype(jnp.float32).sum()

    # a jitted launcher is lowered once a shape, and each of the two row
    # buffers has its own. Grouped products: the two into the hidden width
    # share one kernel (2 a buffer); backward adds each one's transpose by
    # rows (gmm) and by weights (tgmm) (6). Row moves (ops/pallas/
    # row_move.py, PR 33): the tokens' rows out and the weighted rows back
    # (2 a buffer). Backward they are the same two kernels (the rows back
    # with ones for weights; the rows out of the cotangent, scaled and
    # multiplied outside), and only the rows out is lowered again, under
    # each buffer's checkpoint (3 a buffer): PR 32 lowered 5 more, a layout
    # kernel a source and a second rows kernel, and its set-up was refused
    assert _mosaic_calls(loss, *shapes) == 2 * (2 + 2)
    assert _mosaic_calls(jax.grad(loss, (0, 1, 3, 4, 5)), *shapes) == 2 * (6 + 3)

    def grouped(q, k, v):
        return multihead_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q, kv = _sds((2, 8192, 32, 64), jnp.bfloat16), _sds((2, 8192, 8, 64), jnp.bfloat16)
    assert _mosaic_calls(jax.grad(grouped, (0, 1, 2)), q, kv, kv) == 2


def test_expert_layers_share_one_lowering_of_each_row_move(monkeypatch):
    """Four equal expert layers, rematerialised as the LM step's are, lower
    what one does: ``dropless_moe`` is jitted, so the layers share its
    jaxprs and every launcher under them. Of each row-move kernel and
    buffer size there is the forward's lowering, the recompute's under the
    layer's remat and (the rows out) the one under the buffer's checkpoint:
    2 x (3 + 2), whatever the number of layers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer, shapes = _expert_layer()

    def stack(layers):
        def loss(x, *weights):
            for _ in range(layers):
                x = jax.checkpoint(layer)(x, *weights)
            return x.astype(jnp.float32).sum()
        return _mosaic_callers(jax.grad(loss, (0, 1, 3, 4, 5)), *shapes)

    two, four = stack(2), stack(4)
    assert four == two
    assert four["_rows_from_tokens"] == 2 * 3
    assert four["_tokens_from_rows"] == 2 * 2


LFM2_CELL = dict(      # benchmark/configs/lfm2_24b_a2b.json, as its runner reads it
    vocab_size=8192, hidden_size=2048, num_dense_layers=1,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    intermediate_size=11776, moe_intermediate_size=1536, num_experts=64,
    experts_held=(0, 8), num_experts_per_tok=4, num_attention_heads=32,
    num_key_value_heads=8, head_dim=64)


def test_the_lfm2_step_lowers_within_its_budget_of_mosaic_calls(monkeypatch):
    """Every Mosaic call of a step is lowered in Python at every process
    start, cache or no cache, inside the ``setup_s`` the benchmark judges:
    PR 32's row moves took the LFM2 step from 19 to 36 and were refused for
    3.5 s of set-up. The budget is 29: the flash kernels 3, the grouped
    products 16, the row moves 10. And the trainer's jitted ``model.init``
    over 8 tokens traces no row-move kernel at all (nor lowers any: nothing
    it returns depends on the forward pass)."""
    from fedml_tpu.core.telemetry import get_registry
    from fedml_tpu.models.hybrid_lm import DecoderConfig, HybridLM
    from fedml_tpu.ops.losses import lm_cross_entropy
    from fedml_tpu.ops.pallas import row_move

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    traced = []
    for name in ("_rows_kernel", "_tokens_kernel"):
        monkeypatch.setattr(row_move, name, lambda *a, _f=getattr(
            row_move, name), **kw: traced.append(_f) or _f(*a, **kw))
    jax.clear_caches()    # kernels traced by an earlier test would not be again
    model = HybridLM(DecoderConfig(**LFM2_CELL), dtype=jnp.bfloat16,
                     remat=True)
    key, few = jax.random.PRNGKey(0), _sds((1, 8), jnp.int32)
    counted = lambda: sum(  # noqa: E731
        v for k, v in get_registry().snapshot()["counters"].items()
        if k.startswith("fedml_moe_row_move_total"))
    before = counted()
    assert _mosaic_calls(model.init, key, few) == 0
    assert not traced and counted() == before
    variables = jax.eval_shape(model.init, key, few)

    def loss(params, constants, tokens):
        logits, _ = model.apply({**constants, "params": params}, tokens,
                                return_stats=True)
        return lm_cross_entropy(logits, tokens)

    params = variables.pop("params")
    calls = _mosaic_callers(jax.value_and_grad(loss), params, variables,
                            _sds((2, 8192), jnp.int32))
    moves = calls["_rows_from_tokens"] + calls["_tokens_from_rows"]
    assert moves <= 10 and sum(calls.values()) <= 29, calls
    assert traced and counted() > before
