"""The window and global decoder (models/hybrid_lm.py with SmallThinker's
keys) and what it brought: a causal band in the attention op, a softmax
router with no bias, ReGLU experts and a router placed before attention.
Each against a plain float32 formula, and the whole decoder against the
benchmark's plain reference (``benchmark/reference/smallthinker.py``, which
imports nothing of the program), on seeded weights at a tiny size; the share
test that ties a chip's share of the expert layer to the uncut layer.

Tolerances: float32 against float32 at "highest", so what differs is the
order of sums (the grouped product's tiles, the attention's blocks, the
flash kernels' online softmax): measured 1e-7 to 6e-6 relative on these
sizes; 2e-5 leaves a bfloat16 rounding (4e-3) two orders outside."""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import smallthinker as ref  # noqa: E402
from runners import st21_step as runner  # noqa: E402

from fedml_tpu.models.hybrid_lm import DecoderLayer, HybridLM  # noqa: E402
from fedml_tpu.ops import moe  # noqa: E402
from fedml_tpu.ops.attention import multihead_attention  # noqa: E402
from fedml_tpu.parallel.trainer import (  # noqa: E402
    DistributedLMTrainer,
    DistTrainConfig,
)

RTOL = 2e-5
# width 64, one period (global, then three windowed layers of 8 keys), 4 of
# 16 experts held, top-3, ReGLU 48, GQA 4/2 x 16, an untied head over 128 ids
TINY = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=48,
    router_width=16, moe_num_primary_experts=4, experts_held_offset=4,
    moe_num_active_primary_experts=3, vocab_size=128, rms_norm_eps=1e-6,
    rope_theta=1500000, sliding_window_size=8,
    sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    mlp_hidden_act="relu", early_router=True, tie_word_embeddings=False,
    rope_scaling=None, init_std=0.02, embed_init_std=1.0)
B, T = 2, 32


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * float(np.abs(want).max()))


def tokens(seed, b=B, t=T):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (b, t + 1), dtype=np.int32)


@pytest.fixture(scope="module")
def seeded():
    weights = ref.init_weights(5, TINY)
    # norms off 1, so that every leaf's gradient is told from its neighbour's
    rng = np.random.default_rng(3)
    weights = {k: (a + 0.1 * rng.standard_normal(a.shape).astype(np.float32)
                   if a.ndim <= 1 else a) for k, a in weights.items()}
    return weights, {"params": runner.to_program(weights)}


def test_the_layouts_give_each_layer_its_attention():
    cfg = runner.decoder_config(TINY)
    assert [cfg.attention_of(i) for i in range(4)] == [
        (False, None), (True, 8), (True, 8), (True, 8)]
    # without the keys every attention layer is rotary over the triangle
    plain = dataclasses.replace(cfg, sliding_window_layout=None,
                                rope_layout=None, sliding_window_size=None)
    assert {plain.attention_of(i) for i in range(4)} == {(True, None)}
    with pytest.raises(ValueError, match="sliding_window_layout"):
        dataclasses.replace(cfg, sliding_window_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="gives no window"):
        dataclasses.replace(cfg, sliding_window_size=None)
    with pytest.raises(ValueError, match="rope_layout"):
        dataclasses.replace(cfg, rope_layout=(0, 2, 1, 1))


@pytest.mark.parametrize("window", [1, 5, 32, 40])
def test_the_dense_band_is_the_plain_formula(window):
    """The dense path's band: query i over keys j with 0 <= i - j < window,
    grouped KV heads; a window of T or more is the plain causal call."""
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.normal(size=(1, T, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, T, 2, 16)), jnp.float32)
            for _ in range(2))
    got = multihead_attention(q, k, v, causal=True, impl="dense", window=window)
    kk, vv = np.repeat(np.asarray(k), 2, 2), np.repeat(np.asarray(v), 2, 2)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kk) / 4.0
    back = np.arange(T)[:, None] - np.arange(T)[None, :]
    s = np.where((back >= 0) & (back < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), vv)
    close(got, want, rtol=1e-5)
    if window >= T:
        close(got, multihead_attention(q, k, v, causal=True, impl="dense"))
    with pytest.raises(ValueError, match="causal"):
        multihead_attention(q, k, v, causal=False, window=window)


def test_the_softmax_router_is_the_plain_formula():
    """p = softmax(x W) over all the experts in float32, the top k of p,
    their weights p over their sum; no bias."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    chosen, w = moe.route_top_k(x, gate, None, 3, router="softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(gate, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, -1)[:, :3]
    np.testing.assert_array_equal(np.asarray(chosen), want)
    top = np.take_along_axis(p, want, -1)
    close(w, top / top.sum(-1, keepdims=True), rtol=1e-5)
    assert chosen.dtype == jnp.int32 and w.dtype == jnp.float32


@pytest.mark.parametrize("early", [False, True])
def test_reglu_experts_under_the_softmax_router_are_the_plain_formula(early):
    """The held experts' part, ``W2 (relu(W1 u) * W3 u)`` weighted by the
    softmax router's normalised top-k, against a dense loop over the held
    experts; with a router input of its own, routed by that input."""
    rng = np.random.default_rng(2)
    N, D, F, E, held, off, k = 256, 32, 24, 16, 4, 8, 3
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    rx = jnp.asarray(rng.normal(size=(N, D)), jnp.float32) if early else x
    gate = jnp.asarray(0.3 * rng.normal(size=(D, E)), jnp.float32)
    w1, w3 = (jnp.asarray(0.2 * rng.normal(size=(held, D, F)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.2 * rng.normal(size=(held, F, D)), jnp.float32)
    kw = {"rx": rx} if early else {}
    with jax.default_matmul_precision("highest"):
        got, stats = moe.dropless_moe(
            x, gate, None, w1, w3, w2, top_k=k, experts_held=(off, held),
            router="softmax", form="reglu", **kw)
        chosen, wt = moe.route_top_k(rx, gate, None, k, router="softmax")
    onehot = np.asarray(jax.nn.one_hot(chosen, E)).transpose(0, 2, 1)  # N, E, k
    weight = (onehot * np.asarray(wt)[:, None, :]).sum(-1)             # N, E
    want = np.zeros((N, D), np.float32)
    for e in range(held):
        mid = np.maximum(np.asarray(x) @ np.asarray(w1[e]), 0) * (
            np.asarray(x) @ np.asarray(w3[e]))
        want += weight[:, off + e, None] * (mid @ np.asarray(w2[e]))
    close(got, want, rtol=1e-5)
    assert int(stats[0]) == int(onehot[:, off:off + held].sum())


@pytest.mark.parametrize("form,second", [("relu2", True), ("reglu", False)])
def test_an_expert_form_and_its_second_product_agree(form, second):
    """``dropless_moe``'s one ``form`` says whether an expert has a second
    product: ``w3`` given to ``relu2``, or left out of a gated form, is
    refused."""
    x, gate = jnp.ones((16, 8)), jnp.ones((8, 4))
    w = jnp.ones((2, 8, 8))
    with pytest.raises(ValueError, match=form):
        moe.dropless_moe(x, gate, None, w, w if second else None, w, top_k=2,
                         experts_held=(0, 2), router="softmax", form=form)


def test_decoder_matches_the_plain_reference_on_seeded_weights(seeded):
    """The loss and the gradient of every leaf (router, held experts, both
    norms of a layer, the untied head among them) against the reference."""
    weights, variables = seeded
    model = HybridLM(runner.decoder_config(TINY), remat=True)
    init = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert jax.tree.structure(init) == jax.tree.structure(variables)
    toks = jnp.asarray(tokens(6))
    shape = ref.shape_of(TINY)

    def program_loss(p):
        logits = model.apply(p, toks[:, :-1])
        logz = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logz, toks[:, 1:, None], -1).sum()

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(program_loss))(variables)
        want, g_want = jax.jit(jax.value_and_grad(ref.loss_sum),
                               static_argnums=2)(weights, toks, shape)
    close(got, want)
    g_got = runner.from_program(g_got["params"], list(weights))
    for name in weights:
        close(g_got[name], g_want[name], rtol=5e-5)


def test_the_decoder_through_the_windowed_flash_kernels_matches_the_reference():
    """The cell's path: the attention layers through the flash kernels
    (interpreted here), three of them over a band of half the sequence,
    against the reference's masked blocks; loss and every gradient."""
    cfg = dict(TINY, num_attention_heads=2, num_key_value_heads=1, head_dim=64,
               sliding_window_size=256)
    weights = ref.init_weights(7, cfg)
    variables = {"params": runner.to_program(weights)}
    model = HybridLM(runner.decoder_config(cfg), remat=True, attn_impl="flash")
    toks = jnp.asarray(tokens(12, b=1, t=512))

    def program_loss(p):
        logz = jax.nn.log_softmax(model.apply(p, toks[:, :-1]), -1)
        return -jnp.take_along_axis(logz, toks[:, 1:, None], -1).sum()

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(program_loss))(variables)
        want, g_want = jax.jit(jax.value_and_grad(ref.loss_sum),
                               static_argnums=2)(weights, toks,
                                                 ref.shape_of(cfg))
    close(got, want)
    g_got = runner.from_program(g_got["params"], list(weights))
    for name in weights:
        close(g_got[name], g_want[name], rtol=5e-5)


def test_the_window_and_the_router_are_what_the_reference_tells_apart(seeded):
    """The reference's faults are the program's own departures: a decoder
    with no window has the ``no_window`` reference's loss and gradients, one
    whose router reads the experts' input the ``late_router`` one's; and
    neither is the sound reference's (by the gradients: on seeded weights
    the loss hardly moves)."""
    weights, variables = seeded
    toks = jnp.asarray(tokens(8))
    shape = ref.shape_of(TINY)
    cfg = runner.decoder_config(TINY)
    sound = jax.jit(jax.grad(ref.loss_sum), static_argnums=2)(
        weights, toks, shape)
    for changed, fault in (
            (dataclasses.replace(cfg, sliding_window_layout=None,
                                 sliding_window_size=None), "no_window"),
            (dataclasses.replace(cfg, early_router=False), "late_router")):
        model = HybridLM(changed)

        def program_loss(p):
            logz = jax.nn.log_softmax(model.apply(p, toks[:, :-1]), -1)
            return -jnp.take_along_axis(logz, toks[:, 1:, None], -1).sum()

        with jax.default_matmul_precision("highest"):
            got = runner.from_program(
                jax.jit(jax.grad(program_loss))(variables)["params"],
                list(weights))
            faulty = jax.jit(jax.grad(functools.partial(
                ref.loss_sum, **{fault: True})), static_argnums=2)(
                    weights, toks, shape)
        gaps = []
        for name in weights:
            close(got[name], faulty[name], rtol=5e-5)
            scale = float(jnp.abs(sound[name]).max())
            gaps.append(float(jnp.abs(faulty[name] - sound[name]).max())
                        / scale)
        assert max(gaps) > 1e-2, (fault, max(gaps))


def test_the_early_router_reads_the_attentions_input(seeded, monkeypatch):
    """Perturbing the attention's output projection moves the layer's output
    and leaves the choice of experts as it was; a router placed after
    attention, the control, chooses anew."""
    weights, variables = seeded
    choices = []

    def seen(x, gate, bias, top_k, router="sigmoid"):
        chosen, w = route(x, gate, bias, top_k, router)
        jax.debug.callback(lambda c: choices.append(np.asarray(c)), chosen)
        return chosen, w

    route = moe.route_top_k
    monkeypatch.setattr(moe, "route_top_k", seen)
    jax.clear_caches()  # ``dropless_moe`` is jitted: trace it anew
    toks = jnp.asarray(tokens(9)[:, :-1])
    p = variables["params"]["layer_1"]
    moved = dict(p, attn=dict(p["attn"], o_proj={
        "kernel": p["attn"]["o_proj"]["kernel"] * 3.0}))
    h = variables["params"]["embed"]["embedding"][toks]
    for early, same in ((True, True), (False, False)):
        cfg = dataclasses.replace(runner.decoder_config(TINY), early_router=early)
        layer = DecoderLayer(cfg, "full_attention", False, rotary=True, window=8)
        choices.clear()
        outs = [jax.block_until_ready(layer.apply({"params": q}, h))[0]
                for q in (p, moved)]
        assert len(choices) == 2
        assert not np.allclose(outs[0], outs[1])
        assert np.array_equal(choices[0], choices[1]) is same
    jax.clear_caches()


@pytest.mark.parametrize("shares", [8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """A chip's share of the layer tied to the model: at a tiny size, the
    held parts of all ``shares`` shares (router 16 wide, top-3, 2 experts a
    share), with what every chip computes alike (attention, the residual)
    counted once, add up to the uncut reference's layer output."""
    width, held = 16, 16 // shares
    cfg_all = dict(TINY, router_width=width, moe_num_primary_experts=width,
                   experts_held_offset=0)
    w_all = ref.init_weights(11, cfg_all)
    p_all = {n[3:]: a for n, a in w_all.items() if n.startswith("L1.")}
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (B, T, 64), jnp.float32)
    shape_all = ref.shape_of(cfg_all)
    with jax.default_matmul_precision("highest"):
        uncut = ref._layer(h, p_all, shape_all, True, True, "f32", False, False)
        alike = ref._layer(h, p_all, ref.shape_of(dict(
            cfg_all, moe_num_primary_experts=0)), True, True, "f32", False,
            False)
        total = -(shares - 1) * alike
        for s in range(shares):
            cfg = dict(cfg_all, moe_num_primary_experts=held,
                       experts_held_offset=s * held)
            p = dict(p_all, **{leaf: p_all[leaf][s * held:(s + 1) * held]
                               for leaf in ref.EXPERT_LEAVES})
            tree = runner.to_program({f"L0.{k}": v for k, v in p.items()})
            layer = DecoderLayer(runner.decoder_config(dict(
                cfg, num_hidden_layers=1, sliding_window_layout=[1],
                rope_layout=[1])), "full_attention", False, rotary=True,
                window=TINY["sliding_window_size"])
            out, _ = jax.jit(layer.apply)({"params": tree["layer_0"]}, h)
            total = total + out
    close(total, uncut, rtol=5e-5)


def test_trainer_steps_the_decoder_as_the_reference_does(seeded):
    """Loss of each of three steps and the parameters' change after them
    through ``trainer.step`` against the plain reference's AdamW with its
    warm-up (the cell's comparison, tiny); the flash path's window and the
    softmax router leave no constants behind."""
    weights, variables = seeded
    t = DistributedLMTrainer(DistTrainConfig(warmup_steps=4),
                             dtype=jnp.float32,
                             model=runner.decoder_config(TINY))
    assert t.constants == {}
    t.params = jax.device_put(jax.tree.map(jnp.copy, variables),
                              t.param_shardings)
    t.opt_state = t.init_opt_state()
    batches = [tokens(10 + i) for i in range(3)]
    losses = [t.step(b[:, :-1], b[:, 1:]) for b in batches]
    o = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    w = weights
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    shape = ref.shape_of(TINY)
    with jax.default_matmul_precision("highest"):
        for step, (loss, b) in enumerate(zip(losses, batches), start=1):
            want, g = ref.loss_and_grad(w, jnp.asarray(b), shape, "f32",
                                        False, False)
            close(loss, want)
            w, m, v = ref.lm.adamw(
                jax.tree.map(jnp.copy, w), g, m, v, jnp.float32(step),
                o["lr"] * step / 4, o["b1"], o["b2"], o["eps"],
                o["weight_decay"])
    got = runner.from_program(jax.device_get(t.params)["params"], list(weights))
    # Adam's first steps move an element by about lr x sign(gradient): 1% of
    # the leaf's largest move (tests/test_hybrid_lm.py has the reason)
    for name in weights:
        close(got[name] - weights[name], w[name] - weights[name], rtol=1e-2)
