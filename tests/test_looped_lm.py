"""The looped decoder (models/hybrid_lm.py with ``total_ut_steps`` > 1: one
stack of sandwich-norm layers applied several times over one set of weights,
an exit gate, ops/losses.py ``expected_exit_loss``) at a tiny size on the CPU:
against the plain float32 reference ``benchmark/reference/ouro.py`` on seeded
weights (loss, every leaf's gradient, AdamW steps through the trainer); the
reuse tied to the model (looped = unrolled with copied weights, a shared
leaf's gradient = the sum of its copies'); the exit distribution; and the
scopes' names in the compiled step. Helpers and tolerances are
``tests/test_hybrid_lm.py``'s."""

from __future__ import annotations

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hybrid_lm import close  # (puts benchmark/ on the path)

from reference import ouro as ref  # noqa: E402
from runners import ouro_step as runner  # noqa: E402

from fedml_tpu.core import telemetry  # noqa: E402
from fedml_tpu.models.hybrid_lm import (  # noqa: E402
    DecoderLayer,
    ExitGate,
    HybridLM,
    RMSNorm,
    ut_stats,
)
from fedml_tpu.ops.losses import (  # noqa: E402
    exit_distribution,
    expected_exit_loss,
    lm_token_nll,
)
from fedml_tpu.parallel.trainer import (  # noqa: E402
    DistributedLMTrainer,
    DistTrainConfig,
)

# width 64; 2 layers of 4 heads x 16 (as many KV heads) over SwiGLU 160,
# applied 4 times; an untied head over 128 ids; beta 0.1
OURO = dict(
    hidden_size=64, layer_types=["full_attention"] * 2, num_hidden_layers=2,
    intermediate_size=160, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, vocab_size=128, rms_norm_eps=1e-6, rope_theta=1000000,
    total_ut_steps=4, exit_entropy_beta=0.1, tie_word_embeddings=False,
    hidden_act="silu", use_sliding_window=False, rope_scaling=None,
    early_exit_threshold=1, init_std=0.02)
B, T, S, BETA = 2, 32, 4, 0.1


def tokens(seed):
    return np.random.default_rng(seed).integers(
        0, OURO["vocab_size"], (B, T + 1), dtype=np.int32)


@pytest.fixture(scope="module")
def seeded():
    weights = ref.init_weights(5, OURO)
    # a gate that is not all but shut or open, and norms off 1, so that every
    # leaf's gradient is told from its neighbour's
    rng = np.random.default_rng(3)
    weights = {k: (a + 0.1 * rng.standard_normal(a.shape).astype(np.float32)
                   if a.ndim <= 1 else a) for k, a in weights.items()}
    return weights, {"params": runner.to_program(weights)}


def program_loss(model, params, toks):
    (hid, gates), _ = model.apply(params, toks[:, :-1], return_passes=True,
                                  return_stats=True)
    loss, nll, exits = expected_exit_loss(
        hid, gates, HybridLM.head_kernel(params), toks[:, 1:], BETA)
    return loss, (nll, exits)


def test_looped_decoder_matches_the_plain_reference_on_seeded_weights(seeded):
    """Last-pass logits, the objective and the gradient of every leaf (the
    gate's weight and bias, all four norms of a layer, the untied head among
    them) against the reference."""
    weights, variables = seeded
    model = HybridLM(runner.decoder_config(OURO), remat=True)
    init = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert jax.tree.structure(init) == jax.tree.structure(variables)
    assert model.STEP_STATS[-2 * S:] == ut_stats(S) == (
        "ut_nll_1", "ut_nll_2", "ut_nll_3", "ut_nll_4",
        "ut_mass_1", "ut_mass_2", "ut_mass_3", "ut_mass_4")
    toks = jnp.asarray(tokens(6))
    shape = ref.shape_of(OURO)
    with jax.default_matmul_precision("highest"):
        (got, (nll, exits)), g_got = jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, p, toks), has_aux=True))(variables)
        want, g_want = jax.jit(jax.value_and_grad(ref.loss_sum),
                               static_argnums=2)(weights, toks, shape)
        close(jax.jit(model.apply)(variables, toks[:, :-1]),
              jax.jit(ref.forward, static_argnums=2)(weights, toks[:, :-1], shape))
    close(got * B * T, want)
    g_got = runner.from_program(g_got["params"], list(weights))
    for name in weights:
        close(g_got[name] * B * T, g_want[name], rtol=5e-5)
    assert float(exits.sum()) == pytest.approx(B * T, rel=1e-6)
    assert nll.shape == exits.shape == (S,) and bool((nll > 0).all())


def test_trainer_steps_the_looped_decoder_as_the_reference_does(seeded):
    """Loss of each of three steps and the parameters' change after them
    through ``trainer.step`` against the plain reference's AdamW (the cell's
    comparison, tiny), and what the steps file in the registry."""
    weights, variables = seeded
    telemetry.configure(enabled=True, reset=True)
    t = DistributedLMTrainer(
        DistTrainConfig(exit_entropy_weight=BETA), dtype=jnp.float32,
        model=runner.decoder_config(OURO))
    assert jax.tree.structure(t.params) == jax.tree.structure(variables)
    t.params = jax.device_put(jax.tree.map(jnp.copy, variables),
                              t.param_shardings)
    t.opt_state = t.init_opt_state()
    batches = [tokens(10 + i) for i in range(3)]
    losses = [t.step(b[:, :-1], b[:, 1:]) for b in batches]
    assert t._train_step._cache_size() == 1
    o = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    w = weights
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    with jax.default_matmul_precision("highest"):
        for step, (loss, b) in enumerate(zip(losses, batches), start=1):
            want, g = ref.loss_and_grad(w, jnp.asarray(b), ref.shape_of(OURO),
                                        "f32", False, False, False, 1)
            close(loss, want)
            w, m, v = ref.lm.adamw(
                jax.tree.map(jnp.copy, w), g, m, v, jnp.float32(step), o["lr"],
                o["b1"], o["b2"], o["eps"], o["weight_decay"])
    got = runner.from_program(jax.device_get(t.params)["params"], list(weights))
    # Adam's first steps move an element by about lr x sign(gradient): 1% of
    # the leaf's largest move (tests/test_hybrid_lm.py has the reason)
    for name in weights:
        close(got[name] - weights[name], w[name] - weights[name], rtol=1e-2)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["fedml_lm_ut_passes_total"] == 3 * S
    mass = [snap["counters"][f"fedml_lm_exit_mass_total{{ut={s}}}"]
            for s in range(1, S + 1)]
    assert sum(mass) == pytest.approx(3 * B * T, rel=1e-5) and min(mass) > 0
    assert all(snap["gauges"][f"fedml_lm_ut_nll{{ut={s}}}"] > 0
               for s in range(1, S + 1))
    assert not [k for k in snap["counters"] if k.startswith("fedml_moe_")]


class Unrolled(nn.Module):
    """The looped decoder written out: S x L layers, S final norms and S
    gates, each with parameters of its own, in the order the loop meets
    them."""

    cfg: object

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        hidden, gates = [], []
        for t in range(c.total_ut_steps):
            for i in range(len(c.layer_types)):
                h, _ = DecoderLayer(c, "full_attention", True,
                                    name=f"pass_{t}_layer_{i}")(h)
            h = RMSNorm(c.norm_eps, name=f"pass_{t}_final_norm")(h)
            hidden.append(h)
            gates.append(ExitGate(name=f"pass_{t}_exit_gate")(h))
        return jnp.stack(hidden), jnp.stack(gates)


def test_the_loop_is_the_unrolled_stack_given_the_same_weights_four_times(seeded):
    """Reuse tied to the model: S = 4 passes over L = 2 layers equal a plain
    stack of 8 layers (4 final norms, 4 gates) given the same weights four
    times over, output for output, and each shared leaf's gradient is the
    sum of its four copies' gradients."""
    _, variables = seeded
    cfg = runner.decoder_config(OURO)
    model = HybridLM(cfg)
    toks = jnp.asarray(tokens(8))
    p = variables["params"]
    copies = {}
    for t in range(S):
        for i in range(2):
            copies[f"pass_{t}_layer_{i}"] = p[f"layer_{i}"]
        copies[f"pass_{t}_final_norm"] = p["final_norm"]
        copies[f"pass_{t}_exit_gate"] = p["exit_gate"]

    def looped(shared):
        hid, gates = model.apply({"params": {**p, **shared}}, toks[:, :-1],
                                 return_passes=True)
        loss, *_ = expected_exit_loss(hid, gates, p["lm_head"], toks[:, 1:], BETA)
        return loss, (hid, gates)

    def unrolled(copies):
        hid, gates = Unrolled(cfg).apply({"params": copies},
                                         p["embed"]["embedding"][toks[:, :-1]])
        loss, *_ = expected_exit_loss(hid, gates, p["lm_head"], toks[:, 1:], BETA)
        return loss, (hid, gates)

    shared = {k: p[k] for k in ("layer_0", "layer_1", "final_norm", "exit_gate")}
    with jax.default_matmul_precision("highest"):
        (l_loop, out_loop), g_loop = jax.jit(jax.value_and_grad(
            looped, has_aux=True))(shared)
        (l_flat, out_flat), g_flat = jax.jit(jax.value_and_grad(
            unrolled, has_aux=True))(copies)
    close(l_loop, l_flat, rtol=1e-6)
    for a, b in zip(out_loop, out_flat):
        close(a, b, rtol=1e-6)
    for name, g in g_loop.items():
        kind = name if name.startswith("layer_") else "_" + name
        summed = jax.tree.map(
            lambda *gs: sum(gs), *(g_flat[k] for k in sorted(g_flat)
                                   if k.endswith(kind)))
        assert len([k for k in g_flat if k.endswith(kind)]) == S
        for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(summed)):
            close(got, want, rtol=2e-5)
        # and no copy's gradient alone is the shared leaf's
        first = jax.tree.leaves(g_flat[f"pass_0{kind if kind[0] == '_' else '_' + kind}"])
        assert any(float(jnp.abs(a - b).max()) > 1e-3 * float(jnp.abs(a).max())
                   for a, b in zip(jax.tree.leaves(g), first))


def test_exit_distribution_sums_to_one_and_a_shut_gate_leaves_the_last_pass():
    rng = np.random.default_rng(4)
    g = jnp.asarray(3.0 * rng.standard_normal((S, B, T)), jnp.float32)
    p = exit_distribution(g)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(g)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
                               rtol=1e-5, atol=1e-7)
    # the last gate's logit enters nothing
    np.testing.assert_array_equal(p, exit_distribution(g.at[-1].set(7.0)))
    np.testing.assert_array_equal(  # the reference's, written another way
        np.asarray(ref.exit_distribution(g)).round(5), np.asarray(p).round(5))
    # a gate forced shut: all the mass on the last pass, no entropy, and the
    # objective is the last pass's mean token loss
    hidden = jnp.asarray(rng.standard_normal((S, B, T, 16)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((16, 40)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 40, (B, T)), jnp.int32)
    loss, nll, exits = expected_exit_loss(
        hidden, jnp.full((S, B, T), -40.0), head, targets, BETA)
    last = lm_token_nll(hidden[-1] @ head, targets).mean()
    np.testing.assert_allclose(loss, last, rtol=1e-6)
    np.testing.assert_allclose(nll[-1], last, rtol=1e-6)
    np.testing.assert_allclose(exits, [0, 0, 0, B * T], atol=1e-9)
    # and one forced open exits at the first pass
    loss, nll, exits = expected_exit_loss(
        hidden, jnp.full((S, B, T), 40.0), head, targets, BETA)
    np.testing.assert_allclose(loss, nll[0], rtol=1e-6)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.grad(
        lambda g: expected_exit_loss(hidden, g, head, targets, BETA)[0])(
            jnp.full((S, B, T), 40.0)))


def test_each_scope_names_its_own_ops_forward_and_backward():
    """``ut.pass``, ``ut.head`` and ``ut.exit`` in the compiled step's
    ``op_name``s: ``scoped_time`` matches substrings, so no other op's name
    may hold one of them (``head`` alone is inside ``multihead_attention``);
    each names ops of the forward and of the backward pass; and a decoder of
    one pass has none of them."""
    def names_of(passes):
        import dataclasses

        cfg = dataclasses.replace(runner.decoder_config(OURO),
                                  total_ut_steps=passes)
        t = DistributedLMTrainer(DistTrainConfig(), dtype=jnp.float32, model=cfg)
        spec = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=t.batch_sharding)
        text = t._train_step.lower(t.params, t.opt_state, t.constants, spec,
                                   spec).compile().as_text()
        return re.findall(r'op_name="([^"]*)"', text)

    names = names_of(S)
    for scope in ("ut.pass", "ut.head", "ut.exit"):
        assert [n for n in names if scope in n and "transpose(" in n], scope
        assert [n for n in names if scope in n and "transpose(" not in n], scope
    in_passes = [n for n in names if "ut.pass" in n]
    assert [n for n in in_passes if "_local_attention" in n]
    assert not [n for n in in_passes
                if "ut.head" in n or "ut.exit" in n or "lm.loss" in n]
    assert not [n for n in names if "ut.head" in n
                and ("ut.exit" in n or "lm.loss" in n)]
    assert not [n for n in names if "ut.exit" in n and "lm.loss" in n]
    # every product lies in a pass, in a head or, the gate's, in ut.exit
    dots = [n for n in names if n.endswith("dot_general")]
    assert dots and all(sum(scope in n for scope in (
        "ut.pass", "ut.head", "ut.exit")) == 1 for n in dots), [
        n for n in dots if "ut." not in n][:3]
    assert all("ut.exit" in n for n in names if "/exit_gate/" in n)
    assert not [n for n in names_of(1) if "ut." in n]


def test_a_decoder_of_one_pass_has_no_passes_to_return_and_none_is_refused():
    import dataclasses

    cfg = runner.decoder_config(OURO)
    once = dataclasses.replace(cfg, total_ut_steps=1)
    model = HybridLM(once)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))
    assert "exit_gate" not in variables["params"]
    assert model.STEP_STATS == HybridLM(cfg).STEP_STATS[:-2 * S]
    with pytest.raises(ValueError, match="applies its stack once"):
        model.apply(variables, jnp.zeros((1, 8), jnp.int32), return_passes=True)
    with pytest.raises(ValueError, match="total_ut_steps 0"):
        dataclasses.replace(cfg, total_ut_steps=0)
