"""The ``layer_types`` decoder (models/hybrid_lm.py) and the operators it
brought (gated short convolution, rotary + QK-norm + grouped KV heads,
dropless routed experts), each against a plain float32 reference on seeded
weights at a tiny size; the share test that ties a chip's share of an expert
layer to the whole layer; and the trainer that now takes a model. The
one-mixer blocks are in ``tests/test_hybrid_mixers.py`` (another worker's
file: this one is the suite's longest).

The plain reference is the benchmark's (``benchmark/reference/lfm2.py``,
which imports nothing of the program), found as its runner finds it; the
runner's ``to_program`` puts its flat weights into the program's tree.
Tolerances: float32 against float32 at "highest", so what differs is the
order of sums (the grouped product's tiles, the attention's blocks):
measured 1e-7 to 5e-6 relative on these sizes; 2e-5 leaves a bf16 rounding
(4e-3) two orders outside."""

from __future__ import annotations

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

from reference import lfm2 as ref  # noqa: E402
from runners import lfm2_step as runner  # noqa: E402

from fedml_tpu.core import telemetry  # noqa: E402
from fedml_tpu.models.hybrid_lm import (  # noqa: E402
    GroupedQueryAttention,
    HybridLM,
    layer_types_of_pattern,
)
from fedml_tpu.ops import moe  # noqa: E402
from fedml_tpu.ops.attention import multihead_attention  # noqa: E402
from fedml_tpu.ops.rotary import apply_rotary, rms_norm  # noqa: E402
from fedml_tpu.ops.short_conv import gated_short_conv  # noqa: E402
from fedml_tpu.parallel.trainer import (  # noqa: E402
    DistributedLMTrainer,
    DistTrainConfig,
)

RTOL = 2e-5
# width 64, 2 dense + 4 expert layers, 16 experts of which 4 are held
TINY = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    router_width=16, num_experts=4, experts_held_offset=4,
    num_experts_per_tok=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=128, num_dense_layers=2, conv_L_cache=3,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    norm_eps=1e-5, rope_parameters={"rope_theta": 1000000},
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    conv_bias=False, init_std=0.02)
WARMUP = 4  # steps 1, 2, 3 run at 1/4, 2/4, 3/4 of the rate
B, T = 4, 32


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, (np.abs(a - b).max(), scale)


def tokens(seed, batch=B, seq=T):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (batch, seq + 1), dtype=np.int32)


# --- each operator alone ----------------------------------------------------

def test_gated_short_conv_matches_a_plain_loop():
    rng = np.random.default_rng(0)
    bcx = rng.standard_normal((2, 9, 3 * 8)).astype(np.float32)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    b, c, x = np.split(bcx, 3, -1)
    z = b * x
    want = np.zeros_like(z)
    for t in range(9):
        for j in range(3):
            if t - j >= 0:  # causal: nothing before the first position
                want[:, t] += taps[:, j] * z[:, t - j]
    close(gated_short_conv(jnp.asarray(bcx), jnp.asarray(taps)), c * want)
    # a later position never reaches an earlier output
    bumped = bcx.copy()
    bumped[:, 5:] += 1.0
    got = gated_short_conv(jnp.asarray(bumped), jnp.asarray(taps))
    close(got[:, :5], (c * want)[:, :5])


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_grouped_rotary_normed_attention_matches_dense_float32(impl):
    """8 query heads on 2 KV heads, q and k RMS-normalised per head and
    rotated, through the program's ops; against the reference's blocked
    float32 attention. The flash path runs its kernels (interpreted)."""
    seq = 256 if impl == "flash" else 48
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, seq, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, seq, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, 2, 64)), jnp.float32)
    gq = jnp.asarray(1 + 0.1 * rng.standard_normal(64), jnp.float32)
    gk = jnp.asarray(1 + 0.1 * rng.standard_normal(64), jnp.float32)

    def program(q, k, v):
        qn = apply_rotary(rms_norm(q, gq, 1e-5), 1e6)
        kn = apply_rotary(rms_norm(k, gk, 1e-5), 1e6)
        return multihead_attention(qn, kn, v, causal=True, impl=impl)

    def plain(q, k, v):
        qn = ref._rotary(ref._rms(q, gq, 1e-5), 1e6)
        kn = ref._rotary(ref._rms(k, gk, 1e-5), 1e6)
        return ref._attention(qn, kn, v, "f32").reshape(q.shape)

    with jax.default_matmul_precision("highest"):
        close(program(q, k, v), plain(q, k, v))
        want = jax.jit(jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), (0, 1, 2)))(
            q, k, v)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(program(*a) ** 2), (0, 1, 2)))(
            q, k, v)
    for g, w in zip(got, want):  # dk, dv: summed over each KV head's group
        close(g, w)
    with pytest.raises(ValueError, match="divide the query heads"):
        multihead_attention(q, k[:, :, :1].repeat(3, 2), v, impl="dense")


@pytest.mark.parametrize("rotary,qk_norm", [
    (True, True), (False, False), (True, False), (False, True)],
    ids=["lfm2s_layer", "nemotrons_block", "ouros_layer", "norm_alone"])
def test_attention_states_rotary_and_qk_norm_apart(rotary, qk_norm):
    """``GroupedQueryAttention``'s two plain facts, each alone and both:
    the leaves a layer holds (QK-norm's two scales or none) and its output
    against the reference's pieces put together the same way."""
    rng = np.random.default_rng(12)
    u = jnp.asarray(rng.standard_normal((2, 24, 32)), jnp.float32)
    attn = GroupedQueryAttention(32, 4, 2, 16, 1e6, 1e-5, rotary=rotary,
                                 qk_norm=qk_norm)
    variables = attn.init(jax.random.PRNGKey(1), u)
    p = variables["params"]
    assert ("q_norm" in p, "k_norm" in p) == (qk_norm, qk_norm)
    if qk_norm:  # scales off 1, so that the norm's weight shows
        p = dict(p, q_norm={"scale": 1 + 0.1 * jnp.arange(16.0)},
                 k_norm={"scale": 1 - 0.02 * jnp.arange(16.0)})
    proj = lambda name, heads: (u @ p[name]["kernel"]).reshape(2, 24, heads, 16)  # noqa: E731
    q, k, v = proj("q_proj", 4), proj("k_proj", 2), proj("v_proj", 2)
    if qk_norm:
        q = ref._rms(q, p["q_norm"]["scale"], 1e-5)
        k = ref._rms(k, p["k_norm"]["scale"], 1e-5)
    if rotary:
        q, k = ref._rotary(q, 1e6), ref._rotary(k, 1e6)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(q, k, v, "f32") @ p["o_proj"]["kernel"]
        close(attn.apply({"params": p}, u), want)


@pytest.fixture(scope="module")
def layer():
    """One expert layer's seeded weights, all 16 experts: (x, gate, bias,
    w1, w3, w2) and the reference's shape tuple with all of them held."""
    rng = np.random.default_rng(2)
    n = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    cfg = dict(TINY, num_experts=16, experts_held_offset=0)
    return (n(B * T, 64), n(64, 16), n(16) * 0.1, n(16, 64, 48), n(16, 64, 48),
            n(16, 48, 64), ref.shape_of(cfg))


def _plain_experts(x, gate, bias, w1, w3, w2, shape, held=(0, 16)):
    offset, count = held
    shape = shape[:7] + (count, offset) + shape[9:]
    p = {"gate": gate, "ew1": w1[offset:offset + count],
         "ew3": w3[offset:offset + count], "ew2": w2[offset:offset + count]}
    return ref._experts(x, p, bias, shape, "f32", False)[:2]


def _held_part(x, gate, bias, w1, w3, w2, held):
    offset, count = held
    return moe.dropless_moe(
        x, gate, bias, w1[offset:offset + count], w3[offset:offset + count],
        w2[offset:offset + count], top_k=4, experts_held=held)[:2]


def test_routed_experts_match_the_plain_reference(layer):
    *args, shape = layer
    with jax.default_matmul_precision("highest"):
        want, sel = _plain_experts(*args, shape)
        got, stats = _held_part(*args, (0, 16))
        close(got, want)
        chosen, *_ = moe.route_top_k(args[0], args[1], args[2], 4)
        assert (np.sort(chosen, -1) == np.sort(sel, -1)).all()
        # every assignment is held when every expert is, and none is dropped
        assert stats.tolist()[:2] == [B * T * 4, B * T * 4] and stats[3] == 0
        f = lambda fn, held: jax.jit(jax.grad(  # noqa: E731
            lambda *a: jnp.sum(fn(*a, held)[0] ** 2), (0, 1, 3, 4, 5)))(*args)
        for g, w in zip(f(_held_part, (0, 16)),
                        f(lambda *a: _plain_experts(*a[:-1], shape, a[-1]),
                          (0, 16))):
            close(g, w)
        # the selection bias steers the choice and takes no gradient
        g_bias = jax.grad(lambda b: jnp.sum(_held_part(
            args[0], args[1], b, *args[3:], (0, 16))[0]))(args[2])
        assert not np.asarray(g_bias).any()


def test_the_shares_add_up_to_the_uncut_layer(layer):
    """The share test: four chips holding experts 0-3, 4-7, 8-11, 12-15 route
    over all 16 alike; their parts sum to the whole layer's result, their
    held assignments to all of them, and each matches the reference's part."""
    *args, shape = layer
    with jax.default_matmul_precision("highest"):
        whole, _ = _plain_experts(*args, shape)
        parts = [_held_part(*args, (o, 4)) for o in (0, 4, 8, 12)]
        close(sum(p for p, _ in parts), whole)
        assert sum(int(s[0]) for _, s in parts) == B * T * 4
        assert all(int(s[1]) == B * T * 4 and int(s[3]) == 0 for _, s in parts)
        for (part, _), offset in zip(parts, (0, 4, 8, 12)):
            close(part, _plain_experts(*args, shape, (offset, 4))[0])


@pytest.mark.parametrize("favoured,held_rows", [
    ([5], B * T), ([4, 5, 6, 7], B * T * 4)],
    ids=["every_token_to_one_held_expert", "every_assignment_lands_here"])
def test_no_assignment_is_dropped_at_total_imbalance(layer, favoured, held_rows):
    x, gate, bias, w1, w3, w2, shape = layer
    planted = bias.at[jnp.asarray(favoured)].set(10.0)
    with jax.default_matmul_precision("highest"):
        got, stats = _held_part(x, gate, planted, w1, w3, w2, (4, 4))
        want, sel = _plain_experts(x, gate, planted, w1, w3, w2, shape, (4, 4))
    assert all((np.asarray(sel) == e).any(-1).all() for e in favoured)
    held, total, load_max, dropped = stats.tolist()
    assert (held >= held_rows and total == B * T * 4 and dropped == 0
            and load_max == B * T)  # the favoured expert took every token
    close(got, want)


def test_one_compiled_program_whatever_the_seed_or_the_routing(layer):
    x, gate, bias, w1, w3, w2, _ = layer
    step = jax.jit(lambda x, gate, bias: moe.dropless_moe(
        x, gate, bias, w1[4:8], w3[4:8], w2[4:8], top_k=4, experts_held=(4, 4)))
    loads = set()
    for seed in range(3):
        rng = np.random.default_rng(seed)
        _, stats = step(x + seed, gate * (1 + seed),
                        jnp.asarray(rng.standard_normal(16), jnp.float32))
        loads.add(tuple(stats.tolist()))
    assert len(loads) == 3 and step._cache_size() == 1


@pytest.mark.parametrize("tokens,width,impl", [
    (256, 2048, "pallas"), (256, 2688, "pallas"), (256, 2000, "xla"),
    (32, 2048, None)],
    ids=["a_slab", "a_padded_slab", "a_lane_to_spare", "too_few_rows"])
def test_the_row_moves_are_counted_by_what_the_shapes_allow(tokens, width, impl):
    """``fedml_moe_row_move_total{impl, use}`` counts, at trace time, each
    site where the layer moves rows: by the Pallas kernel at a width of
    whole 128-lane sublanes (the LFM2 cell's 2048, a slab as it lies; the
    Nemotron cell's 2688, in a padded slab), by XLA's gather at one that
    is not; both uses, forward and backward; nothing else chooses.
    Under ``MIN_ROWS`` tokens (a model's 8-token init) XLA's gather runs
    and nothing is counted: no kernel could have paid there."""
    registry = telemetry.get_registry()
    count = lambda: {  # noqa: E731
        (i, u): registry.counter("fedml_moe_row_move_total", impl=i, use=u).value
        for i in ("pallas", "xla") for u in ("rows", "tokens")}
    before = count()
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    jax.eval_shape(
        jax.grad(lambda x, *a: moe.dropless_moe(
            x, *a, top_k=4, experts_held=(4, 4))[0].sum()),
        s(tokens, width).update(dtype=jnp.bfloat16), s(width, 16), s(16),
        s(4, width, 48), s(4, width, 48), s(4, 48, width))
    added = {key: after - before[key] for key, after in count().items()}
    for (i, use), n in added.items():
        assert (n > 0) == (i == impl), added


def test_the_row_move_kernels_run_under_the_data_parallel_shard_map():
    """512 tokens a device on a data axis of 2: each device's share takes
    the kernels (interpreted here) inside ``RoutedExperts``' ``shard_map``,
    and loss, counts and gradients equal the unsharded layer's to bf16's
    order of summation."""
    from jax.sharding import Mesh

    from fedml_tpu.ops.moe import RoutedExperts
    from fedml_tpu.parallel.mesh import AXIS_DATA

    build = lambda mesh: RoutedExperts(  # noqa: E731
        dim=256, width=64, num_experts=16, top_k=4, experts_held=(4, 8),
        dtype=jnp.bfloat16, mesh=mesh)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 512, 256)),
                    jnp.float32)
    whole = build(None)
    variables = whole.init(jax.random.PRNGKey(0), x)

    def run(layer):
        def loss(params):
            out, stats = layer.apply({**variables, "params": params}, x)
            return jnp.sum(out.astype(jnp.float32) ** 2), stats
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(
            variables["params"])

    (want, stats), grads = run(whole)
    (got, stats_dp), grads_dp = run(
        build(Mesh(np.array(jax.devices()[:2]), (AXIS_DATA,))))
    assert stats_dp[:2].tolist() == stats[:2].tolist() and stats_dp[3] == 0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, g_dp in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_dp)):
        assert jnp.abs(g - g_dp).max() <= 2.0 ** -7 * jnp.abs(g).max()


# --- the whole decoder, and the trainer that takes it ----------------------

@pytest.fixture(scope="module")
def seeded():
    weights, biases = ref.init_weights(5, TINY)
    variables = {"params": runner.to_program(weights),
                 "buffers": runner.to_program(biases)}
    return weights, biases, variables


def test_decoder_matches_the_plain_reference_on_seeded_weights(seeded):
    weights, biases, variables = seeded
    model = HybridLM(runner.decoder_config(TINY), remat=True)
    assert (jax.tree.structure(model.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
            == jax.tree.structure(variables))
    toks = jnp.asarray(tokens(6))
    shape = ref.shape_of(TINY)

    def program_loss(params):
        logits, stats = model.apply({**variables, "params": params},
                                    toks[:, :-1], return_stats=True)
        logz = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logz, toks[:, 1:, None], -1).sum(), stats

    with jax.default_matmul_precision("highest"):
        (got, stats), g_got = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"])
        want, g_want = jax.jit(
            jax.value_and_grad(ref.loss_sum),
            static_argnums=(3, 4, 5))(weights, biases, toks, shape, "f32", False)
        close(jax.jit(model.apply)(variables, toks[:, :-1]),
              jax.jit(ref.forward, static_argnums=3)(
                  weights, biases, toks[:, :-1], shape)[0])
    close(got, want)
    g_got = runner.from_program(g_got, list(weights))
    for name in weights:
        close(g_got[name], g_want[name], rtol=5e-5)
    held, total, _, dropped = stats.tolist()
    assert total == 4 * B * T * 4 and 0 < held < total and dropped == 0


@pytest.fixture(scope="module")
def stepped(seeded):
    """(losses, params, registry counters, programs compiled, constants)
    after three steps of a ``WARMUP``-step warm-up from the seeded weights,
    at dp = 1 and at dp = 2 over the CPU's devices."""
    out = {}
    batches = [tokens(10 + i) for i in range(3)]
    for dp in (1, 2):
        telemetry.configure(enabled=True, reset=True)
        t = DistributedLMTrainer(
            DistTrainConfig(dp=dp, warmup_steps=WARMUP), dtype=jnp.float32,
            model=runner.decoder_config(TINY))
        assert jax.tree.structure(t.params) == jax.tree.structure(
            {"params": seeded[2]["params"]})
        t.params = jax.device_put(  # a copy: the step donates its params
            jax.tree.map(jnp.copy, {"params": seeded[2]["params"]}),
            t.param_shardings)
        t.constants = jax.device_put(
            jax.tree.map(jnp.copy, {"buffers": seeded[2]["buffers"]}),
            jax.tree.map(lambda a: a.sharding, t.constants))
        t.opt_state = t.init_opt_state()
        losses = [t.step(b[:, :-1], b[:, 1:]) for b in batches]
        out[dp] = (losses, jax.device_get(t.params),
                   telemetry.get_registry().snapshot(),
                   t._train_step._cache_size(), jax.device_get(t.constants))
    return out


def test_trainer_steps_the_decoder_as_the_reference_does(seeded, stepped):
    """Loss of each of three steps and the parameters' change after them,
    against the plain reference's AdamW under the same warm-up (the cell's
    comparison, tiny)."""
    weights, biases, _ = seeded
    shape = ref.shape_of(TINY)
    o = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    w = weights
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    with jax.default_matmul_precision("highest"):
        for step, loss in enumerate(stepped[1][0], start=1):
            want, g = ref.loss_and_grad(
                w, biases, jnp.asarray(tokens(9 + step)), shape, "f32", False, 1)
            close(loss, want)
            w, m, v = ref.lm.adamw(
                jax.tree.map(jnp.copy, w), g, m, v, jnp.float32(step),
                o["lr"] * step / WARMUP, o["b1"], o["b2"], o["eps"],
                o["weight_decay"])
    got = runner.from_program(stepped[1][1]["params"], list(weights))
    # Adam's first steps move an element by about lr x sign(gradient): where a
    # gradient is within rounding of 0 the order of a sum shows, so 1% of the
    # leaf's largest move (measured 2e-3), where a step left out reads 33%
    for name in weights:
        close(got[name] - weights[name], w[name] - weights[name], rtol=1e-2)


def test_data_parallel_steps_match_and_count_their_routing(stepped):
    (l1, p1, reg1, compiles1, _), (l2, p2, reg2, compiles2, _) = (
        stepped[1], stepped[2])
    assert compiles1 == compiles2 == 1  # one program, first step to last
    np.testing.assert_allclose(l2, l1, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=1e-6)
    for reg in (reg1, reg2):
        counters = reg["counters"]
        held = counters["fedml_moe_assignments_total{held=yes}"]
        away = counters["fedml_moe_assignments_total{held=no}"]
        assert held + away == 3 * 4 * B * T * 4 and held > 0
        assert counters["fedml_moe_dropped_total"] == 0
        assert reg["gauges"]["fedml_moe_held_load_max_over_mean"] >= 1.0
    assert (reg1["counters"]["fedml_moe_assignments_total{held=yes}"]
            == reg2["counters"]["fedml_moe_assignments_total{held=yes}"])


def test_a_step_reads_the_selection_bias_and_never_moves_it(seeded, stepped):
    """The constants come out of three steps as set-up put them in, at dp = 1
    and dp = 2: the layer chooses by the bias, how it is balanced is the
    training recipe's. ``apply`` has nothing mutable to hand back."""
    before = seeded[2]["buffers"]
    for dp in (1, 2):
        after = stepped[dp][4]["buffers"]
        assert jax.tree.structure(after) == jax.tree.structure(before)
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
            assert (np.asarray(a) == np.asarray(b)).all()
    out = HybridLM(runner.decoder_config(TINY)).apply(
        seeded[2], jnp.asarray(tokens(3)[:, :-1]))
    assert isinstance(out, jax.Array)


def test_warm_up_reaches_the_rate_and_stays_there(seeded):
    """``warmup_steps``: step t runs at lr * t / warmup_steps, then at lr;
    0 keeps the constant rate and optax's stateless scaling (the GPT-2
    step's program)."""
    model = runner.decoder_config(TINY)
    t = DistributedLMTrainer(DistTrainConfig(lr=1e-3, warmup_steps=2),
                             dtype=jnp.float32, model=model)
    rates = []
    state = t.opt_state
    grads = jax.tree.map(jnp.ones_like, t.params)
    for _ in range(3):  # AdamW's first moves are lr * sign(gradient)
        updates, state = t.opt.update(grads, state, t.params)
        rates.append(-float(updates["params"]["final_norm"]["scale"][0]))
    np.testing.assert_allclose(rates, [5.05e-4, 1.01e-3, 1.01e-3], rtol=1e-4)
    flat = DistributedLMTrainer(DistTrainConfig(lr=1e-3), dtype=jnp.float32,
                                model=model)
    assert (len(jax.tree.leaves(t.opt_state))
            == len(jax.tree.leaves(flat.opt_state)) + 1)  # the schedule's count


def test_dropped_counts_what_a_buffer_too_small_would_lose(layer):
    """``dropped`` is counted from the rows the taken size really gave: were
    the small buffer taken with more rows held than it has (a wrong
    predicate, a wrong clamp), the held assignments beyond it would read a
    row that is not theirs, and each one counts."""
    x, gate, bias, w1, w3, w2, _ = layer
    planted = bias.at[jnp.asarray([4, 5, 6, 7])].set(10.0)  # all land here
    chosen, w = moe.route_top_k(x, gate, planted, 4)
    A = chosen.size
    local = chosen.reshape(A) - 4
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=4).astype(jnp.int32)
    assert int(sizes.sum()) == A == 512
    for rows, lost in ((768, 0), (256, 256)):
        fits = jnp.diff(jnp.minimum(jnp.cumsum(sizes), rows), prepend=0)
        _, placed = moe._held_rows(
            x, w, w1[4:8], w3[4:8], w2[4:8],
            order=jnp.pad(order, (0, 768 - A)), place=jnp.argsort(order),
            is_held=jnp.ones(A, bool), group_sizes=fits, n_held=sizes.sum(),
            top_k=4, rows=rows)
        assert A - int(placed) == lost


@pytest.mark.parametrize("axes", [dict(tp=2), dict(sp=2)], ids=["tp", "sp"])
def test_model_and_sequence_axes_are_refused_for_the_decoder(axes):
    with pytest.raises(NotImplementedError, match="dp only"):
        DistributedLMTrainer(DistTrainConfig(**axes),
                             model=runner.decoder_config(TINY))


def test_gpt2_tree_and_its_runner_mapping_are_unchanged():
    """The GPT-2-style trainer keeps its tree (``lm_step.to_program`` still
    maps onto it), has no constants and no routing statistics."""
    from reference import lm as lm_ref
    from runners import lm_step

    cfg = dict(vocab_size=97, n_embd=64, n_layer=2, n_head=4, n_positions=32,
               mlp_ratio=4, init_std=0.02)
    t = DistributedLMTrainer(
        DistTrainConfig(), vocab_size=97, dim=64, num_heads=4, num_layers=2,
        max_len=32, dtype=jnp.float32)
    mapped = lm_step.to_program(lm_ref.init_weights(3, cfg), 2)
    assert jax.tree.structure(mapped) == jax.tree.structure(t.params)
    assert t.constants == {} and t.step_stats == ()
    assert sorted(t.params["params"]) == [
        "block_0", "block_1", "head", "ln_f", "wpe", "wte"]
    assert sorted(t.params["params"]["block_0"]) == [
        "LayerNorm_0", "LayerNorm_1", "MLPBlock_0", "SelfAttention_0"]
    b = tokens(1) % 97
    assert isinstance(t.step(b[:, :-1], b[:, 1:]), float)


def test_chunked_cross_entropy_reads_the_tied_head(seeded, stepped):
    """``ce_chunk`` never makes the (B, T, V) logits: it takes the hidden
    states and the head, which for this decoder is the embedding."""
    t = DistributedLMTrainer(DistTrainConfig(ce_chunk=16), dtype=jnp.float32,
                             model=runner.decoder_config(TINY))
    t.params = jax.device_put(
        jax.tree.map(jnp.copy, {"params": seeded[2]["params"]}),
        t.param_shardings)
    t.constants = jax.device_put(
        jax.tree.map(jnp.copy, {"buffers": seeded[2]["buffers"]}),
        jax.tree.map(lambda a: a.sharding, t.constants))
    t.opt_state = t.init_opt_state()
    b = tokens(10)
    np.testing.assert_allclose(t.step(b[:, :-1], b[:, 1:]), stepped[1][0][0],
                               rtol=2e-6)


def test_lfm2_tree_is_unchanged_by_the_new_kinds():
    """The two-part layers keep their leaves, name for name: what
    ``runners/lfm2_step.py`` maps onto and its cell's compiled step holds."""
    cfg = runner.decoder_config(TINY)
    assert cfg.tie_word_embeddings and cfg.expert_layers == 4
    shapes = jax.eval_shape(HybridLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    paths = lambda tree: sorted(  # noqa: E731
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree))
    conv = ["conv/in_proj/kernel", "conv/out_proj/kernel", "conv/taps"]
    attn = [f"attn/{n}" for n in (
        "k_norm/scale", "k_proj/kernel", "o_proj/kernel", "q_norm/scale",
        "q_proj/kernel", "v_proj/kernel")]
    norms = ["ffn_norm/scale", "operator_norm/scale"]
    dense = [f"mlp/{n}/kernel" for n in ("w1", "w2", "w3")]
    moe = [f"moe/{n}" for n in ("gate", "w1", "w2", "w3")]
    want = ["embed/embedding", "final_norm/scale"]
    for i, kind in enumerate(TINY["layer_types"]):
        want += [f"layer_{i}/{leaf}" for leaf in
                 (conv if kind == "conv" else attn) + norms
                 + (dense if i < TINY["num_dense_layers"] else moe)]
    assert paths(shapes["params"]) == sorted(want)
    assert paths(shapes["buffers"]) == [
        f"layer_{i}/moe/expert_bias" for i in range(2, 6)]


def test_kinds_and_pattern_letters_are_refused_by_name():
    import dataclasses

    cfg = runner.decoder_config(TINY)
    with pytest.raises(ValueError, match=r"\['sliding_attention'\].*mamba"):
        dataclasses.replace(cfg, layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="gelu"):
        dataclasses.replace(cfg, mlp_hidden_act="gelu")
    with pytest.raises(ValueError, match=r"\['-'\]"):
        layer_types_of_pattern("ME-*")
    assert layer_types_of_pattern("ME*") == ("mamba", "moe", "attention")
