"""The one-mixer blocks of the ``layer_types`` decoder (models/hybrid_lm.py:
Mamba-2, attention without positions, relu2 experts with a shared expert and
a scaling factor; an untied head) against the plain float32 reference
``benchmark/reference/nemotron_h.py`` on seeded weights at a tiny size, whose
scan is the recurrence and never the chunked form; relu2 experts through
``dropless_moe`` against a dense loop at a width that is no multiple of 128;
and the share test of sixteen shares with one shared expert. Helpers and
tolerances are ``tests/test_hybrid_lm.py``'s."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hybrid_lm import B, T, close, tokens  # (puts benchmark/ on the path)

from reference import nemotron_h as nemo_ref  # noqa: E402
from runners import nemo3_step as nemo_runner  # noqa: E402

from fedml_tpu.models.hybrid_lm import HybridLM  # noqa: E402
from fedml_tpu.ops import moe  # noqa: E402

# width 48; Mamba-2 of 8 heads x 8 with a state of 16 in 2 groups, chunk 8;
# 4 heads on 2 KV heads of 16; 16 relu2 experts 40 wide of which 4 are held,
# top-6, scaled by 2.5, and a shared expert 72 wide; an untied head
NEMO = dict(
    hidden_size=48, hybrid_override_pattern="MEM*E", mamba_num_heads=8,
    mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=40, moe_intermediate_size=40,
    moe_shared_expert_intermediate_size=72, router_width=16,
    n_routed_experts=4, experts_held_offset=4, num_experts_per_tok=6,
    routed_scaling_factor=2.5, vocab_size=128, layer_norm_epsilon=1e-5,
    norm_eps=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, init_std=0.02, mlp_hidden_act="relu2",
    mamba_hidden_act="silu", norm_topk_prob=True, use_conv_bias=True,
    n_shared_experts=1, n_group=1, topk_group=1, use_bias=False, mlp_bias=False,
    attention_bias=False, mamba_proj_bias=False, tie_word_embeddings=False)


@pytest.fixture(scope="module")
def nemo_seeded():
    weights, biases = nemo_ref.init_weights(5, NEMO)
    variables = {"params": nemo_runner.to_program(weights),
                 "buffers": nemo_runner.to_program(biases)}
    return weights, biases, variables


def test_one_mixer_decoder_matches_the_plain_reference(nemo_seeded):
    """Logits, loss and the gradient of every leaf (the scan's ``A_log``,
    ``dt_bias`` and ``D``, the convolution's taps and bias, the gated norm,
    the shared expert and the untied head among them) against the reference,
    whose scan is the recurrence and never the chunked form."""
    weights, biases, variables = nemo_seeded
    model = HybridLM(nemo_runner.decoder_config(NEMO), remat=True)
    init = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert jax.tree.structure(init) == jax.tree.structure(variables)
    assert init["params"]["lm_head"].shape == (48, 128)  # untied: its own leaf
    assert HybridLM.head_kernel(init) is init["params"]["lm_head"]
    toks = jnp.asarray(tokens(6))
    shape = nemo_ref.shape_of(NEMO)

    def program_loss(params):
        logits, stats = model.apply({**variables, "params": params},
                                    toks[:, :-1], return_stats=True)
        logz = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logz, toks[:, 1:, None], -1).sum(), stats

    with jax.default_matmul_precision("highest"):
        (got, stats), g_got = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(variables["params"])
        want, g_want = jax.jit(
            jax.value_and_grad(nemo_ref.loss_sum), static_argnums=(3, 4, 5, 6))(
                weights, biases, toks, shape, "f32", False, False)
        close(jax.jit(model.apply)(variables, toks[:, :-1]),
              jax.jit(nemo_ref.forward, static_argnums=3)(
                  weights, biases, toks[:, :-1], shape)[0])
    close(got, want)
    g_got = nemo_runner.from_program(g_got, list(weights))
    for name in weights:
        close(g_got[name], g_want[name], rtol=5e-5)
    held, total, _, dropped = stats.tolist()
    assert total == 2 * B * T * 6 and 0 < held < total and dropped == 0


@pytest.fixture(scope="module")
def relu2_layer():
    """One relu2 expert block's seeded weights, all 128 experts at a width
    that is no multiple of 128: (x, gate, bias, w1, w2, shared w1, shared
    w2) and the reference's shape tuple with all of them held."""
    rng = np.random.default_rng(7)
    n = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    cfg = dict(NEMO, hidden_size=72, router_width=128, n_routed_experts=128,
               experts_held_offset=0)
    return (n(B * T, 72), n(72, 128), n(128) * 0.1, n(128, 72, 40),
            n(128, 40, 72), n(72, 56), n(56, 72), nemo_ref.shape_of(cfg))


def _plain_relu2(x, gate, bias, w1, w2, sw1, sw2, shape, held=(0, 128),
                 shared=True):
    offset, count = held
    shape = shape[:15] + (count, offset) + shape[17:]
    p = {"gate": gate, "ew1": w1[offset:offset + count],
         "ew2": w2[offset:offset + count], "sw1": sw1, "sw2": sw2}
    return nemo_ref._experts(x, p, bias, shape, "f32", False, shared=shared)[0]


def test_relu2_experts_and_the_scaling_factor_match_a_dense_loop(relu2_layer):
    """``dropless_moe`` with no ``w3`` and ``scale`` 2.5, 72 wide (no
    multiple of 128: XLA's gather moves the rows), against the reference's
    loop over experts; values and the gradient of every input."""
    x, gate, bias, w1, w2, sw1, sw2, shape = relu2_layer
    held = (8, 8)

    def program(x, gate, w1, w2):
        return moe.dropless_moe(x, gate, bias, w1[8:16], None, w2[8:16],
                                top_k=6, experts_held=held, scale=2.5,
                                form="relu2")[0]

    def plain(x, gate, w1, w2):
        return _plain_relu2(x, gate, bias, w1, w2, sw1, sw2, shape, held,
                            shared=False)

    with jax.default_matmul_precision("highest"):
        close(program(x, gate, w1, w2), plain(x, gate, w1, w2))
        grad = lambda f: jax.jit(jax.grad(  # noqa: E731
            lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2, 3)))(x, gate, w1, w2)
        for g, w in zip(grad(program), grad(plain)):
            close(g, w)
        # the scaling factor multiplies the routed sum and nothing else
        unscaled = moe.dropless_moe(x, gate, bias, w1[8:16], None, w2[8:16],
                                    top_k=6, experts_held=held,
                                    form="relu2")[0]
        close(2.5 * unscaled, program(x, gate, w1, w2))


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_block(
        relu2_layer):
    """The share test: sixteen chips holding experts 0-7, 8-15, ... route
    over all 128 alike; their routed parts, with the shared expert (which
    every chip computes alike) counted once, sum to the uncut reference's
    expert block, and their held assignments to all of them."""
    x, gate, bias, w1, w2, sw1, sw2, shape = relu2_layer
    variables = lambda o: {  # noqa: E731
        "params": {"gate": gate, "w1": w1[o:o + 8], "w2": w2[o:o + 8],
                   "shared_w1": sw1, "shared_w2": sw2},
        "buffers": {"expert_bias": bias}}
    share = lambda o, shared_width: jax.jit(moe.RoutedExperts(  # noqa: E731
        72, 40, 128, 6, experts_held=(o, 8), form="relu2", scale=2.5,
        shared_width=shared_width).apply)
    with jax.default_matmul_precision("highest"):
        whole = _plain_relu2(x, gate, bias, w1, w2, sw1, sw2, shape)
        first, stats = share(0, 56)(variables(0), x[None])
        parts, held = [first[0]], [int(stats[0])]
        for o in range(8, 128, 8):  # the other chips' routed parts alone
            v = variables(o)
            v["params"] = {k: a for k, a in v["params"].items()
                           if not k.startswith("shared_")}
            part, stats = share(o, 0)(v, x[None])
            parts.append(part[0])
            held.append(int(stats[0]))
            assert int(stats[1]) == B * T * 6 and int(stats[3]) == 0
        close(sum(parts), whole)
        assert sum(held) == B * T * 6
        # a share's own part is the reference's at the same share
        close(first[0], _plain_relu2(x, gate, bias, w1, w2, sw1, sw2, shape,
                                     (0, 8)))


def test_the_shared_experts_ops_stay_out_of_the_routed_experts_scopes(
        relu2_layer):
    """``moe_ms`` and ``moe_shuffle_ms`` find the routed experts by ``moe.``
    in an op's name and ``shared_expert_ms`` finds the shared expert by its
    scope: forward and backward, no op of the shared expert holds ``moe.``
    (as a method of the module it would: flax names a method's ops
    ``moe._shared_expert``, and the first chip run read it so)."""
    import re

    x, gate, bias, w1, w2, sw1, sw2, _ = relu2_layer
    layer = moe.RoutedExperts(72, 40, 128, 6, experts_held=(0, 8), form="relu2",
                              scale=2.5, shared_width=56, name="moe")
    variables = {"params": {"gate": gate, "w1": w1[:8], "w2": w2[:8],
                            "shared_w1": sw1, "shared_w2": sw2},
                 "buffers": {"expert_bias": bias}}
    text = jax.jit(jax.grad(lambda p: layer.apply(
        {**variables, "params": p}, x[None])[0].sum())).lower(
            variables["params"]).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    shared = {n for n in names if "shared_expert" in n}
    assert {n for n in shared if "transpose(" in n}
    assert {n for n in shared if "transpose(" not in n}
    assert not {n for n in shared if "moe." in n}
    assert {n for n in names if "moe.experts" in n}
