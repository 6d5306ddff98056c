"""The LM step's cross-entropy (``ops/losses.py lm_cross_entropy``): one
custom-VJP function, held to the ``log_softmax`` + ``take_along_axis`` form
it replaced in the trainer — value, gradient, the gradient's dtype, what it
keeps for the backward, the scope its ops carry, and a vocabulary-sharded
run against an unsharded one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.losses import lm_cross_entropy, lm_token_nll
from fedml_tpu.parallel.trainer import DistTrainConfig, DistributedLMTrainer


def _replaced_ce(logits, targets):
    """What the trainer's ``loss_fn`` ran until PR 30."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logz, targets[..., None], -1)[..., 0]
    return -ll.mean()


def _case(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(2.0 * rng.standard_normal(shape), dtype)
    targets = jnp.asarray(rng.integers(0, shape[-1], shape[:-1]), jnp.int32)
    return logits, targets


def _bf16_ulps_apart(a, b) -> int:
    """Largest distance between two bfloat16 arrays, counted in
    representable values (sign-magnitude bits mapped onto one line)."""
    def line(x):
        bits = np.asarray(x).view(np.int16).astype(np.int32)
        return np.where(bits < 0, -(bits & 0x7FFF), bits)
    return int(np.abs(line(a) - line(b)).max())


# V = 8192 is the LFM2 cell's share of its vocabulary, 50257 GPT-2's (at a
# small T); the trainer hands over (B, T, V), the chunked path (B, chunk, V)
@pytest.mark.parametrize("shape", [(8, 8192), (2, 4, 8192), (6, 50257),
                                   (2, 3, 50257)],
                         ids=["2d-8192", "3d-8192", "2d-50257", "3d-50257"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_lm_cross_entropy_matches_the_replaced_form(shape, dtype):
    logits, targets = _case(shape, dtype)
    want, want_g = jax.value_and_grad(_replaced_ce)(logits, targets)
    got, got_g = jax.jit(jax.value_and_grad(lm_cross_entropy))(logits, targets)
    assert got.dtype == jnp.float32 and got_g.dtype == dtype
    np.testing.assert_allclose(got, want, rtol=1e-6)
    if dtype == jnp.float32:
        # the same float32 arithmetic in another order: a few units in the
        # last place of each entry
        np.testing.assert_allclose(got_g, want_g, rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(want_g).max()))
    else:
        assert _bf16_ulps_apart(got_g, want_g) <= 1


def test_the_picked_logit_has_the_only_negative_gradient():
    logits, targets = _case((3, 5, 97), jnp.float32, seed=1)
    g = np.asarray(jax.grad(lm_cross_entropy)(logits, targets))
    onehot = np.arange(97) == np.asarray(targets)[..., None]
    assert (g[onehot] < 0).all() and (g[~onehot] > 0).all()
    np.testing.assert_allclose(g.sum(-1), 0.0, atol=1e-6)  # softmax - onehot


@pytest.mark.parametrize("target", [-1, 97, -100],
                         ids=["negative", "V", "ignore_id"])
def test_a_target_outside_the_vocabulary_picks_the_rows_max(target):
    """The precondition is 0 <= target < V. Outside it no column matches:
    the row's loss is ``lse - max`` and its gradient a plain softmax, finite
    and silent, where ``take_along_axis`` wrapped a negative id and gave NaN
    past V. Pinned so that a change to it is seen."""
    logits, targets = _case((3, 97), jnp.float32, seed=2)
    targets = targets.at[1].set(target)
    nll, vjp = jax.vjp(lambda x: lm_token_nll(x, targets), logits)
    x = np.asarray(logits, np.float64)
    lse = np.log(np.exp(x).sum(-1))
    np.testing.assert_allclose(nll[1], lse[1] - x[1].max(), rtol=1e-6)
    g = np.asarray(vjp(jnp.ones(3))[0])
    np.testing.assert_allclose(g[1], np.exp(x[1] - lse[1]), rtol=1e-5)
    np.testing.assert_allclose(g[1].sum(), 1.0, rtol=1e-6)
    np.testing.assert_allclose(g[[0, 2]].sum(-1), 0.0, atol=1e-6)  # in range


@pytest.mark.parametrize("fn,keeps_f32", [(lm_cross_entropy, False),
                                          (_replaced_ce, True)],
                         ids=["lm_cross_entropy", "replaced_form"])
def test_no_float32_copy_of_bf16_logits_is_kept_for_the_backward(fn, keeps_f32):
    """At the GPT-2 cell's shape, by shapes alone: the backward's closure
    holds the logits as they came, the targets, a (B, T) vector and the (V,)
    column index. The replaced form kept float32 (B, T, V) arrays, 1.65 GB
    each."""
    shape = (8, 1024, 50257)
    logits = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    targets = jax.ShapeDtypeStruct(shape[:-1], jnp.int32)
    kept = jax.tree.leaves(jax.eval_shape(
        lambda x, t: jax.vjp(lambda x: fn(x, t), x)[1], logits, targets))
    wide_f32 = [r for r in kept
                if r.shape[-1:] == shape[-1:] and r.dtype == jnp.float32]
    assert bool(wide_f32) == keeps_f32, kept
    if not keeps_f32:
        assert sorted((r.shape, str(r.dtype)) for r in kept if r.shape) == [
            (shape[:-1], "float32"), (shape[:-1], "int32"), (shape, "bfloat16"),
            (shape[-1:], "int32")]


def test_forward_and_backward_ops_carry_the_callers_scope():
    """``scoped_time`` and ``scope_reduce.classify`` find the loss by
    ``lm.loss`` in ``op_name``: the custom VJP's backward is traced inside
    the transpose of the scope it was called under."""
    def scoped(logits, targets):
        with jax.named_scope("lm.loss"):
            return lm_cross_entropy(logits, targets)

    logits, targets = _case((2, 4, 64), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(scoped)).lower(
        logits, targets).as_text(debug_info=True)
    # the sum of exponentials, and the softmax of the backward
    assert '/jvp(lm.loss)/exp"' in text
    assert '/transpose(jvp(lm.loss))/exp"' in text
    assert text.count("stablehlo.exponential") == 2


def test_vocabulary_sharded_loss_and_first_update_match_unsharded():
    """Under tp = 2 the logits are sharded over V: the max, the two sums and
    the one-hot compare partition under GSPMD as ``log_softmax`` did."""
    vocab, B, T = 64, 4, 16
    rng = np.random.default_rng(0)
    batches = rng.integers(0, vocab, (2, B, T + 1)).astype(np.int32)
    runs = {}
    for tp in (1, 2):
        t = DistributedLMTrainer(
            DistTrainConfig(dp=1, tp=tp, sp=1, lr=1e-3), vocab_size=vocab,
            dim=32, num_heads=4, num_layers=1, max_len=T, dtype=jnp.float32)
        if tp == 2:
            head = t.params["params"]["head"]["kernel"]
            assert head.sharding.shard_shape(head.shape) == (32, vocab // 2)
        first, second = batches
        loss = t.step(first[:, :-1], first[:, 1:])
        runs[tp] = (loss, jax.device_get(t.params),
                    t.step(second[:, :-1], second[:, 1:]))
    np.testing.assert_allclose(runs[2][0], runs[1][0], rtol=1e-6)
    np.testing.assert_allclose(runs[2][2], runs[1][2], rtol=1e-6)
    # AdamW's first update moves every parameter by about lr = 1e-3
    for a, b in zip(jax.tree.leaves(runs[2][1]), jax.tree.leaves(runs[1][1])):
        np.testing.assert_allclose(a, b, atol=1e-6)
