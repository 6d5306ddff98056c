"""LM training ops: chunked cross-entropy and block rematerialization.

These are the memory levers of the LM trainer (DistTrainConfig): both must
be pure memory/time tradeoffs — numerics identical to the naive
formulations.
"""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ops.losses import chunked_lm_cross_entropy


def _plain_ce(h, w, t):
    logz = jax.nn.log_softmax((h @ w).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logz, t[..., None], -1))


def test_chunked_ce_matches_plain():
    rng = np.random.RandomState(0)
    B, T, D, V = 2, 12, 16, 50
    h = jnp.asarray(rng.randn(B, T, D), jnp.float32)
    w = jnp.asarray(rng.randn(D, V), jnp.float32) * 0.1
    t = jnp.asarray(rng.randint(0, V, (B, T)))
    np.testing.assert_allclose(_plain_ce(h, w, t),
                               chunked_lm_cross_entropy(h, w, t, chunk=4),
                               rtol=1e-6)
    g1 = jax.grad(chunked_lm_cross_entropy, (0, 1))(h, w, t, chunk=4)
    g2 = jax.grad(_plain_ce, (0, 1))(h, w, t)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_chunked_ce_keeps_no_chunk_of_logits_outside_its_loop():
    """An array the chunk body makes from no input (a zero to select
    against) is moved out of the differentiated loop by scan's partial
    evaluation and kept whole, at (B, chunk, V): the loss's one-hot pick
    selects against the row's max for that reason."""
    B, T, D, V, chunk = 2, 12, 16, 50, 4
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w, t: chunked_lm_cross_entropy(h, w, t, chunk=chunk), (0, 1)))(
        jnp.zeros((B, T, D)), jnp.zeros((D, V)), jnp.zeros((B, T), jnp.int32))
    outside = [v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars]
    assert not [s for s in outside if s[-2:] == (chunk, V)]


def test_chunked_ce_rejects_indivisible_t():
    h = jnp.zeros((1, 10, 4))
    w = jnp.zeros((4, 7))
    t = jnp.zeros((1, 10), jnp.int32)
    try:
        chunked_lm_cross_entropy(h, w, t, chunk=4)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


def test_transformer_lm_remat_identical():
    """remat=True must change memory behavior only: outputs and grads are
    bit-compatible with the non-remat model on the same params."""
    from fedml_tpu.models.transformer import TransformerLM

    kw = dict(vocab_size=64, dim=32, num_heads=4, num_layers=2, max_len=16)
    m0 = TransformerLM(**kw)
    m1 = TransformerLM(**kw, remat=True)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    p = m0.init(jax.random.PRNGKey(0), toks)
    np.testing.assert_allclose(m0.apply(p, toks), m1.apply(p, toks),
                               rtol=1e-6)

    def loss(m):
        return lambda p: (m.apply(p, toks).astype(jnp.float32) ** 2).mean()

    g0 = jax.grad(loss(m0))(p)
    g1 = jax.grad(loss(m1))(p)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_return_hidden_head_equivalence():
    """apply(return_hidden) @ head == apply() — the chunked-CE contract."""
    from fedml_tpu.models.transformer import TransformerLM

    m = TransformerLM(vocab_size=64, dim=32, num_heads=4, num_layers=2,
                      max_len=16)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 16)))
    p = m.init(jax.random.PRNGKey(0), toks)
    full = m.apply(p, toks)
    hid = m.apply(p, toks, return_hidden=True)
    np.testing.assert_allclose(full, hid @ p["params"]["head"]["kernel"],
                               rtol=1e-5, atol=1e-5)
