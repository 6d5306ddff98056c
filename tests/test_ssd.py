"""The Mamba-2 mixer's core (ops/ssd.py): the chunked state-space scan
against a sequential float32 recurrence written here, values and the
gradient of every input, at lengths of one chunk, several chunks and under
one chunk, with one group and with eight; the convolution and the gated
norm against plain loops; the refusal of a length that is no multiple of the
chunk; and the dispatch counter.

Tolerances: float32 against float32 at "highest"; what differs is the
order of the sums (a chunk's matmuls against a running state): measured
2e-7 to 3e-6 relative at these sizes, so 2e-5 leaves a bf16 rounding (4e-3)
two orders outside."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry
from fedml_tpu.ops import ssd

RTOL = 2e-5
H, P, N, CHUNK = 8, 4, 16, 8


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, (np.abs(a - b).max(), scale)


def inputs(T, groups, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(n(batch, T, H) - 2.0)  # 0.02 .. 0.7
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    return (n(batch, T, H, P), dt, A, n(batch, T, groups, N),
            n(batch, T, groups, N), n(H))


def plain_scan(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D
    x_t``, one position at a time, head ``h`` reading group ``h // (H / G)``."""
    rep = H // B.shape[2]
    B, C = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def step(S, at_t):
        x_t, dt_t, B_t, C_t = at_t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t) + D[:, None] * x_t

    by_t = [jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)]
    _, y = jax.lax.scan(step, jnp.zeros((x.shape[0], H, P, N)), by_t)
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("T,impl", [(CHUNK, "chunked"), (4 * CHUNK, "chunked"),
                                    (CHUNK - 3, "sequential")],
                         ids=["one_chunk", "four_chunks", "under_one_chunk"])
def test_scan_matches_the_recurrence_values_and_gradients(T, impl, groups):
    args = inputs(T, groups)
    assert ssd.scan_impl(T, CHUNK) == impl
    with jax.default_matmul_precision("highest"):
        close(ssd.ssd_scan(*args, chunk=CHUNK), plain_scan(*args))
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (2, T, H, P)), jnp.float32)
        grad = lambda f: jax.jit(jax.grad(  # noqa: E731
            lambda *a: jnp.sum(f(*a) * w), tuple(range(6))))(*args)
        got = grad(lambda *a: ssd.ssd_scan(*a, chunk=CHUNK))
        want = grad(plain_scan)
    for g, wnt in zip(got, want):  # x, dt, A, B, C, D
        close(g, wnt)


def test_the_state_is_carried_across_chunks():
    """A bump in the first chunk reaches the last chunk's output (through
    three carried states), and no later position reaches an earlier one."""
    x, dt, A, B, C, D = inputs(4 * CHUNK, 8)
    A = A * 0.01  # slow decay: the state outlives a chunk
    base = ssd.ssd_scan(x, dt, A, B, C, D, chunk=CHUNK)
    early = ssd.ssd_scan(x.at[:, 2].add(1.0), dt, A, B, C, D, chunk=CHUNK)
    assert np.abs(np.asarray(early - base)[:, 3 * CHUNK:]).max() > 1e-3
    late = ssd.ssd_scan(x.at[:, 3 * CHUNK:].add(1.0), dt, A, B, C, D,
                        chunk=CHUNK)
    assert not np.abs(np.asarray(late - base)[:, :3 * CHUNK]).any()


@pytest.mark.parametrize("T", [CHUNK + 1, 3 * CHUNK - 2])
def test_a_length_that_is_no_multiple_of_the_chunk_is_refused(T):
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd.ssd_scan(*inputs(T, 1), chunk=CHUNK)


def test_the_dispatch_is_counted_once_a_call_site_a_trace():
    registry = telemetry.get_registry()
    value = lambda impl, T: registry.counter(  # noqa: E731
        "fedml_ssd_dispatch_total", impl=impl, seq_len=T, chunk=CHUNK).value
    before = value("chunked", 2 * CHUNK), value("sequential", 3)
    f = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=CHUNK))
    for _ in range(2):  # the second call runs the first one's trace
        f(*inputs(2 * CHUNK, 8))
    f(*inputs(3, 8))
    ssd.ssd_scan(*inputs(3, 8), chunk=CHUNK)  # op by op: nothing traced
    assert (value("chunked", 2 * CHUNK), value("sequential", 3)) == (
        before[0] + 1, before[1] + 1)


def test_conv_and_gated_norm_match_plain_loops():
    rng = np.random.default_rng(3)
    xBC = rng.standard_normal((2, 9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    bias = rng.standard_normal(6).astype(np.float32)
    want = np.zeros_like(xBC) + bias
    for t in range(9):
        for j in range(4):
            if t - j >= 0:  # causal: nothing before the first position
                want[:, t] += w[:, j] * xBC[:, t - j]
    close(ssd.causal_conv_silu(jnp.asarray(xBC), jnp.asarray(w),
                               jnp.asarray(bias)), want / (1 + np.exp(-want)))
    y = rng.standard_normal((2, 5, 12)).astype(np.float32)
    z = rng.standard_normal((2, 5, 12)).astype(np.float32)
    g = rng.standard_normal(12).astype(np.float32)
    gated = (y * z / (1 + np.exp(-z))).reshape(2, 5, 3, 4)  # 3 groups of 4
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    close(ssd.gated_rms_norm(jnp.asarray(y), jnp.asarray(z), jnp.asarray(g),
                             groups=3, eps=1e-5), normed.reshape(2, 5, 12) * g)


def test_the_whole_core_in_bfloat16_stays_near_float32():
    """``mamba2_core`` end to end (split, conv, softplus, scan, gated norm)
    in bfloat16 against itself in float32: bf16 products with float32 sums,
    decays in float32, so the result is within a few bf16 roundings."""
    rng = np.random.default_rng(4)
    heads, head_dim, state, groups = 8, 4, 16, 2
    inner, bc = heads * head_dim, groups * state
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    args = (n(2, 4 * CHUNK, 2 * inner + 2 * bc + heads),
            0.3 * n(inner + 2 * bc, 4), 0.3 * n(inner + 2 * bc), n(heads) - 3,
            jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)),
            jnp.ones(heads), jnp.ones(inner))
    kw = dict(heads=heads, head_dim=head_dim, state=state, groups=groups,
              chunk=CHUNK, eps=1e-5)
    want = ssd.mamba2_core(*args, **kw)
    got = ssd.mamba2_core(args[0].astype(jnp.bfloat16), *args[1:], **kw)
    assert got.dtype == jnp.bfloat16 and want.shape == (2, 4 * CHUNK, inner)
    close(got.astype(jnp.float32), want, rtol=4e-2)
