"""The scan's kernel pair (ops/pallas/ssd.py) in interpret mode, at small
shapes that fill its tiles (head width 64, state 128, chunk 128): against
``ops/ssd.py``'s two XLA forms, values and the gradient of every input; the
carried state; the dispatch rule by shape; and the two counters.

Tolerances. In float32 at "highest" the kernels do ``_chunked``'s sums in
``_chunked``'s order but for the matmuls' tiling: 2e-5 of the largest element
(tests/test_ssd.py's), measured 3e-8 (y) to 1e-5 (dA). Against the
recurrence the dual form itself is further off at a chunk of 128 than at
test_ssd.py's 8, ``_chunked`` as far as the kernels: its decays span
``exp(-400)`` here and ``dA`` sums 128 cancelling terms a chunk, measured
5e-6 (y, dx, dB, dC), 1.4e-5 (ddt), 1.4e-4 (dA) for both against a float64
recurrence; so 2e-5 for the values and 5e-4 for the gradients. In bfloat16:
test_ssd.py's whole-core tolerance, 4e-2."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry
from fedml_tpu.ops import ssd
from fedml_tpu.ops.pallas import ssd as ssd_kernel

RTOL = 2e-5
P, N, CHUNK = 64, 128, 128
NAMES = ("x", "dt", "A", "B", "C", "D")
# (heads, groups, chunks): one group of four heads (two lane tiles), two
# groups of two heads (one lane tile each) over four chunks, eight groups
SHAPES = [(4, 1, 2), (4, 2, 4), (16, 8, 2)]


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, (np.abs(a - b).max(), scale)


def inputs(H, G, chunks, batch=2, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    T = chunks * CHUNK
    dt = jax.nn.softplus(n(batch, T, H) - 2.0)  # 0.02 .. 0.7
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    return (n(batch, T, H, P).astype(dtype), dt, A,
            n(batch, T, G, N).astype(dtype), n(batch, T, G, N).astype(dtype),
            n(H))


def by_xla(form):
    """``_chunked`` or ``_sequential`` with the skip, as ``ssd_scan`` adds it."""
    def scan(x, dt, A, B, C, D):
        y = form(x, dt, A, B, C, CHUNK)
        return (y + x.astype(jnp.float32) * D[:, None]).astype(x.dtype)
    return scan


def by_kernel(*args):
    return ssd.ssd_scan(*args, chunk=CHUNK)


def value_and_grads(scan, args):
    w = jnp.asarray(np.random.default_rng(1).standard_normal(args[0].shape),
                    jnp.float32)

    def loss(*a):
        y = scan(*a)
        return jnp.sum(y.astype(jnp.float32) * w), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(6)), has_aux=True))(*args)
    return dict(zip(("y",) + NAMES, (y,) + grads))


@pytest.fixture(scope="module")
def float32_readings():
    """Kernel, ``_chunked`` and ``_sequential`` once a shape, for the cases
    below (an interpreted kernel pair takes seconds)."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for shape in SHAPES:
            args = inputs(*shape)
            assert ssd.scan_kernel(args[0], args[3], CHUNK) == "pallas"
            out[shape] = {"kernel": value_and_grads(by_kernel, args),
                          "chunked": value_and_grads(by_xla(ssd._chunked), args),
                          "sequential": value_and_grads(
                              by_xla(ssd._sequential), args)}
    return out


@pytest.mark.parametrize("what", ("y",) + NAMES)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "h%d_g%d_c%d" % s)
def test_kernel_pair_matches_both_xla_forms_in_float32(
        float32_readings, shape, what):
    got = float32_readings[shape]
    close(got["kernel"][what], got["chunked"][what])
    close(got["kernel"][what], got["sequential"][what],
          rtol=RTOL if what == "y" else 5e-4)


@pytest.fixture(scope="module")
def bfloat16_readings():
    """The float32 recurrence, and the kernels and ``_chunked`` in bf16."""
    shape = (4, 2, 4)
    low = inputs(*shape, dtype=jnp.bfloat16)
    assert ssd.scan_kernel(low[0], low[3], CHUNK) == "pallas"
    return (value_and_grads(by_xla(ssd._sequential), inputs(*shape)),
            value_and_grads(by_kernel, low),
            value_and_grads(by_xla(ssd._chunked), low))


@pytest.mark.parametrize("what", ("y",) + NAMES)
def test_kernel_pair_in_bfloat16_stays_near_float32(bfloat16_readings, what):
    """bf16 operands, float32 sums, decays and state in float32: y and
    every gradient within a few bf16 roundings of the float32 recurrence,
    and as near it as ``_chunked`` in bf16 is (twice its distance, or 1e-2
    of the largest element where that is nearer zero)."""
    want, got, xla = bfloat16_readings
    assert got[what].dtype == xla[what].dtype
    gap = lambda a: np.abs(np.asarray(a, np.float64)  # noqa: E731
                           - np.asarray(want[what], np.float64)).max()
    scale = np.abs(np.asarray(want[what])).max()
    assert gap(got[what]) <= 4e-2 * scale
    assert gap(got[what]) <= max(2 * gap(xla[what]), 1e-2 * scale)


def test_the_state_is_carried_across_chunks_and_starts_from_zero():
    """A bump at position 0 reaches the last chunk's output (through three
    handed states), no later position reaches an earlier one, and a batch
    row's scan does not see the row before it (the state's scratch is
    zeroed at every row's first chunk)."""
    x, dt, A, B, C, D = inputs(4, 2, 4)
    A = A * 0.01  # slow decay: the state outlives a chunk
    base = by_kernel(x, dt, A, B, C, D)
    early = by_kernel(x.at[:, 0].add(1.0), dt, A, B, C, D)
    assert np.abs(np.asarray(early - base)[:, 3 * CHUNK:]).max() > 1e-3
    late = by_kernel(x.at[:, 3 * CHUNK:].add(1.0), dt, A, B, C, D)
    assert not np.abs(np.asarray(late - base)[:, :3 * CHUNK]).any()
    alone = by_kernel(*(a[1:] for a in (x, dt)), A, B[1:], C[1:], D)
    assert np.array_equal(np.asarray(alone[0]), np.asarray(base[1]))
    other = by_kernel(x.at[0].add(1.0), dt, A, B, C, D)
    assert np.array_equal(np.asarray(other[1]), np.asarray(base[1]))


# (T, chunk, H, P, G, N) -> what runs the scan
@pytest.mark.parametrize("shape,kernel", [
    ((8192, 128, 64, 64, 8, 128), "pallas"),   # the Nemotron cell
    ((1024, 128, 64, 64, 8, 128), "pallas"),   # tests/test_tpu_compile.py
    ((256, 128, 4, 64, 1, 128), "pallas"),
    ((512, 256, 8, 128, 2, 256), "pallas"),
    ((32, 8, 8, 4, 8, 16), "xla"),             # tests/test_ssd.py
    ((32, 8, 8, 8, 2, 16), "xla"),             # the benchmark's tiny cell
    ((8, 128, 64, 64, 8, 128), "xla"),         # under one chunk: sequential
    ((128, 128, 64, 64, 8, 128), "xla"),       # one chunk carries nothing
    ((8192, 64, 64, 64, 8, 128), "xla"),       # the chunk fills no lane tile
    ((8192, 128, 64, 64, 8, 64), "xla"),       # nor the state
    ((8192, 128, 64, 48, 8, 128), "xla"),      # a head no share of a tile
    ((8192, 128, 8, 32, 8, 128), "xla"),       # a group's heads half a tile
    ((8192, 128, 64, 64, 1, 128), "xla"),      # 4,096 lanes a step: VMEM
], ids=lambda v: v if isinstance(v, str) else "x".join(map(str, v)))
def test_the_dispatch_rule_reads_the_shapes(shape, kernel):
    T, chunk, H, P_, G, N_ = shape
    x = jax.ShapeDtypeStruct((1, T, H, P_), jnp.bfloat16)
    B = jax.ShapeDtypeStruct((1, T, G, N_), jnp.bfloat16)
    assert ssd.scan_kernel(x, B, chunk) == kernel
    assert ssd_kernel.kernel_shapes_ok(T, chunk, H, P_, G, N_) == (
        kernel == "pallas")


def test_both_counters_count_once_a_call_site_a_trace():
    registry = telemetry.get_registry()

    def values():
        return {(family, impl, T, chunk): registry.counter(
            f"fedml_ssd_{family}_total", impl=impl, seq_len=T,
            chunk=chunk).value
            for family, impl, T, chunk in [
                ("dispatch", "chunked", 256, CHUNK),
                ("kernel", "pallas", 256, CHUNK), ("kernel", "xla", 256, CHUNK),
                ("dispatch", "chunked", 32, 8), ("kernel", "xla", 32, 8),
                ("kernel", "pallas", 32, 8),
                ("dispatch", "sequential", 8, CHUNK),
                ("kernel", "xla", 8, CHUNK)]}

    before = values()
    wide = inputs(4, 1, 2)
    f = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=CHUNK))
    for _ in range(2):  # the second call runs the first one's trace
        f(*wide)
    f(*(a[:, :8] for a in wide[:2]), wide[2], wide[3][:, :8], wide[4][:, :8],
      wide[5])
    rng = np.random.default_rng(0)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    small = (n(2, 32, 8, 4), jax.nn.softplus(n(2, 32, 8)), -jnp.ones(8),
             n(2, 32, 8, 16), n(2, 32, 8, 16), n(8))
    jax.jit(jax.grad(lambda *a: ssd.ssd_scan(*a, chunk=8).sum()))(*small)
    ssd.ssd_scan(*wide, chunk=CHUNK)  # op by op: nothing traced
    grown = {k: v - before[k] for k, v in values().items()}
    assert grown == {
        ("dispatch", "chunked", 256, CHUNK): 1,
        ("kernel", "pallas", 256, CHUNK): 1, ("kernel", "xla", 256, CHUNK): 0,
        ("dispatch", "chunked", 32, 8): 1, ("kernel", "xla", 32, 8): 1,
        ("kernel", "pallas", 32, 8): 0,
        ("dispatch", "sequential", 8, CHUNK): 1, ("kernel", "xla", 8, CHUNK): 1}
    # the family the benchmark reads keeps its labels, to the letter
    series = registry.snapshot()["counters"]
    assert "fedml_ssd_dispatch_total{chunk=128,impl=chunked,seq_len=256}" in series
    assert "fedml_ssd_kernel_total{chunk=128,impl=pallas,seq_len=256}" in series


def test_only_a_scan_that_takes_the_kernel_imports_it():
    """``ops/ssd.py`` imports the kernels' module where it asks the rule
    about a chunked scan, not at its own import: a GPT-2 or an LFM2 step,
    which imports ``ops`` but traces no scan, lowers nothing new."""
    import os
    import subprocess
    import sys

    code = ("import sys, fedml_tpu.ops.ssd, fedml_tpu.ops.moe\n"
            "import fedml_tpu.models.hybrid_lm, fedml_tpu.parallel.trainer\n"
            "sys.exit('fedml_tpu.ops.pallas.ssd' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
