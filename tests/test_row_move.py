"""The Pallas row-move kernels (ops/pallas/row_move.py) alone, in interpret
mode on the CPU, against ``src[index]`` and a plain float sum: pure moves
bit for bit, weighted sums rounded once, nothing read for a row or slot that
is not held, zeros past the rows held; and ``ops/moe.py``'s ``_take_rows``
through them against the XLA gathers it falls back to."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.telemetry import get_registry
from fedml_tpu.ops import moe
from fedml_tpu.ops.pallas import row_move
from fedml_tpu.ops.pallas.row_move import (
    row_move_shapes_ok,
    rows_from_tokens,
    token_lists,
    tokens_from_rows,
)

N, ROWS = 40, 272          # tokens; buffer rows (two 128-row tiles and 16)
# (dtype, row width): the narrowest slab of each dtype, then rows that fill no
# slab and lie in one padded up: bf16 2688 = 21 sublanes in 24 (the Nemotron
# cell's), bf16 768 = 6 in 8, float32 384 = 3 in 4
CASES = {"bfloat16": ("bfloat16", 256), "float32": ("float32", 128),
         "bfloat16-2688": ("bfloat16", 2688), "bfloat16-768": ("bfloat16", 768),
         "float32-384": ("float32", 384)}
# rows held: none; one tile less one row; into the second tile; every row
HELD = {"none": 0, "tile_less_one": 127, "part": 150, "all": ROWS}


def _problem(case, k, seed=0, tokens=N):
    """Sources with every row finite, and indices both ways."""
    rng = np.random.default_rng(seed)
    dtype, D = CASES[case]
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    index = jnp.asarray(rng.integers(0, tokens, ROWS), jnp.int32)
    w = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    return normal(tokens, D), normal(ROWS, D), index, w


def _poisoned(src, read):
    """``src`` with NaN in every row that ``read`` does not name."""
    keep = np.zeros(src.shape[0], bool)
    keep[np.asarray(read).reshape(-1)] = True
    return jnp.where(keep[:, None], src, jnp.nan)


def _f64(a):
    return np.asarray(a.astype(jnp.float32), np.float64)


def _rounded_once(got, want64, terms64, dtype):
    """``got`` is ``want64`` rounded once to ``dtype``: within half a unit
    in the last place of the result, plus float32's share of the terms."""
    half_ulp = {"bfloat16": 2.0 ** -8, "float32": 2.0 ** -24}[dtype]
    bound = 1.01 * half_ulp * np.abs(want64) + 1e-6 * terms64 + 1e-30
    assert (np.abs(_f64(got) - want64) <= bound).all()


@pytest.mark.parametrize("held", list(HELD))
@pytest.mark.parametrize("case", list(CASES))
def test_rows_out_of_tokens(case, held):
    """``out[r] = src[index[r]]`` under ``n_held``, bit for bit; zero rows
    after; no row that ``index[:n_held]`` does not name is read."""
    x, _, index, _ = _problem(case, 1)
    n_held = HELD[held]
    got = rows_from_tokens(_poisoned(x, index[:n_held]), index, n_held)
    assert got.shape == (ROWS, x.shape[1]) and got.dtype == x.dtype
    assert np.isfinite(_f64(got)).all()          # no stranger's row was read
    assert not _f64(got[n_held:]).any()          # exactly zero past n_held
    assert (got[:n_held] == x[index][:n_held]).all()


def _slots(tokens, k, n_held, rows, seed=1):
    """Which slot (token * k + j) each buffer row belongs to, as the layer's
    sort gives it (padded past the slots there are), and from it what the
    kernel is handed: (order, the lists, held (N, k), place (N, k))."""
    rng = np.random.default_rng(seed)
    order = np.zeros(rows, np.int32)
    slots = rng.permutation(tokens * k)[:rows]
    order[:len(slots)] = slots
    held = np.zeros(tokens * k, bool)
    held[order[:n_held]] = True
    place = np.full(tokens * k, rows - 1, np.int32)
    place[order[:n_held]] = np.arange(n_held)
    lists = token_lists(jnp.asarray(order), n_held, tokens, k)
    return order, lists, held.reshape(tokens, k), place.reshape(tokens, k)


@pytest.mark.parametrize("held", list(HELD))
@pytest.mark.parametrize("weighted", [False, True], ids=["pure", "weighted"])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("case", list(CASES))
def test_tokens_out_of_rows(case, k, weighted, held):
    """``out[n] = sum_j [slot (n, j) is held] * w[n, j] * src[its row]``, in
    float32, rounded once; over two tiles of tokens, the second short."""
    tokens, dtype = 200, CASES[case][0]
    _, ys, _, w = _problem(case, k, tokens=tokens)
    rows = min(ROWS, tokens * k - 8)     # k = 1: fewer slots than ROWS
    ys = ys[:rows]
    n_held = min(HELD[held], rows)
    _, lists, is_held, place = _slots(tokens, k, n_held, rows)
    weights = np.where(is_held, np.asarray(w) if weighted else 1.0, 0.0)
    got = tokens_from_rows(_poisoned(ys, place[is_held]), lists,
                           jnp.asarray(weights, jnp.float32))
    assert got.shape == (tokens, ys.shape[1]) and got.dtype == ys.dtype
    assert np.isfinite(_f64(got)).all()          # no slot not held was read
    terms = _f64(ys)[place] * weights.astype(np.float64)[..., None]
    _rounded_once(got, terms.sum(1), np.abs(terms).sum(1), dtype)
    if k == 1 and not weighted:                  # a pure move again
        assert (got == jnp.where(is_held, ys[place[:, 0]], 0)).all()


def test_sizes_that_fill_no_whole_tile():
    """A buffer of 21 rows and 13 tokens: the one grid step's block hangs
    over the end of both arrays."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((13, 128)), jnp.float32)
    index = jnp.asarray(rng.integers(0, 13, 21), jnp.int32)
    got = rows_from_tokens(x, index, 17)
    assert (got == jnp.where((jnp.arange(21) < 17)[:, None], x[index], 0)).all()
    order = jnp.asarray(rng.permutation(13 * 4)[:21], jnp.int32)
    held = np.zeros(13 * 4, bool)
    held[np.asarray(order[:17])] = True
    back = tokens_from_rows(got, token_lists(order, 17, 13, 4),
                            jnp.asarray(held.reshape(13, 4), jnp.float32))
    want = np.zeros((13, 128))
    for r in range(17):
        want[int(order[r]) // 4] += _f64(got)[r]
    np.testing.assert_allclose(_f64(back), want, rtol=1e-6, atol=1e-6)


def test_which_rows_make_slabs():
    """Whole 128-lane sublanes in bf16 or float32: a row that fills a slab
    the kernels read (1, 2 or 4 words, or a multiple of 8 sublanes) is laid
    out by a plain reshape, as the LFM2 cell's and the smoke's widths are;
    any other lies in the next such slab, padded. Not a width with a lane to
    spare, nor a dtype the kernel does not unpack. And the layer takes the
    kernels only from ``MIN_ROWS`` tokens on (not on a model's 8-token
    init)."""
    heights = lambda D: tuple(  # noqa: E731
        row_move._slab_sublanes(D, t) for t in (jnp.bfloat16, jnp.float32))
    for D in (2048, 1024, 512, 256):           # a row is its own slab
        assert heights(D) == (D // 128, D // 128)
        x = jnp.arange(3 * D, dtype=jnp.float32).reshape(3, D)
        assert (row_move.to_slabs(x) == x.reshape(3, D // 128, 128)).all()
    assert heights(128) == (2, 1) and heights(3 * 128) == (4, 4)
    assert heights(6 * 128) == (8, 8) and heights(12 * 128) == (16, 16)
    assert heights(2688) == (24, 24)           # 21 sublanes: the Nemotron cell
    x = jnp.arange(2 * 384, dtype=jnp.float32).reshape(2, 384)
    slabs = row_move.to_slabs(x)                # three sublanes in four
    assert slabs.shape == (2, 4, 128) and not slabs[:, 3].any()
    assert (slabs[:, :3].reshape(2, 384) == x).all()
    assert all(row_move_shapes_ok(D, t) for D in range(128, 4097, 128)
               for t in (jnp.bfloat16, jnp.float32))
    assert not any(row_move_shapes_ok(D, t) for D in (0, 64, 192, 2000)
                   for t in (jnp.bfloat16, jnp.float32))
    assert not row_move_shapes_ok(2048, jnp.float16)
    impl = lambda n, D: moe._row_move_impl(  # noqa: E731
        jax.ShapeDtypeStruct((n, D), jnp.bfloat16))
    assert impl(row_move.MIN_ROWS, 2048) == "pallas"
    assert impl(row_move.MIN_ROWS, 2688) == "pallas"
    assert impl(row_move.MIN_ROWS, 2000) == "xla"
    assert impl(8, 2048) is None and impl(row_move.MIN_ROWS - 1, 2000) is None


def _moves_of(tokens, k, n_held, rows, impl):
    order, _, _, place = _slots(tokens, k, n_held, rows)
    return moe._moves(jnp.asarray(order), jnp.asarray(place),
                      jnp.int32(n_held), rows, impl)


@pytest.mark.parametrize("use", ["rows", "tokens"])
@pytest.mark.parametrize("case", list(CASES))
def test_each_move_is_the_others_transpose(case, use):
    """``jax.vjp`` of ``_take_rows`` through the kernels against the same
    through XLA's gathers: the rows' cotangent goes back as tokens out of
    rows, the tokens' as rows out of tokens (weighted, and each row against
    its token's cotangent for the weights)."""
    tokens, k, n_held, dtype = 200, 4, HELD["part"], CASES[case][0]
    x, ys, _, w = _problem(case, k, tokens=tokens)
    rng = np.random.default_rng(5)
    src, w = (x, None) if use == "rows" else (ys, w)
    out_rows = ROWS if use == "rows" else tokens
    g = jnp.asarray(rng.standard_normal((out_rows, src.shape[1])), dtype)

    def through(impl):
        moves = _moves_of(tokens, k, n_held, ROWS, impl)
        # what lies past the rows held is whatever the grouped product left
        poisoned = src if use == "rows" else src.at[n_held:].set(jnp.nan)
        out, vjp = jax.vjp(lambda s, w: moe._take_rows(use, impl, s, w, moves),
                           poisoned, w)
        return out, vjp(g)

    (got, grads), (want, grads_xla) = through("pallas"), through("xla")
    tol = {"float32": 2e-6, "bfloat16": 2.0 ** -7}[dtype]
    for a, b in [(got, want)] + [(a, b) for a, b in zip(grads, grads_xla)
                                 if a is not None]:
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(_f64(a)).all()
        assert np.abs(_f64(a) - _f64(b)).max() <= tol * np.abs(_f64(b)).max()


@pytest.mark.parametrize("case", ["bfloat16", "float32", "bfloat16-768"])
def test_the_layer_through_the_kernels_equals_it_through_xla(case, monkeypatch):
    """``dropless_moe`` at a width and length the kernels take against the
    same layer with the XLA gathers it falls back to: result, statistics,
    and the gradients of x, gate, w1, w3, w2 (the gate's comes through the
    routing weights, whose gradient the combine's backward owes)."""
    rng = np.random.default_rng(2)
    (dtype, D), F, tokens = CASES[case], 48, row_move.MIN_ROWS
    n = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, gate, bias = n(tokens, D).astype(dtype), n(D, 16), n(16) * 0.1
    weights = n(4, D, F), n(4, D, F), n(4, F, D)

    def loss(x, gate, w1, w3, w2):
        out, stats = moe.dropless_moe(x, gate, bias, w1, w3, w2, top_k=4,
                                      experts_held=(4, 4))
        return jnp.sum(out.astype(jnp.float32) ** 2), stats

    def run():
        jax.clear_caches()          # the layer is jitted: trace it anew
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True))(
            x, gate, *weights)

    count = lambda impl: sum(  # noqa: E731
        get_registry().counter("fedml_moe_row_move_total", impl=impl,
                               use=use).value for use in ("rows", "tokens"))
    before = count("pallas"), count("xla")
    (got, stats), grads = run()
    assert count("pallas") > before[0] and count("xla") == before[1]
    monkeypatch.setattr(moe, "_row_move_impl", lambda x: "xla")
    (want, stats_xla), grads_xla = run()
    assert count("xla") > before[1]
    assert stats.tolist() == stats_xla.tolist() and 0 < stats[0] < stats[1]
    # float32: the order of four float32 terms; bf16: an output that lay
    # between two values rounds the other way now and then
    rtol = {"float32": 2e-6, "bfloat16": 2.0 ** -7}[dtype]
    np.testing.assert_allclose(got, want, rtol=rtol)
    for g, g_xla in zip(grads, grads_xla):
        scale = np.abs(_f64(g_xla)).max()
        assert np.abs(_f64(g) - _f64(g_xla)).max() <= rtol * scale


def _kernel_calls(jaxpr) -> int:
    """``pallas_call`` equations in a jaxpr and every jaxpr under it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub)
    return n


def test_an_outer_checkpoint_adds_no_third_forward_of_the_expert_block():
    """ROADMAP S7.1 held that remat ``full`` around a layer made the expert
    block run forward three times (the layer's recompute, and inside it each
    buffer size's own ``jax.checkpoint`` again). It does not: the gradient of
    a ``dropless_moe`` layer holds as many kernel calls under an outer
    ``jax.checkpoint`` as without one. A side of the ``cond``: 5 forward (two
    row moves, three grouped products), 4 for the one recompute (the
    combine's move is not needed), 8 backward (two row moves, three products'
    two transposes each): two forward runs of the products, not three."""
    D, F, tokens, k = 256, 48, row_move.MIN_ROWS, 4
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    args = (sds(tokens, D, dtype=jnp.bfloat16), sds(D, 16), sds(4, D, F),
            sds(4, D, F), sds(4, F, D))

    def layer(x, gate, w1, w3, w2):
        out, _ = moe.dropless_moe(x, gate, jnp.zeros(16), w1, w3, w2, top_k=k,
                                  experts_held=(4, 4))
        return out

    def calls(layer):
        loss = lambda *a: jnp.sum(layer(*a).astype(jnp.float32) ** 2)  # noqa: E731
        return _kernel_calls(jax.make_jaxpr(
            jax.grad(loss, (0, 1, 2, 3, 4)))(*args).jaxpr)

    plain, rematted = calls(layer), calls(jax.checkpoint(layer))
    assert plain == rematted == 2 * (5 + 4 + 8)
