"""Pallas state-space scan (TPU): the chunked dual form of ``ops/ssd.py``
with its (L, L) decays, its masked scores and its carried state in VMEM.

``ops/ssd.py _chunked`` is the mathematics and the rounding points; this is
the same algorithm as two kernels, forward and backward, behind one custom
VJP. What ``_chunked`` writes to HBM between its XLA fusions (a (chunks,
heads, L, L) float32 decay, the masked scores, every chunk's closing state,
the states handed on by a ``lax.scan``) lives here in vector registers and
VMEM: x, dt, B, C are read once a pass and y written once.

Layout. x and y are indexed as they lie, as (b, T, H*P), B and C as (b, T,
G*N): no head-major transpose before or after. A grid step is one chunk of
one group: the grid is (batch, group, chunk) with the chunk axis innermost
and sequential. The group's ``C B^T`` (L, L) is made once for its Q = H / G
heads, the x tile (L, Q*P) is lane-dense, and the state of the group's heads
is one float32 (N, Q*P) scratch (the state *transposed*: a head's decay is
then a factor along lanes, like everything else a head owns), zeroed at
chunk 0. ``dt`` and the running sum ``cum`` (both float32, (b, T, H): 2 MB
at the Nemotron cell; the cumulative sum itself stays XLA's) are handed in
positions-on-sublanes, (b, G, T, Q), and ``cum`` also positions-on-lanes,
(b, G, Q, T): a decay ``exp(cum_l - cum_s)`` needs a column of the one and a
row of the other. A head's (L, 1) column is spread over its P lanes by a
broadcast and a select a 128-lane tile (``_spread``); heads that share a
lane tile (two at P = 64) are kept apart in a product by zeroing the other
heads' lanes in one operand and stacking the heads along the contraction,
as ``flash_attention.py`` does: the tile's (L, 128) output is lane-dense and
the MXU does no more passes than half-filled ones would take.

Precision, as ``_chunked``: products take operands in the inputs' dtype and
accumulate float32; dt, cum, every decay and the carried state are float32;
the masked scores, ``dt x``, its decayed copy and the state read by ``C``
are rounded to the inputs' dtype where ``_chunked``'s ``mm`` rounds them.
The ``D`` skip is added in float32 before y's one rounding. Backward, the
cotangents that enter a product (dy, the state's, the scores') are rounded
the same way.

Backward: the reverse sweep over the chunks with the state's cotangent in
the scratch. From dy and the state each chunk was handed (the forward's
residual, (b, chunks, G, N, Q*P) float32) it makes the chunk's scores and
decays again and writes dx, dB and dC (summed over the group's heads inside
the step), dD's partial sums, and the gradients of dt (through ``dt x``) and
of cum. The latter has three parts, each as autodiff has it:

- inside the chunk a head's ``d(cum_l - cum_s) = dM . M`` (M the masked
  scores in float32, dM = dy X^T) goes to ``cum_l`` by its row sums and from
  ``cum_s`` by its column sums. The two are sums of one float32 matrix, so
  what cancels between them in the reverse cumulative sum cancels to
  float32 rounding; rewriting them as ``sum_p dy y`` and ``sum_p X dX``
  would make the two halves from different bf16 products and leave a bf16
  rounding's noise where there should be none. Row sums come out with the
  positions on sublanes, column sums with the positions on lanes: the
  kernel writes cum's cotangent in the two layouts cum came in and XLA adds
  them;
- what the chunk read from the handed state scales with ``exp(cum_l)``:
  ``sum_p dy_lp exp(cum_l) (C state)_lp`` to ``cum_l``;
- the closing state's ``exp(cum_L - cum_s)`` takes ``dt_s sum_p x_sp dXe_sp``
  from ``cum_s`` and gives it to the chunk's last position, which also takes
  what the whole decay earns, ``sum dS_out . exp(cum_L) S_in``.

The per-head sums over a head's lanes are ``_gather_heads``; the reverse
cumulative sum and the reductions to ``ddt`` and ``dA`` stay XLA's over (b,
T, H) arrays.

Two pallas calls in a program, whatever the number of layers: the launchers
are jitted with static arguments only, so equal call sites share one
lowering. The forward always writes the handed states, a VJP's or not: the
write hides under the step's arithmetic (0.60 against 0.58 ms a call at the
Nemotron cell: PERF.md section 6, PR 35), and a second forward kernel would
be a third Mosaic lowering at every start. Off the TPU the kernels run in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, _TN, _VMEM_LIMIT, _dot, _only_head


def _bwd_vmem(L: int, W: int, N: int, itemsize: int) -> int:
    """An upper estimate of one backward instance's scoped VMEM, in bytes:
    the double-buffered tiles (x, dy, dx; B, C, dB, dC; the state handed
    in), the state's cotangent, and the step's (L, W), (N, W) and (L, L)
    float32 values that Mosaic spills. Held to ahead-of-time compiles for
    the v5e (PR 35): every shape it puts under the limit compiled (W = 512
    and 1024 at L = N = 128; L = 256 or N = 256 at W = 512), it refuses all
    that Mosaic refused (W = 4096; W = 2048 at N = 256; W = 1024 at
    L = 256), and W = 2048 at L = N = 128, which Mosaic still took."""
    piped = 2 * (3 * L * W * itemsize + 4 * L * N * itemsize + N * W * 4)
    return piped + N * W * 4 + 14 * L * W * 4 + 3 * N * W * 4 + 6 * L * L * 4


def kernel_shapes_ok(T: int, chunk: int, H: int, P: int, G: int, N: int,
                     itemsize: int = 2) -> bool:
    """Whether the kernels take a scan of these shapes: the chunk and the
    state whole 128-lane tiles (the (L, L) scores and the (N, Q P) state
    are MXU operands), a head a whole share of a lane tile and a group's
    heads whole lane tiles, at least two chunks (one chunk carries
    nothing), and a step's working set inside scoped VMEM."""
    if H % G or T % chunk or T < 2 * chunk:
        return False
    W = H // G * P
    return (chunk % 128 == 0 and N % 128 == 0 and 128 % P == 0
            and W % 128 == 0
            and _bwd_vmem(chunk, W, N, itemsize) <= _VMEM_LIMIT)


def _join(tiles):
    """128-lane tiles side by side."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _spread(cols, P: int):
    """(rows, Q), one value a head -> (rows, Q * P): a head's value on each
    of its P lanes. A broadcast a head and a select a head but one, a
    128-lane tile at a time."""
    rows, Q = cols.shape
    hp = 128 // P
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    tiles = []
    for first in range(0, Q, hp):
        tile = jnp.broadcast_to(cols[:, first + hp - 1:first + hp],
                                (rows, 128))
        for i in range(hp - 2, -1, -1):
            tile = jnp.where(lane < (i + 1) * P,
                             cols[:, first + i:first + i + 1], tile)
        tiles.append(tile)
    return _join(tiles)


def _gather_heads(wide, P: int):
    """(rows, Q * P) -> (rows, Q): the sum over each head's lanes."""
    rows, W = wide.shape
    hp, Q = 128 // P, W // P
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, Q), 1)
    out = jnp.zeros((rows, Q), jnp.float32)
    for j in range(W // 128):
        tile = wide[:, j * 128:(j + 1) * 128]
        for i in range(hp):
            own = tile if hp == 1 else jnp.where(
                (lane >= i * P) & (lane < (i + 1) * P), tile, 0.0)
            out = jnp.where(head == j * hp + i,
                            jnp.sum(own, axis=1, keepdims=True), out)
    return out


def _chunk_values(x_ref, dt_ref, cum_ref, P: int):
    """What both passes make of a chunk's x, dt and cum: x in float32,
    ``dt``, ``cum`` and the decay to the chunk's end spread over the heads'
    lanes, ``cum`` at the chunk's last position, ``X = dt x`` in float32."""
    L = x_ref.shape[1]
    x32 = x_ref[0].astype(jnp.float32)
    dt_wide = _spread(dt_ref[0, 0], P)
    cum_wide = _spread(cum_ref[0, 0], P)
    last = cum_wide[L - 1:L, :]
    return x32, dt_wide, cum_wide, last, jnp.exp(last - cum_wide), x32 * dt_wide


def _masked_scores(scores, keep, cum_col, cum_row):
    """One head's decay ``exp(cum_l - cum_s)`` at s <= l, 0 above the
    diagonal, and the scores under it; float32 (L, L)."""
    decay = jnp.exp(jnp.where(keep, cum_col - cum_row, -jnp.inf))
    return decay, scores * decay


def _keep(L: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    return rows >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)


def _fwd_kernel(x_ref, dt_ref, cum_ref, rows_ref, b_ref, c_ref, d_ref,
                y_ref, handed_ref, st_scr, *, P: int):
    """Grid (b, G, chunks), chunks innermost. Refs: x/y (1, L, W) with
    W = Q P; dt/cum (1, 1, L, Q) float32; rows (1, 1, Q, L), cum with the
    positions on lanes; b/c (1, L, N); d (1, W), D on each head's lanes;
    the state handed to this chunk (1, 1, 1, N, W) float32, out;
    scratch: the state (N, W) float32, transposed, across the chunks."""
    dtype = x_ref.dtype
    L, W = x_ref.shape[1:]
    hp = 128 // P

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros(st_scr.shape, jnp.float32)

    x32, _, cum_wide, last, to_end, X32 = _chunk_values(
        x_ref, dt_ref, cum_ref, P)
    X = X32.astype(dtype)
    Bc, Cc, cum, cum_rows = b_ref[0], c_ref[0], cum_ref[0, 0], rows_ref[0, 0]
    state = st_scr[...]
    handed_ref[0, 0, 0] = state

    scores, keep = _dot(Cc, Bc, _NT), _keep(L)
    tiles = []
    for j in range(W // 128):
        tile = X[:, j * 128:(j + 1) * 128]
        masked, own = [], []
        for i in range(hp):
            q = j * hp + i
            _, m = _masked_scores(scores, keep, cum[:, q:q + 1],
                                  cum_rows[q:q + 1, :])
            masked.append(m.astype(dtype))
            own.append(_only_head(tile, i, hp, P))
        tiles.append(_dot(jnp.concatenate(masked, axis=1),
                          jnp.concatenate(own, axis=0), _NN))
    y = _join(tiles) + _dot(Cc, state.astype(dtype), _NN) * jnp.exp(cum_wide)
    y_ref[0] = (y + x32 * d_ref[...]).astype(y_ref.dtype)
    st_scr[...] = jnp.exp(last) * state + _dot(
        Bc, (X32 * to_end).astype(dtype), _TN)


def _bwd_kernel(x_ref, dt_ref, cum_ref, rows_ref, b_ref, c_ref, d_ref,
                handed_ref, dy_ref, dx_ref, ddt_ref, dcum_ref, drows_ref,
                db_ref, dc_ref, dd_ref, dst_scr, *, P: int):
    """The forward's grid with the chunks in reverse. Refs as the forward's;
    handed (1, 1, 1, N, W), the state this chunk was handed; dy/dx (1, L,
    W); ddt/dcum (1, 1, L, Q) and drows (1, 1, Q, L) float32, cum's cotangent
    in the two layouts cum came in (their sum is the whole); db/dc (1, L,
    N); dd (1, 1, 1, W) float32, summed over the chunks in place; scratch:
    the cotangent of the state this chunk hands on, (N, W) float32."""
    dtype = x_ref.dtype
    L, W = x_ref.shape[1:]
    hp, Q = 128 // P, W // P

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros(dst_scr.shape, jnp.float32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

    x32, dt_wide, cum_wide, last, to_end, X32 = _chunk_values(
        x_ref, dt_ref, cum_ref, P)
    X, Xe32 = X32.astype(dtype), X32 * to_end
    Bc, Cc, cum, cum_rows = b_ref[0], c_ref[0], cum_ref[0, 0], rows_ref[0, 0]
    dy = dy_ref[0]
    dy32 = dy.astype(jnp.float32)
    dy = dy.astype(dtype)
    state32, dstate32 = handed_ref[0, 0, 0], dst_scr[...]
    state, dstate = state32.astype(dtype), dstate32.astype(dtype)
    from_start, whole = jnp.exp(cum_wide), jnp.exp(last)

    # the skip
    dd_ref[0, 0] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
    # what the chunk read from the state it was handed: exp(cum) (C state)
    dread32 = dy32 * from_start
    dread = dread32.astype(dtype)
    dcum_read = _gather_heads(dread32 * _dot(Cc, state, _NN), P)
    dC = _dot(dread, state, _NT)
    dst_scr[...] = _dot(Cc, dread, _TN) + whole * dstate32
    # the state it handed on: exp(cum_L) state + B^T (exp(cum_L - cum) X)
    dXe = _dot(Bc, dstate, _NN) * to_end
    dB = _dot(Xe32.astype(dtype), dstate, _NT)
    ddt_end = _gather_heads(x32 * dXe, P)  # X's share of Xe's cotangent
    dlast = _gather_heads(
        jnp.sum(dstate32 * (whole * state32), axis=0, keepdims=True), P)
    # inside the chunk: (scores * decay) X, a head's (L, L) at a time
    scores, keep = _dot(Cc, Bc, _NT), _keep(L)
    dscores = jnp.zeros((L, L), jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, (L, Q), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (Q, L), 0)
    dcum_in = jnp.zeros((L, Q), jnp.float32)    # as the later position l
    drows = jnp.zeros((Q, L), jnp.float32)      # as the earlier position s
    dX_tiles = []
    for j in range(W // 128):
        lanes = slice(j * 128, (j + 1) * 128)
        masked, dy_own = [], []
        for i in range(hp):
            q = j * hp + i
            decay, m = _masked_scores(scores, keep, cum[:, q:q + 1],
                                      cum_rows[q:q + 1, :])
            masked.append(m.astype(dtype))
            dy_own.append(_only_head(dy[:, lanes], i, hp, P))
            dm = _dot(dy_own[-1], X[:, lanes], _NT)
            dscores = dscores + dm * decay
            dgap = dm * m
            dcum_in = jnp.where(head == q,
                                jnp.sum(dgap, axis=1, keepdims=True), dcum_in)
            drows = jnp.where(head_row == q,
                              -jnp.sum(dgap, axis=0, keepdims=True), drows)
        dX_tiles.append(_dot(jnp.concatenate(masked, axis=0),
                             jnp.concatenate(dy_own, axis=0), _TN))
    dX = _join(dX_tiles)
    dscores = dscores.astype(dtype)
    dc_ref[0] = (dC + _dot(dscores, Bc, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot(dscores, Cc, _TN)).astype(db_ref.dtype)
    dx_ref[0] = ((dX + dXe) * dt_wide + dy32 * d_ref[...]).astype(dx_ref.dtype)
    ddt_ref[0, 0] = _gather_heads(x32 * dX, P) + ddt_end
    # exp(cum_L - cum_s) gives cum_s what it takes from cum_L
    to_last = dt_ref[0, 0] * ddt_end
    at_last = jax.lax.broadcasted_iota(jnp.int32, (L, Q), 0) == L - 1
    dcum_ref[0, 0] = dcum_in + dcum_read - to_last + jnp.where(
        at_last, dlast + jnp.sum(to_last, axis=0, keepdims=True), 0.0)
    drows_ref[0, 0] = drows


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _operands(x, dt, cum, B, C, D, chunk: int, reverse: bool):
    """The arrays as the kernels index them, and their block specs."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    Q, L, nc = H // G, chunk, T // chunk
    W = Q * P
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    by_group = lambda a: a.reshape(b, T, G, Q).transpose(0, 2, 1, 3)  # noqa: E731
    wide = pl.BlockSpec((1, L, W), lambda i, g, c: (i, at(c), g))
    cols = pl.BlockSpec((1, 1, L, Q), lambda i, g, c: (i, g, at(c), 0))
    rows = pl.BlockSpec((1, 1, Q, L), lambda i, g, c: (i, g, 0, at(c)))
    group = pl.BlockSpec((1, L, N), lambda i, g, c: (i, at(c), g))
    skip = pl.BlockSpec((1, W), lambda i, g, c: (0, g))
    handed = pl.BlockSpec((1, 1, 1, N, W), lambda i, g, c: (i, at(c), g, 0, 0))
    arrays = (x.reshape(b, T, H * P), by_group(dt), by_group(cum),
              cum.transpose(0, 2, 1).reshape(b, G, Q, T),
              B.reshape(b, T, G * N),
              C.reshape(b, T, G * N),
              jnp.repeat(D.astype(jnp.float32), P)[None])
    return arrays, dict(wide=wide, cols=cols, rows=rows, group=group,
                        skip=skip, handed=handed), (b, G, nc, Q, W, N)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _forward(x, dt, cum, B, C, D, chunk: int, interpret: bool):
    """x (b, T, H, P); dt, cum (b, T, H) float32; B, C (b, T, G, N); D (H,)
    -> y (b, T, H, P) in x's dtype and the state each chunk was handed,
    (b, T / chunk, G, N, Q P) float32."""
    with jax.named_scope("ssd.core"):
        arrays, s, (b, G, nc, _, W, N) = _operands(
            x, dt, cum, B, C, D, chunk, reverse=False)
        y, handed = pl.pallas_call(
            functools.partial(_fwd_kernel, P=x.shape[3]),
            out_shape=(jax.ShapeDtypeStruct(arrays[0].shape, x.dtype),
                       jax.ShapeDtypeStruct((b, nc, G, N, W), jnp.float32)),
            grid=(b, G, nc),
            in_specs=[s["wide"], s["cols"], s["cols"], s["rows"], s["group"],
                      s["group"], s["skip"]],
            out_specs=(s["wide"], s["handed"]),
            scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
            **_params(interpret),
        )(*arrays)
        return y.reshape(x.shape), handed


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(x, dt, cum, B, C, D, handed, dy, chunk: int, interpret: bool):
    """The cotangents of x, dt, cum, B, C, D from y's."""
    with jax.named_scope("ssd.core"):
        b, T, H, P = x.shape
        arrays, s, (_, G, nc, _, W, N) = _operands(
            x, dt, cum, B, C, D, chunk, reverse=True)
        flat = lambda a, dtype: jax.ShapeDtypeStruct(a.shape, dtype)  # noqa: E731
        dx, ddt, dcum, drows, dB, dC, dD = pl.pallas_call(
            functools.partial(_bwd_kernel, P=P),
            out_shape=(flat(arrays[0], x.dtype),
                       flat(arrays[1], jnp.float32),
                       flat(arrays[1], jnp.float32),
                       flat(arrays[3], jnp.float32),
                       flat(arrays[4], B.dtype), flat(arrays[5], C.dtype),
                       jax.ShapeDtypeStruct((b, G, 1, W), jnp.float32)),
            grid=(b, G, nc),
            in_specs=[s["wide"], s["cols"], s["cols"], s["rows"], s["group"],
                      s["group"], s["skip"], s["handed"], s["wide"]],
            out_specs=(s["wide"], s["cols"], s["cols"], s["rows"], s["group"],
                       s["group"],
                       pl.BlockSpec((1, 1, 1, W), lambda i, g, c: (i, g, 0, 0))),
            scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
            **_params(interpret),
        )(*arrays, handed, dy.reshape(arrays[0].shape))
        by_time = lambda a: a.transpose(0, 2, 1, 3).reshape(b, T, H)  # noqa: E731
        return (dx.reshape(x.shape), by_time(ddt),
                by_time(dcum) + drows.reshape(b, H, T).transpose(0, 2, 1),
                dB.reshape(B.shape), dC.reshape(C.shape),
                dD.sum(0).reshape(H, P).sum(-1).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_chunked(x, dt, cum, B, C, D, chunk: int):
    """The chunked scan with its skip: x (b, T, H, P); dt (b, T, H), after
    its softplus, and cum, the running sum of ``dt A`` inside each chunk,
    both float32; B, C (b, T, G, N); D (H,). Returns y (b, T, H, P) in x's
    dtype. Shapes as ``kernel_shapes_ok`` admits."""
    interpret = jax.default_backend() != "tpu"
    return _forward(x, dt, cum, B, C, D, chunk, interpret)[0]


def _fwd(x, dt, cum, B, C, D, chunk):
    interpret = jax.default_backend() != "tpu"
    y, handed = _forward(x, dt, cum, B, C, D, chunk, interpret)
    return y, (x, dt, cum, B, C, D, handed)


def _bwd(chunk, res, dy):
    interpret = jax.default_backend() != "tpu"
    return _backward(*res, dy, chunk, interpret)


ssd_chunked.defvjp(_fwd, _bwd)
