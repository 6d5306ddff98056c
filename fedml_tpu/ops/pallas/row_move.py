"""Pallas row moves (TPU): the two gathers of a routed expert layer, each
the other's transpose, done by DMA from indices the kernel is handed.

``rows_from_tokens``: ``out[r] = src[index[r]]`` for the ``n_held`` first
rows of a buffer, bit for bit; zeros written (nothing read) for the rest.
``tokens_from_rows``: ``out[n] = sum_j w[n, j] * src[the row of slot (n,
j)]`` over the held slots of a token's k, accumulated in float32 and rounded
once; a slot that is not held issues no DMA and is not even looked at: the
kernel walks ``token_lists``, the held slots in token order. Work follows the
rows held: at an eighth of the slots held (8 of 64 experts on a chip) a call
copies an eighth of what ``src[place]`` would, and no (N * k, D) array
exists.

How a row gets to be one DMA. Mosaic slices an HBM array only by whole
tiles of its last two dims, and a row of a (rows, D) array is one sublane
of D / 128 such tiles. So the source is first laid out as *slabs*
(``to_slabs``: a reshape to (rows, D / 128, 128), which XLA:TPU runs as one
streaming copy and Mosaic never sees), a row the block of its own tiles,
contiguous in HBM. A row whose D / 128 sublanes are no height both compilers
take (``_slab_sublanes``: a bf16 row of 2688 is 21, 10.5 words) lies in the
next taller slab, zero sublanes after it (PR 37: a pad before the reshape,
still XLA's), and the kernels read back the row's own chunks and no more. A
grid step starts one DMA for each row it needs
(indices and counts arrive by scalar prefetch), all in flight at once on one
semaphore, waits for them, and reads the landed slabs back a 128-lane
column at a time (a strided sublane load) into the ordinary lane-dense
(tile, D) output block. Every load inside the kernels is 32-bit: a bf16
slab is read through a uint32 view of the buffer it landed in, where a word
holds two sublanes of one lane (chunk 2s of the row low, chunk 2s + 1
high), and widened exactly.

Two kernels a buffer size and no more (PERF.md section 6, PR 33): every
distinct kernel is traced and lowered by Mosaic at every process start,
cache or no cache, and that is set-up the benchmark judges. So the rows
kernel only copies (what scales a row or multiplies it into another is an
elementwise pass outside, XLA's), the tokens kernel is always weighted (a
plain sum passes ones), and the launchers are jitted with static arguments
only, as the flash launchers are: equal shapes share one lowering.

What the chip said (PR 32): the kernels are bound by the scalar core issuing
the copies (about 40 ns a row), not by the copies' latency: starting a
tile's copies while the tile before was written out changed nothing. And
(PR 37, rows of 2688 in bf16) the bytes of a copy do count a little: the
rows kernel took 0.128 ms over 6,400 rows in slabs of 32 sublanes and 0.093
in slabs of 24, the tokens kernel 0.62 in both.

Off the TPU the kernels run in interpret mode, as every kernel of the
package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows (or tokens) a grid step moves: its DMAs are all in flight together
TILE = 128
# a layer of fewer tokens than this is left to XLA's gather (ops/moe.py): a
# kernel's set-up (traced, lowered and loaded at every start) buys nothing
# on a model's 8-token init or a toy layer
MIN_ROWS = 256


def _slab_sublanes(D: int, dtype) -> int | None:
    """Sublanes of a row's slab, or None where a row makes no slab the
    kernels can read (a width with a lane to spare, a dtype they do not
    unpack). A row is D / 128 sublanes of 128 lanes; its slab is as tall as
    the next height both compilers take: 1, 2 or 4 32-bit words, or a
    multiple of a tile's 8 sublanes (under 8 XLA and Mosaic agree on a tile
    as tall as the slab only at powers of two). So a bf16 row of 2048 is its
    own 16 sublanes, and one of 2688, 21 sublanes = 10.5 words, lies in a
    slab of 24 whose last three the kernels never read."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    if D <= 0 or D % LANES:
        return None
    sublanes, per_word = D // LANES, 4 // dtype.itemsize
    for words in (1, 2, 4):
        if sublanes <= words * per_word:
            return words * per_word
    return -(-sublanes // 8) * 8


def row_move_shapes_ok(D: int, dtype) -> bool:
    """Whether rows of this width and dtype make slabs the kernels can
    move: whole 128-lane sublanes (D % 128 == 0) in bf16 or float32."""
    return _slab_sublanes(D, dtype) is not None


def to_slabs(x: jax.Array) -> jax.Array:
    """(rows, D) -> (rows, S, 128), a row one contiguous slab of S =
    ``_slab_sublanes`` sublanes: a plain reshape where the row fills its
    slab, the row padded with zero sublanes first where it does not. Plain
    XLA either way: one copy at the rate of HBM, no kernel to set up."""
    rows, D = x.shape
    sublanes = _slab_sublanes(D, x.dtype)
    if sublanes * LANES != D:
        x = jnp.pad(x, ((0, 0), (0, sublanes * LANES - D)))
    return x.reshape(rows, sublanes, LANES)


# The kernels' bodies are written in ``lax`` primitives: every ``jnp`` call
# and operator inside a kernel is a jitted function traced anew each time the
# body is (PR 32: 2,300 small traces, 4 s of the LFM2 cell's set-up).
def _word_view(buf):
    """The buffer the slabs land in, (..., tile, S, 128), as 32-bit words:
    float32 as it is; bf16 as uint32, a word two sublanes of one lane. (Made
    once a kernel: a ref's bitcast is an ``eval_shape`` each time.)"""
    return buf if buf.dtype == jnp.float32 else buf.bitcast(jnp.uint32)


def _row_words(words, chunks: int) -> int:
    """The word-sublanes of a slab in ``words`` (``_word_view``) that hold
    the row's ``chunks`` 128-lane chunks: the slab may be taller."""
    return chunks if words.dtype == jnp.float32 else -(-chunks // 2)


def _columns(words, s: int, chunks: int, *at):
    """The float32 128-lane columns that sublane ``s`` of the slabs in
    ``words`` (``_word_view``), at leading index ``at``, holds: [(chunk of
    the row, (tile, 128) float32)]. A float32 sublane is chunk ``s``; a
    uint32 one holds chunk 2s low and 2s + 1 high, widened exactly (the
    high half of the last word is no chunk of a row of an odd ``chunks``)."""
    w = words[(*at, slice(None), s, slice(None))]
    if w.dtype == jnp.float32:
        return [(s, w)]
    f32 = lambda u: lax.bitcast_convert_type(u, jnp.float32)  # noqa: E731
    columns = [(2 * s, f32(lax.shift_left(w, lax.full_like(w, 16))))]
    if 2 * s + 1 < chunks:
        columns.append((2 * s + 1, f32(lax.bitwise_and(
            w, lax.full_like(w, 0xFFFF0000)))))
    return columns


def _rows_kernel(index_ref, n_ref, src_ref, out_ref, buf, sem, *, tile: int,
                 whole_wait: bool):
    r0 = pl.program_id(0) * tile
    n = jnp.clip(n_ref[0] - r0, 0, tile)   # rows of this tile that are held

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(n > 0)
    def _():
        copy = lambda i, s: pltpu.make_async_copy(  # noqa: E731
            src_ref.at[s], buf.at[i], sem)

        def start(i, _):
            copy(i, index_ref[r0 + i]).start()

        def wait(i, _):
            copy(i, 0).wait()

        jax.lax.fori_loop(0, n, start, None)
        if whole_wait:
            # a DMA semaphore counts bytes: a full tile's copies are waited
            # for as the one copy of the buffer's size they add up to
            pl.when(n == tile)(lambda: pltpu.make_async_copy(
                src_ref.at[pl.ds(0, tile)], buf, sem).wait())
            pl.when(n < tile)(lambda: jax.lax.fori_loop(0, n, wait, None))
        else:
            jax.lax.fori_loop(0, n, wait, None)
        held = lax.lt(lax.broadcasted_iota(jnp.int32, (tile, LANES), 0),
                      lax.broadcast_in_dim(n, (tile, LANES), ()))
        zeros = jnp.zeros((tile, LANES), jnp.float32)
        words, chunks = _word_view(buf), out_ref.shape[1] // LANES
        for s in range(_row_words(words, chunks)):
            for c, part in _columns(words, s, chunks):
                # rows past n hold what an earlier step left: selected away
                out_ref[:, c * LANES:(c + 1) * LANES] = (
                    lax.convert_element_type(lax.select(held, part, zeros),
                                             out_ref.dtype))


def _tokens_kernel(list_ref, starts_ref, src_ref, w_ref, out_ref, buf, sem,
                   *, tile: int, k: int):
    t = pl.program_id(0)
    first, last = starts_ref[t], starts_ref[t + 1]   # this tile's held slots
    slot_bits, token_bits = _bits(k), _bits(tile)
    copy = lambda j, i, s: pltpu.make_async_copy(  # noqa: E731
        src_ref.at[s], buf.at[j, i], sem)

    def start(e, _):
        entry = list_ref[e]
        copy(entry & ((1 << slot_bits) - 1),
             (entry >> slot_bits) & ((1 << token_bits) - 1),
             entry >> (slot_bits + token_bits)).start()

    def wait(e, _):
        copy(0, 0, 0).wait()

    jax.lax.fori_loop(first, last, start, None)
    jax.lax.fori_loop(first, last, wait, None)
    zeros = jnp.zeros((tile, LANES), jnp.float32)
    words, chunks = _word_view(buf), out_ref.shape[1] // LANES
    for s in range(_row_words(words, chunks)):
        acc = {}
        for j in range(k):
            # a slot's weight is 0 where it is not held: nothing landed
            # there, and what lies there is selected away. (Read again for
            # every column: the k weights broadcast once and kept, 64
            # vregs, cost 0.43 ms a call in spills: PR 32's chip runs.)
            w = lax.broadcast_in_dim(w_ref[:, j:j + 1], (tile, LANES), (0, 1))
            keep = lax.ne(w, zeros)
            for c, part in _columns(words, s, chunks, j):
                part = lax.select(keep, lax.mul(w, part), zeros)
                acc[c] = part if j == 0 else lax.add(acc[c], part)
        for c, total in acc.items():
            out_ref[:, c * LANES:(c + 1) * LANES] = lax.convert_element_type(
                total, out_ref.dtype)


def _bits(n: int) -> int:
    return max(1, (n - 1).bit_length())


def _tile(rows: int) -> int:
    """Rows a grid step holds: ``TILE``, or a short array rounded up to
    whole (16, 128) tiles (the last block of a grid may hang over the end)."""
    return min(TILE, -(-rows // 16) * 16)


def _params(interpret: bool):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",))}


# Jitted with static shapes, as the flash launchers are and for their
# reason: a model's layers, and the three forward passes a remat step makes
# of each, share one Mosaic lowering of each kernel and size.
@functools.partial(jax.jit, static_argnums=(3,))
def _rows_from_tokens(src, index, n_held, interpret: bool):
    slabs, rows = to_slabs(src), index.shape[0]
    tile = _tile(rows)
    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile,
                          whole_wait=src.shape[0] >= tile),
        out_shape=jax.ShapeDtypeStruct((rows, src.shape[1]), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(rows, tile),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, src.shape[1]),
                                   lambda i, index, n: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tile,) + slabs.shape[1:], src.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        **_params(interpret),
    )(index.astype(jnp.int32), n_held.reshape(1).astype(jnp.int32), slabs)


@functools.partial(jax.jit, static_argnums=(4,))
def _tokens_from_rows(src, lists, starts, w, interpret: bool):
    slabs, (N, k), D = to_slabs(src), w.shape, src.shape[1]
    tile = _tile(N)
    by_tile = lambda i, lists, starts: (i, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_tokens_kernel, tile=tile, k=k),
        out_shape=jax.ShapeDtypeStruct((N, D), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(N, tile),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tile, k), by_tile)],
            out_specs=pl.BlockSpec((tile, D), by_tile),
            scratch_shapes=[pltpu.VMEM((k, tile) + slabs.shape[1:], src.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        **_params(interpret),
    )(lists, starts, slabs, w)


def rows_from_tokens(src: jax.Array, index: jax.Array, n_held) -> jax.Array:
    """``out[r] = src[index[r]]`` for ``r < n_held``, bit for bit, and zero
    rows after: (len(index), D) in ``src``'s dtype. Only rows
    ``index[:n_held]`` of ``src`` are read."""
    return _rows_from_tokens(src, index, jnp.asarray(n_held),
                             jax.default_backend() != "tpu")


def token_lists(order: jax.Array, n_held, n_tokens: int, k: int):
    """What ``tokens_from_rows`` walks: the held slots in token order, so
    that a grid step visits its own and no other. ``order[r]`` is the slot
    (token * k + j) that row ``r`` of the buffer belongs to, for the
    ``n_held`` first rows. Returns (entries (len(order),) int32, row <<
    bits | token in its tile << bits | j; first entry of each tile and the
    end (tiles + 1,) int32). One sort of len(order) keys."""
    rows = order.shape[0]
    tile = _tile(n_tokens)
    slot_bits, token_bits = _bits(k), _bits(tile)
    if rows >= 1 << (31 - slot_bits - token_bits):
        raise ValueError(f"{rows} rows do not fit an entry's row bits")
    row = jnp.arange(rows, dtype=jnp.int32)
    slot = jnp.where(row < n_held, order.astype(jnp.int32), n_tokens * k)
    slot, row = jax.lax.sort((slot, row), num_keys=1)
    firsts = jnp.arange(pl.cdiv(n_tokens, tile) + 1, dtype=jnp.int32) * (tile * k)
    starts = jnp.sum(slot[None, :] < firsts[:, None], axis=1, dtype=jnp.int32)
    token, j = slot // k, slot % k
    return ((row << (slot_bits + token_bits)) | ((token % tile) << slot_bits)
            | j), starts


def tokens_from_rows(src: jax.Array, lists, w: jax.Array) -> jax.Array:
    """``out[n] = sum_j w[n, j] * src[row of slot (n, j)]`` over the slots
    ``token_lists`` lists, summed in float32 and rounded once to ``src``'s
    dtype: (N, D). ``w`` (N, k) float32 is 0 at every slot not listed (what
    lies in the kernel's buffer there is selected away by it); a plain sum
    passes ones at the listed. Only the listed rows of ``src`` are read."""
    return _tokens_from_rows(src, *lists, w.astype(jnp.float32),
                             jax.default_backend() != "tpu")
