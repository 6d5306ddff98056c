"""Pallas flash attention (TPU): fused QK^T -> online softmax -> V.

The hot op of the transformer stack (FedNLP/Cheetah planes). K-blocked: the
forward's grid is (batch, head group, q-block, k-block) with the k dimension
innermost, so Mosaic's pipeline streams (block_k, W) K/V tiles through VMEM
while the online-softmax state (running max, normalizer, output accumulator)
lives in VMEM scratch across the k steps. The (T, T) score matrix never
exists in HBM, in any of the three passes: memory is O(T * Dh).

Layout. q/k/v arrive (B, T, H, Dh) and are indexed as they lie, as
(B, T, H*Dh): a grid step holds the fewest heads whose widths fill whole
128-lane tiles (two at Dh=64, one at Dh=128), so no transpose to a
head-major layout runs before or after the kernel and every load, store and
accumulator is lane-dense. Heads that share a tile are kept apart by zeroing
the other heads' lanes in one MXU operand: the contraction over the tile's W
lanes then sees one head, at the cost of a (block, W) select where the
scores are (block_q, block_k).

Precision. The MXU is fed the dtype the caller gave (bf16 operands contract
in bf16, float32 in float32) and accumulates float32; scores, running max,
normalizer, ``lse``, ``delta`` and every accumulator are float32; the
probabilities and ``ds`` are cast to the operand dtype for their second
contraction, as the dense path casts ``probs``. 1/sqrt(Dh) is folded into q
(forward, dq) or k (dk/dv) once a block, rounded to the operand dtype: exact
at Dh = 64, where it is 1/8.

Causal masking skips key blocks above the diagonal (``pl.when``; their
index maps repeat the last block that is needed, so nothing is fetched for
them) and runs the blocks under it unmasked (``_causal_cases``, the rule
for all three kernels). A square block on the diagonal at least two
STRIPEs tall runs as row stripes (``_stripes``): each STRIPE query rows
against the prefix of keys they can see, a lane-dense rectangle of whole
128-lane tiles, only its last STRIPE columns masked, with one max, one sum
and one state update per row, as a square block has; the masked half is
no longer formed (it gave exp(-inf) = 0). Every stripe's score products
are made first, so the MXU runs them back to back while the vector units
take each stripe's softmax. Any other block that straddles the diagonal
(unequal or one-stripe blocks) runs as one square, the row >= col mask
over all of it.

A sliding window (``window``, a static int: query i sees key j where
``0 <= i - j < window``, a sliding-window layer's band) is the same rule
on both sides of the band (``_band_cases``): a key block wholly behind it,
``q_start - (k_start + block_k - 1) >= window``, is skipped like a block
above the diagonal, and its index map clamps to the first block that is
needed as the causal map clamps to the last, so nothing is fetched for it;
a block wholly inside runs unmasked; the diagonal keeps its stripes; a
block that straddles the band's lower edge runs as one square masked by
``i - j < window`` (the mirror of the diagonal: striping it is left for
later). The blocks are capped so that two fit the band (``block_q +
block_k <= window``): a pair then crosses one of its edges at most, and a
band under two blocks of MIN_BLOCK is refused (the dispatch rule sends it
to dense). A window of T or more is the causal call. ``window`` None
traces exactly the causal kernels. Each windowed launcher opens the scope
``attn.window`` itself,
forward and backward (a custom VJP's backward inherits no scope), and
``fedml_flash_window_total{pass, seq_len, window}`` counts the band a call
runs, once per call site per trace.

Gradients: custom VJP, probabilities recomputed blockwise from the saved
per-row logsumexp (FlashAttention-2). One kernel on a (batch, head group,
k-block, q-block) grid makes all three: dk/dv accumulate over the inner q
blocks, dq in a float32 (T, W) scratch that stays in VMEM for the whole head
group (five contractions a block pair). That scratch and the whole-sequence
dq output grow with T, W and the dtype (``_bwd_vmem``). Where they pass
Mosaic's default scoped VMEM (past T = 8192 at W = 128 in bf16, T = 4096 in
float32 or at W = 256) the kernel asks for its count and ``_VMEM_MARGIN``,
up to ``_VMEM_CAP`` of the v5e's 128 MiB; a shorter call asks for nothing
of its own and runs under the default. The SmallThinker cell's layers
(T = 16384, W = 128, bf16, windowed or global) take it so, and
``chip_smoke.py`` runs it against dense on the chip at T = 12288 and, over
a band, at the cell's length. Past the cap (T of about 109k at W = 128 in
bf16, 71k in float32, 53k at W = 256) dq has its own kernel on the
forward's grid (seven contractions): that split pair's tiles fit the
default at any T, and it is the only backward such lengths have.
``fedml_flash_backward_total{impl, seq_len}`` counts which backward a call
runs, once per call site per trace.

What PR 27's sweeps on the v5e found, all of it for the causal kernels
(``window`` None), whose bodies stay as they were (PERF.md section 6 has the
tables):
the kernels are bound by vector loads, stores and lane shuffles, not by the
MXU (float32 operands ran as fast as bf16 ones), so the softmax state is
kept lane-replicated, which took a third off the forward; 1024 squares are
the fastest forward blocks and 512 squares the fastest backward ones at
T = 1024, 2048 and 4096; the mask on every block costs 0-3%. Static
triangular tiles on the diagonal block ran slower then (each tile paid an
online-softmax update, on the kernel whose state was still ``(rows, 1)``
columns). PR 39's stripes pay one update a row, as the square did; what
they save is the masked half's products and result pops on the MXU (at
T = 1024 Mosaic had already folded the static mask out of the vector work)
and, with the products issued first, the wait of each stripe's softmax on
its own products. On non-TPU backends the kernels run in interpret mode so
tests validate numerics everywhere.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.telemetry import get_registry

MIN_BLOCK = 128
# the query rows a stripe of a diagonal block holds (``_causal_cases``)
STRIPE = 128
NEG_INF = float(jnp.finfo(jnp.float32).min)
# the largest square block each pass takes by default: the fastest of
# {128, 256, 512, 1024} squares and rectangles at T = 1024, 2048, 4096 on the
# v5e, (B, H, Dh) = (8, 16, 64) bf16 causal (PR 27's sweeps)
FWD_BLOCK, BWD_BLOCK = 1024, 512
# Mosaic's default scoped-VMEM limit on the v5e: what one kernel instance's
# buffers and spilled temporaries have to fit (``_fwd_vmem``, ``_bwd_vmem``)
_VMEM_LIMIT = 16 * 2**20
# the largest count for which a kernel asks for more than that default
# (``vmem_limit_bytes``), of the v5e's 128 MiB of VMEM; a part with less
# refuses at compile time
_VMEM_CAP = 112 * 2**20
# asked for above the fused backward's count where that passes _VMEM_LIMIT:
# Mosaic's own allocation outgrew the count by up to 1.2 MiB in ahead-of-time
# compiles for the v5e (PERF.md section 6)
_VMEM_MARGIN = 2 * 2**20


def auto_block(T: int, largest: int = FWD_BLOCK,
               fits=lambda block: True) -> int | None:
    """Largest power-of-two block in [128, ``largest``] dividing T (every
    candidate is a multiple of 128, as Mosaic's lane dimension requires)
    that ``fits``. None if no candidate does."""
    for b in (1024, 512, 256, MIN_BLOCK):
        if b <= min(largest, T) and T % b == 0 and fits(b):
            return b
    return None


def heads_per_step(H: int, Dh: int) -> int:
    """Heads one grid step holds: the fewest whose widths fill whole
    128-lane tiles, else all of them (a block as wide as the array)."""
    for hp in range(1, H):
        if H % hp == 0 and (hp * Dh) % 128 == 0:
            return hp
    return H


def _only_head(x, h: int, hp: int, Dh: int):
    """x with the lanes of every head but h zeroed."""
    if hp == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * Dh) & (lane < (h + 1) * Dh), x,
                     jnp.zeros_like(x))


def _by_head(parts, Dh: int):
    """One tile whose lanes [h*Dh, (h+1)*Dh) are parts[h]'s."""
    out = parts[-1]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (h + 1) * Dh, parts[h], out)
    return out


def _lanes(x, W: int):
    """A lane-replicated (rows, 128) statistic at a tile's width."""
    tiles = -(-W // 128)
    if tiles > 1:
        x = pltpu.repeat(x, tiles, 1)
    return x if W == tiles * 128 else x[:, :W]


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a.T @ b


def _stripes(causal: bool, block_q: int, block_k: int) -> bool:
    """Whether a block pair that straddles the diagonal runs as row stripes:
    a causal square, at least two stripes tall (then the pair that straddles
    is the one on the diagonal, ``q_start == k_start``)."""
    return causal and block_q == block_k and block_q >= 2 * STRIPE


def _causal_cases(run, causal, q_start, block_q, k_start, block_k,
                  window=None):
    """Run this block pair as ``run(parts)``, each part (rows, keys, mask):
    ``rows`` a slice of the q block, ``keys`` of the k block, ``mask`` None
    or what masks their scores. Not at all above the diagonal, one unmasked
    part under it. A pair that straddles it is one masked part, or, where
    ``_stripes``, a part for each stripe of STRIPE query rows over the
    prefix of keys it can see, only its last STRIPE columns masked. With a
    ``window``: ``_band_cases``."""
    whole = slice(None)
    if not causal:
        run([(whole, whole, None)])
        return
    if window is not None:
        _band_cases(run, q_start, block_q, k_start, block_k, window)
        return
    under = k_start + block_k - 1 <= q_start
    pl.when(under)(lambda: run([(whole, whole, None)]))
    straddles = jnp.logical_and(jnp.logical_not(under),
                                k_start <= q_start + block_q - 1)
    _diagonal(run, straddles, q_start, block_q, k_start, block_k)


def _diagonal(run, straddles, q_start, block_q, k_start, block_k):
    """A pair that straddles the diagonal, where ``straddles``: one square
    masked part, or the stripes."""
    whole = slice(None)
    if not _stripes(True, block_q, block_k):
        pl.when(straddles)(lambda: run([(whole, whole, lambda s: jnp.where(
            _keep(s.shape, q_start, k_start), s, NEG_INF))]))
        return
    pl.when(straddles)(lambda: run([
        (slice(lo, lo + STRIPE), slice(0, lo + STRIPE), _mask_last)
        for lo in range(0, block_q, STRIPE)]))


def _band_cases(run, q_start, block_q, k_start, block_k, window):
    """The causal band ``0 <= i - j < window``: a pair wholly outside it
    (above the diagonal, or every key ``window`` or more behind every query
    of the block) is not run; one wholly inside runs unmasked; the diagonal
    as ``_diagonal``; one that straddles the band's lower edge runs masked
    by ``i - j < window``. Two blocks fit the band (``_resolve_blocks``), so
    no pair crosses both edges."""
    whole = slice(None)
    least = q_start - (k_start + block_k - 1)   # the pair's least i - j
    most = q_start + block_q - 1 - k_start      # and its greatest
    inside = jnp.logical_and(least >= 0, most < window)
    pl.when(inside)(lambda: run([(whole, whole, None)]))
    edge = jnp.logical_and(
        jnp.logical_and(most >= 0, least < window), jnp.logical_not(inside))
    # (a diagonal pair's greatest i - j is under block_q + block_k - 2, so
    # here it never reaches the lower edge, nor a lower-edge pair the diagonal)
    _diagonal(run, jnp.logical_and(edge, least < 0), q_start, block_q,
              k_start, block_k)
    pl.when(jnp.logical_and(edge, least >= 0))(lambda: run([
        (whole, whole, lambda s: jnp.where(
            _near(s.shape, q_start, k_start, window), s, NEG_INF))]))


def _mask_last(s):
    """s (STRIPE, n) with the causal mask on its last STRIPE columns, the
    diagonal square a stripe ends with; the columns before it all stay."""
    tail = jnp.where(_keep((STRIPE, STRIPE), 0, 0), s[:, -STRIPE:], NEG_INF)
    if s.shape[1] == STRIPE:
        return tail
    return jnp.concatenate([s[:, :-STRIPE], tail], axis=1)


def _keep(shape, q_start, k_start):
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows >= cols


def _near(shape, q_start, k_start, window):
    """The scores whose key is fewer than ``window`` positions behind."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows - cols < window


def _scores(a, b, mask):
    s = _dot(a, b, _NT)
    return s if mask is None else mask(s)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  q_scr, m_scr, l_scr, acc_scr, *, hp: int, Dh: int,
                  block_k: int, causal: bool, scale: float, window=None):
    """Grid (B, H//hp, T//block_q, T//block_k), k innermost. Refs:
    q/o (1, block_q, W), k/v (1, block_k, W), lse (1, 1, hp, block_q).
    Scratch: q_scr (hp, block_q, W) the scaled q with one head's lanes
    each; m/l (hp, block_q, 128), every lane of a row the same value, so
    the state's updates are whole-vreg ops with no lane broadcast, and acc
    (block_q, W), float32 — the softmax state persists across the k steps;
    o/lse are written once on the last step (their block index is
    k-invariant)."""
    block_q, W = q_ref.shape[1], q_ref.shape[2]
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        q = (q_ref[0].astype(jnp.float32) * scale).astype(q_scr.dtype)
        for h in range(hp):
            q_scr[h] = _only_head(q, h, hp, Dh)
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def run(parts):
        # several parts (the stripes): every score product first, so that
        # the MXU runs them back to back while the vector units take each
        # stripe's softmax; in program order each softmax waited on its own
        ahead = [[_scores(q_scr[h, rows], k_ref[0, keys], mask)
                  for h in range(hp)]
                 for rows, keys, mask in parts] if len(parts) > 1 else None
        for i, (rows, keys, mask) in enumerate(parts):
            v_blk = v_ref[0, keys]
            corrs, pvs = [], []
            for h in range(hp):
                s = (ahead[i][h] if ahead else
                     _scores(q_scr[h, rows], k_ref[0, keys], mask))
                m = m_scr[h, rows]                       # lane-replicated
                new_m = jnp.maximum(m, jnp.max(s, axis=1)[:, None])
                p = jnp.exp(s - pltpu.repeat(new_m, s.shape[1] // 128, 1))
                corr = jnp.exp(m - new_m)
                l_scr[h, rows] = (l_scr[h, rows] * corr
                                  + jnp.sum(p, axis=1)[:, None])
                m_scr[h, rows] = new_m
                pvs.append(_dot(p.astype(v_blk.dtype), v_blk, _NN))
                corrs.append(_lanes(corr, W))
            acc_scr[rows] = (acc_scr[rows] * _by_head(corrs, Dh)
                             + _by_head(pvs, Dh))

    _causal_cases(run, causal, q_start, block_q, k_start, block_k, window)

    @pl.when(ki == nk - 1)
    def _finalize():
        ls = [jnp.maximum(l_scr[h], 1e-30) for h in range(hp)]
        norm = _by_head([_lanes(l, W) for l in ls], Dh)
        o_ref[0] = (acc_scr[...] / norm).astype(o_ref.dtype)
        for h in range(hp):
            lse_ref[0, 0, h] = (m_scr[h] + jnp.log(ls[h]))[:, 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               q_scr, do_scr, dq_scr, *, hp: int, Dh: int, block_k: int,
               causal: bool, scale: float, window=None):
    """The forward's grid: one q block accumulates dq over the streamed key
    blocks; p recomputed from (q, k, lse). Only where the fused backward's
    whole-sequence dq does not fit VMEM (``_bwd_vmem``)."""
    block_q = q_ref.shape[1]
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        q = (q_ref[0].astype(jnp.float32) * scale).astype(q_scr.dtype)
        for h in range(hp):
            q_scr[h] = _only_head(q, h, hp, Dh)
            do_scr[h] = _only_head(do_ref[0], h, hp, Dh)
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def run(parts):
        # the stripes' two score-shaped products first (see _flash_kernel)
        ahead = [[(_scores(q_scr[h, rows], k_ref[0, keys], mask),
                   _dot(do_scr[h, rows], v_ref[0, keys], _NT))
                  for h in range(hp)]
                 for rows, keys, mask in parts] if len(parts) > 1 else None
        for i, (rows, keys, mask) in enumerate(parts):
            k_blk, v_blk = k_ref[0, keys], v_ref[0, keys]
            dqs = []
            for h in range(hp):
                s = (ahead[i][h][0] if ahead else
                     _scores(q_scr[h, rows], k_blk, mask))
                p = jnp.exp(s - lse_ref[0, 0, h, rows][:, None])
                dp = (ahead[i][h][1] if ahead else
                      _dot(do_scr[h, rows], v_blk, _NT))
                ds = p * (dp - delta_ref[0, 0, h, rows][:, None])
                dqs.append(_dot(ds.astype(k_blk.dtype), k_blk, _NN))
            dq_scr[rows] = dq_scr[rows] + _by_head(dqs, Dh)

    _causal_cases(run, causal, q_start, block_q, k_start, block_k, window)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                hp: int, Dh: int, block_q: int, causal: bool, scale: float,
                fused: bool, window=None):
    """Grid (B, H//hp, T//block_k, T//block_q), q innermost: one key block
    accumulates dk/dv over the streamed query blocks. k_scr holds the scaled
    k with one head's lanes each, so ``ds @ k_scr[h]`` is that head's dq
    with its scale, in its own lanes: ``fused`` adds it into a (T, W)
    scratch that outlives the key blocks and is written out at the end."""
    if fused:
        dk_ref, dv_ref, dq_ref, k_scr, v_scr, dk_scr, dv_scr, dq_scr = refs
    else:
        dk_ref, dv_ref, k_scr, v_scr, dk_scr, dv_scr = refs
    block_k = k_ref.shape[1]
    ki, qi = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    k_start = ki * block_k
    q_start = qi * block_q

    @pl.when(qi == 0)
    def _init():
        k = (k_ref[0].astype(jnp.float32) * scale).astype(k_scr.dtype)
        for h in range(hp):
            k_scr[h] = _only_head(k, h, hp, Dh)
            v_scr[h] = _only_head(v_ref[0], h, hp, Dh)
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    if fused:
        @pl.when(jnp.logical_and(ki == 0, qi == 0))
        def _init_dq():
            dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def run(parts):
        # the stripes' two score-shaped products first (see _flash_kernel)
        ahead = [[(_scores(q_ref[0, rows], k_scr[h, keys], mask),
                   _dot(do_ref[0, rows], v_scr[h, keys], _NT))
                  for h in range(hp)]
                 for rows, keys, mask in parts] if len(parts) > 1 else None
        for i, (rows, keys, mask) in enumerate(parts):
            q, do = q_ref[0, rows], do_ref[0, rows]
            dks, dvs, dq = [], [], None
            for h in range(hp):
                s = (ahead[i][h][0] if ahead else
                     _scores(q, k_scr[h, keys], mask))   # (rows, keys)
                p = jnp.exp(s - lse_ref[0, 0, h, rows][:, None])
                dp = (ahead[i][h][1] if ahead else
                      _dot(do, v_scr[h, keys], _NT))
                ds = (p * (dp - delta_ref[0, 0, h, rows][:, None])
                      ).astype(q.dtype)
                dvs.append(_dot(p.astype(do.dtype), do, _TN))
                dks.append(_dot(ds, q, _TN))
                if fused:
                    part = _dot(ds, k_scr[h, keys], _NN)
                    dq = part if dq is None else dq + part
            dv_scr[keys] = dv_scr[keys] + _by_head(dvs, Dh)
            dk_scr[keys] = dk_scr[keys] + _by_head(dks, Dh)
            if fused:
                lo, hi, _ = rows.indices(block_q)
                at = pl.ds(pl.multiple_of(q_start + lo, MIN_BLOCK), hi - lo)
                dq_scr[at, :] = dq_scr[at, :] + dq

    _causal_cases(run, causal, q_start, block_q, k_start, block_k, window)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    if fused:
        @pl.when(jnp.logical_and(ki == nk - 1, qi == nq - 1))
        def _finalize_dq():
            dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _specs(block_q: int, block_k: int, W: int, hp: int, causal: bool,
           q_inner: bool, window=None):
    """BlockSpecs over the (B, T, G*W) arrays and the (B, G, hp, T) rows,
    for a grid (b, g, i, j) whose inner axis j walks key blocks, or query
    blocks when ``q_inner``. A causal inner index repeats the nearest
    block that is needed where the step is skipped: no fetch for it; with a
    ``window`` on both sides of the band."""
    if q_inner:
        def qk(i, j):
            first = (i * block_k) // block_q  # first q block at or past k's
            j = jnp.maximum(j, first) if causal else j
            if window is not None:  # the last q block that sees k's
                j = jnp.minimum(
                    j, (i * block_k + block_k + window - 2) // block_q)
            return j, i
    else:
        def qk(i, j):
            last = (i * block_q + block_q - 1) // block_k
            j = jnp.minimum(j, last) if causal else j
            if window is not None:  # the first key block in i's band
                j = jnp.maximum(
                    j, jnp.maximum(i * block_q - window + 1, 0) // block_k)
            return i, j

    q_tile = pl.BlockSpec(
        (1, block_q, W), lambda b, g, i, j: (b, qk(i, j)[0], g))
    k_tile = pl.BlockSpec(
        (1, block_k, W), lambda b, g, i, j: (b, qk(i, j)[1], g))
    q_rows = pl.BlockSpec(
        (1, 1, hp, block_q), lambda b, g, i, j: (b, g, 0, qk(i, j)[0]))
    return q_tile, k_tile, q_rows


def _params(interpret: bool, carried_over_blocks: bool = False,
            vmem_limit: int | None = None):
    """The inner grid axis always carries scratch state; the fused backward's
    dq scratch also outlives the key blocks of axis 2, which a chip with two
    TensorCores must therefore not split between them. ``vmem_limit``: the
    scoped VMEM asked for, None for Mosaic's default."""
    if interpret:
        return {"interpret": True}
    blocks = "arbitrary" if carried_over_blocks else "parallel"
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", blocks, "arbitrary"),
        vmem_limit_bytes=vmem_limit)}


def _fwd_vmem(block_q: int, block_k: int, W: int, hp: int, itemsize: int) -> int:
    """Scoped VMEM of one forward instance, in bytes: the double-buffered
    q/o and k/v tiles and lse rows, the scratch, and what Mosaic spills (one
    head's float32 scores; every head's p @ v and correction until
    ``_by_head`` joins them). Fitted to ahead-of-time compiles for the v5e
    (PERF.md section 6, PR 27): it refuses every shape Mosaic refused."""
    piped = 2 * (2 * block_q + 2 * block_k) * W * itemsize + 2 * 8 * block_q * 4
    scratch = hp * block_q * (W * itemsize + 2 * 128 * 4) + block_q * W * 4
    spilled = block_q * block_k * 4 + 2 * hp * block_q * W * 4
    return piped + scratch + spilled


def _bwd_vmem(T: int, block_q: int, block_k: int, W: int, hp: int,
              itemsize: int, fused: bool) -> int:
    """Scoped VMEM of one dk/dv instance, in bytes, as ``_fwd_vmem`` counts:
    q/dO/k/v tiles in, dk/dv tiles out, lse/delta rows, the scratch, and the
    spilled p and dp (float32) and ds (operand dtype). ``fused`` adds dq for
    the whole sequence: the float32 scratch and the double-buffered output,
    their rows padded to whole 128-lane tiles, as VMEM holds them. The dq
    kernel of the split pair needs less at square blocks."""
    piped = (2 * (2 * block_q + 2 * block_k) + 4 * block_k) * W * itemsize \
        + 4 * 8 * block_q * 4
    scratch = 2 * block_k * W * (hp * itemsize + 4)
    spilled = block_q * block_k * (8 + itemsize)
    whole_dq = T * -(-W // 128) * 128 * (4 + 2 * itemsize) if fused else 0
    return piped + scratch + spilled + whole_dq


def _fused_bwd_vmem(q, block_q: int, block_k: int) -> int:
    """``_bwd_vmem`` of the fused backward at q's (B, T, H, Dh) and dtype."""
    _, T, H, Dh = q.shape
    hp = heads_per_step(H, Dh)
    return _bwd_vmem(T, block_q, block_k, hp * Dh, hp, q.dtype.itemsize,
                     fused=True)


def _fused_bwd_plan(q, block_q: int, block_k: int):
    """(fused, vmem_limit) of the backward at q's shape and dtype: the fused
    kernel where its count is at most ``_VMEM_CAP``, asking for that count
    and ``_VMEM_MARGIN`` where it passes Mosaic's default, for nothing of
    its own (None) where it does not."""
    need = _fused_bwd_vmem(q, block_q, block_k)
    raised = _VMEM_LIMIT < need <= _VMEM_CAP
    return need <= _VMEM_CAP, need + _VMEM_MARGIN if raised else None


def _window_scope(window):
    """``attn.window`` around a windowed launcher's body, nothing around
    another: a custom VJP's backward inherits no scope from its caller, so
    the launchers name their own, both ways."""
    if window is None:
        return contextlib.nullcontext()
    return jax.named_scope("attn.window")


# The launchers are jitted so that a model's layers share one trace and one
# Mosaic lowering of each kernel: lowering a pallas_call is Python-side work
# that no compilation cache skips, and 96 of them added 21 s to every warm
# start of the 24-layer LM step (my chip runs, PR 27: 66 s against 45 s).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    block_q: int, block_k: int, interpret: bool, window=None,
):
    """q/k/v: (B, T, H, Dh) -> (out (B, T, H, Dh), lse (B, H//hp, hp, T)
    f32: the head group's rows are a block's last two dims, equal to the
    array's, which is Mosaic's rule for blocks under (8, 128))."""
    with _window_scope(window):
        B, T, H, Dh = q.shape
        hp = heads_per_step(H, Dh)
        G, W = H // hp, hp * Dh
        q_tile, k_tile, q_rows = _specs(block_q, block_k, W, hp, causal,
                                        False, window)
        out, lse = pl.pallas_call(
            functools.partial(_flash_kernel, hp=hp, Dh=Dh, block_k=block_k,
                              causal=causal, scale=1.0 / (Dh ** 0.5),
                              window=window),
            out_shape=(
                jax.ShapeDtypeStruct((B, T, H * Dh), q.dtype),
                jax.ShapeDtypeStruct((B, G, hp, T), jnp.float32),
            ),
            grid=(B, G, T // block_q, T // block_k),
            in_specs=[q_tile, k_tile, k_tile],
            out_specs=(q_tile, q_rows),
            scratch_shapes=[
                pltpu.VMEM((hp, block_q, W), q.dtype),
                pltpu.VMEM((hp, block_q, 128), jnp.float32),
                pltpu.VMEM((hp, block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, W), jnp.float32),
            ],
            **_params(interpret),
        )(*(t.reshape(B, T, H * Dh) for t in (q, k, v)))
        return out.reshape(B, T, H, Dh), lse


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k, interpret,
                    window=None):
    """Blockwise dq/dk/dv; q/k/v/out/g (B, T, H, Dh), lse (B, H//hp, hp, T)."""
    with _window_scope(window):
        B, T, H, Dh = q.shape
        hp = heads_per_step(H, Dh)
        G, W = H // hp, hp * Dh
        scale = 1.0 / (Dh ** 0.5)
        # delta_i = sum_d dO_id * O_id — O(T*Dh), plain XLA (one fused pass)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        # in lse's layout
        delta = delta.reshape(B, T, G, hp).transpose(0, 2, 3, 1)
        flat = (B, T, H * Dh)
        args = (*(t.reshape(flat) for t in (q, k, v, g)), lse, delta)
        fused, vmem_limit = _fused_bwd_plan(q, block_q, block_k)
        like = lambda t: jax.ShapeDtypeStruct(flat, t.dtype)  # noqa: E731

        q_tile, k_tile, q_rows = _specs(block_q, block_k, W, hp, causal, True,
                                        window)
        dq_whole = pl.BlockSpec((1, T, W), lambda b, g, i, j: (b, 0, g))
        grads = pl.pallas_call(
            functools.partial(_dkv_kernel, hp=hp, Dh=Dh, block_q=block_q,
                              causal=causal, scale=scale, fused=fused,
                              window=window),
            out_shape=(like(k), like(v)) + ((like(q),) if fused else ()),
            grid=(B, G, T // block_k, T // block_q),
            in_specs=[q_tile, k_tile, k_tile, q_tile, q_rows, q_rows],
            out_specs=(k_tile, k_tile) + ((dq_whole,) if fused else ()),
            scratch_shapes=[
                pltpu.VMEM((hp, block_k, W), k.dtype),
                pltpu.VMEM((hp, block_k, W), v.dtype),
                pltpu.VMEM((block_k, W), jnp.float32),
                pltpu.VMEM((block_k, W), jnp.float32),
            ] + ([pltpu.VMEM((T, W), jnp.float32)] if fused else []),
            **_params(interpret, carried_over_blocks=fused,
                      vmem_limit=vmem_limit),
        )(*args)
        if fused:
            dk, dv, dq = grads
        else:
            dk, dv = grads
            q_tile, k_tile, q_rows = _specs(block_q, block_k, W, hp, causal,
                                            False, window)
            dq = pl.pallas_call(
                functools.partial(_dq_kernel, hp=hp, Dh=Dh, block_k=block_k,
                                  causal=causal, scale=scale, window=window),
                out_shape=like(q),
                grid=(B, G, T // block_q, T // block_k),
                in_specs=[q_tile, k_tile, k_tile, q_tile, q_rows, q_rows],
                out_specs=q_tile,
                scratch_shapes=[
                    pltpu.VMEM((hp, block_q, W), q.dtype),
                    pltpu.VMEM((hp, block_q, W), g.dtype),
                    pltpu.VMEM((block_q, W), jnp.float32),
                ],
                **_params(interpret),
            )(*args)
        return tuple(t.reshape(B, T, H, Dh) for t in (dq, dk, dv))


def _auto_blocks(T: int, H: int, Dh: int, itemsize: int):
    """(forward, backward) square blocks for a problem: the largest under
    each pass's measured cap whose working set fits scoped VMEM."""
    hp = heads_per_step(H, Dh)
    return (
        auto_block(T, FWD_BLOCK, lambda b: _fwd_vmem(
            b, b, hp * Dh, hp, itemsize) <= _VMEM_LIMIT),
        auto_block(T, BWD_BLOCK, lambda b: _bwd_vmem(
            T, b, b, hp * Dh, hp, itemsize, fused=False) <= _VMEM_LIMIT))


def _resolve_blocks(q, block_q, block_k, backward: bool, window=None):
    """A pass's (block_q, block_k): the explicit ones, else the auto block;
    with a ``window``, no larger than half of it (``_band_cases``)."""
    _, T, H, Dh = q.shape
    auto = _auto_blocks(T, H, Dh, q.dtype.itemsize)[backward]
    if window is not None and auto is not None:
        auto = auto_block(T, min(auto, window // 2))
    bq = block_q or auto
    bk = block_k or auto
    if window is not None and (bq is None or bk is None or bq + bk > window):
        raise ValueError(
            f"flash_attention: a band of {window} keys holds no two blocks of "
            f"at least {MIN_BLOCK} (got {bq}, {bk}): take the dense path")
    if bq is None or bk is None or T % bq or T % bk:
        raise ValueError(
            f"flash_attention: T={T} has no block tiling that fits VMEM "
            "(callers should gate on flash_shapes_ok and fall back to dense)")
    return bq, bk


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
) -> jax.Array:
    """Flash attention with K-blocked pallas forward AND backward.
    q/k/v (B, T, H, Dh); block sizes default to auto_block's tiling for T
    under each pass's measured cap (FWD_BLOCK, BWD_BLOCK), and explicit ones
    hold for both passes; requires T % block == 0 (callers fall back to
    dense otherwise). ``window`` (causal only): query i sees key j where
    ``0 <= i - j < window``; one of T or more is the plain causal call."""
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: a window is a causal band")
        if window >= q.shape[1]:
            window = None
    # counted here, where a call site is traced once: under remat jax traces
    # the custom VJP's primal and its forward rule both
    blocks = _resolve_blocks(q, block_q, block_k, False, window)
    _count_diagonal("fwd", q, causal, *blocks)
    _count_window("fwd", q, window)
    return _flash_attention(q, k, v, causal, block_q, block_k, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, window):
    return _fwd(q, k, v, causal, block_q, block_k, window)[0]


def _count_diagonal(pass_: str, q, causal, block_q, block_k) -> None:
    """``fedml_flash_diagonal_total{pass, impl, seq_len}``: how a causal
    call's diagonal blocks run, ``striped`` or ``square``, where the blocks
    are resolved: once per call site per trace, as
    ``fedml_attention_dispatch_total`` is counted, nothing at run time."""
    if causal and isinstance(q, jax.core.Tracer):
        impl = "striped" if _stripes(causal, block_q, block_k) else "square"
        get_registry().counter("fedml_flash_diagonal_total", impl=impl,
                               seq_len=q.shape[1], **{"pass": pass_}).inc()


def _count_window(pass_: str, q, window) -> None:
    """``fedml_flash_window_total{pass, seq_len, window}``: the band a call
    runs (``none``: the whole causal triangle, or no mask), counted as
    ``_count_diagonal`` counts."""
    if isinstance(q, jax.core.Tracer):
        get_registry().counter(
            "fedml_flash_window_total", seq_len=q.shape[1],
            window="none" if window is None else window,
            **{"pass": pass_}).inc()


def _count_backward(q, block_q, block_k) -> None:
    """``fedml_flash_backward_total{impl, seq_len}``: which backward a call
    runs, ``fused`` (one kernel) or ``split`` (the dk/dv and dq pair),
    counted as ``_count_diagonal`` counts."""
    if isinstance(q, jax.core.Tracer):
        impl = "fused" if _fused_bwd_plan(q, block_q, block_k)[0] else "split"
        get_registry().counter("fedml_flash_backward_total", impl=impl,
                               seq_len=q.shape[1]).inc()


def _fwd(q, k, v, causal, block_q, block_k, window):
    interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(q, block_q, block_k, False, window)
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                              window)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(q, block_q, block_k, True, window)
    _count_diagonal("bwd", q, causal, block_q, block_k)
    _count_window("bwd", q, window)
    _count_backward(q, block_q, block_k)
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret, window)


_flash_attention.defvjp(_fwd, _bwd)


def flash_vmem_ok(T: int, Dh: int, itemsize: int = 2,
                  block: int | None = None, heads: int | None = None) -> bool:
    """Whether the forward and the split backward fit scoped VMEM: at
    ``block`` squares if given, else at some auto block of each pass. Their
    working set is O(block * W), independent of T; the fused backward, which
    is not, is taken only up to ``_VMEM_CAP`` (``_fused_bwd_plan``). Refuses a
    huge Dh, oversized explicit blocks, and a head count whose lane tile is
    the whole H * Dh and wide (an odd H at Dh = 64; without ``heads`` the
    tile is taken to be the fewest whole lane tiles)."""
    H = heads or max(1, 128 // Dh)
    if block is None:
        return None not in _auto_blocks(T, H, Dh, itemsize)
    hp = heads_per_step(H, Dh)
    return (_fwd_vmem(block, block, hp * Dh, hp, itemsize) <= _VMEM_LIMIT
            and _bwd_vmem(T, block, block, hp * Dh, hp, itemsize,
                          fused=False) <= _VMEM_LIMIT)


def flash_shapes_ok(T: int, Dh: int, block_q: int | None = None,
                    block_k: int | None = None,
                    itemsize: int = 2, heads: int | None = None) -> bool:
    """Static dispatch guard used by ops.attention.multihead_attention: the
    sequence must tile into whole blocks, Dh must fill lanes reasonably,
    and the requested (or auto) blocks must fit scoped VMEM; T itself is
    unbounded on a single chip (HBM is the ceiling)."""
    bq = block_q or auto_block(T)
    bk = block_k or auto_block(T)
    explicit = max(bq or 0, bk or 0) if (block_q or block_k) else None
    return (bq is not None and bk is not None
            and T % bq == 0 and T % bk == 0
            and bq % MIN_BLOCK == 0 and bk % MIN_BLOCK == 0
            and (Dh % 128 == 0 or Dh == 64)
            and flash_vmem_ok(T, Dh, itemsize, block=explicit, heads=heads))
