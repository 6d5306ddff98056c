"""Pallas flash attention (TPU): fused QK^T -> online softmax -> V.

The hot op of the transformer stack (FedNLP/Cheetah planes). K-blocked 3-D
grid design (round-3 rewrite): the grid is (batch*head, q-block, k-block)
with the k dimension innermost, so Mosaic's pipeline streams (block_k, Dh)
K/V tiles through VMEM while the online-softmax state (running max,
normalizer, output accumulator) lives in VMEM scratch across the k steps.
Nothing stages the full sequence: VMEM use is O(block_q * Dh + block_k * Dh)
regardless of T — single-chip T is bounded by HBM, not the ~16 MB VMEM
budget that capped the round-2 full-K/V kernel at T~12k. The (T, T) score
matrix never exists in HBM — memory O(T * Dh) — and every matmul is a
(block_q x Dh) x (Dh x block_k) MXU tile.

Causal masking skips fully-masked key blocks via ``pl.when`` (the grid step
still runs but does no FLOPs and no accumulation), and the diagonal block
applies the row>=col mask.

Gradients: custom VJP with the same K-blocked scheme (FlashAttention-2):
dq accumulates over k-blocks on a (bh, qi, ki) grid; dk/dv accumulate over
q-blocks on a (bh, ki, qi) grid. The forward saves per-row logsumexp;
probabilities are recomputed blockwise. Cost is the standard ~one extra
forward of FLOPs.

On non-TPU backends the kernels run in interpret mode so tests validate
numerics everywhere; the compiled path engages on real TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block sizes are constants carried over from earlier rounds; their speed is
# not measured on this chip. What IS established on the v5e (chip_smoke.py,
# PR 21): 1024x1024 blocks compile inside Mosaic's default scoped VMEM,
# forward and backward, at Dh=64 and Dh=128 in bf16.
MAX_BLOCK = 1024
MIN_BLOCK = 128
NEG_INF = float(jnp.finfo(jnp.float32).min)

# scoped-VMEM budget for one kernel instance's working set: the
# 1536-block working set at Dh=64 bf16, a line drawn above the 1024 blocks
# that compile on the v5e (2048 is not tried on this chip)
_VMEM_BUDGET = (1536 + 2 * 2 * 1536) * 64 * 2 + (2 * 128 + 64) * 1536 * 4


def auto_block(T: int) -> int | None:
    """Largest power-of-two block in [128, 1024] dividing T (every candidate
    is a multiple of 128, as Mosaic's lane dimension requires); at T <= 1024
    prefer T//2 (diagonal-only work). None if no candidate divides T."""
    if T <= MAX_BLOCK:
        half = T // 2
        if half >= MIN_BLOCK and half % MIN_BLOCK == 0 and T % half == 0:
            return half
    for b in (MAX_BLOCK, 512, 256, MIN_BLOCK):
        if b <= T and T % b == 0:
            return b
    return None


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *, block_k: int, causal: bool,
                  scale: float):
    """Grid (B*H, T//block_q, T//block_k), k innermost. Refs:
    q (1, block_q, Dh), k/v (1, block_k, Dh), o (1, block_q, Dh),
    lse (1, 1, block_q). Scratch (f32): m/l (block_q, 128), acc
    (block_q, Dh) — softmax state persists across the k steps; o/lse are
    written once on the last step (their block index is k-invariant, so
    Mosaic flushes them to HBM only when the q block advances)."""
    block_q = q_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # causal: key blocks strictly above the diagonal contribute nothing
    run = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (block_q, block_k)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        blk_max = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(new_m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(new_l, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        m = m_scr[:, :1]
        l_safe = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l_safe))[:, 0]


def _bh_layout(t):
    """(B, T, H, Dh) -> (B*H, T, Dh)."""
    B, T, H, Dh = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    block_q: int, block_k: int, interpret: bool,
):
    """q/k/v: (B, T, H, Dh) -> (out (B, T, H, Dh), lse (B*H, 1, T) f32).
    lse carries a singleton middle dim so its blocks satisfy Mosaic's
    last-two-dims rule (divisible by (8, 128) or equal to the array dims)."""
    B, T, H, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    qb, kb, vb = _bh_layout(q), _bh_layout(k), _bh_layout(v)
    grid = (B * H, T // block_q, T // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                          scale=scale),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, T, Dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    return out.reshape(B, H, T, Dh).transpose(0, 2, 1, 3), lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, block_k: int, causal: bool, scale: float):
    """Grid (B*H, T//block_q, T//block_k), k innermost: one q block
    accumulates dq over the streamed key blocks; p recomputed from
    (q, k, lse)."""
    block_q = q_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    run = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]       # (block_q, 1)
        delta = delta_ref[0, 0][:, None]   # (block_q, 1)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                causal: bool, scale: float):
    """Grid (B*H, T//block_k, T//block_q), q innermost: one key block
    accumulates dk/dv over the streamed query blocks."""
    block_k = k_ref.shape[1]
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    k_start = ki * block_k
    q_start = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    # causal: q blocks entirely above this key block see none of it
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _body():
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                       # (block_q, block_k)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k, interpret):
    """Blockwise dq/dk/dv; q/k/v/out/g (B, T, H, Dh), lse (B*H, 1, T)."""
    B, T, H, Dh = q.shape
    scale = 1.0 / (Dh ** 0.5)
    qb, kb, vb = _bh_layout(q), _bh_layout(k), _bh_layout(v)
    dob = _bh_layout(g)
    # delta_i = sum_d dO_id * O_id — O(T*Dh), plain XLA (fuses into one pass)
    delta = jnp.sum(dob.astype(jnp.float32) * _bh_layout(out).astype(jnp.float32),
                    axis=-1)[:, None, :]  # (B*H, 1, T), lse's layout

    def qblk(blk):
        return pl.BlockSpec((1, blk, Dh), lambda bh, i, j: (bh, i, 0))

    def jblk(blk):
        return pl.BlockSpec((1, blk, Dh), lambda bh, i, j: (bh, j, 0))

    def row_i(blk):
        return pl.BlockSpec((1, 1, blk), lambda bh, i, j: (bh, 0, i))

    def row_j(blk):
        return pl.BlockSpec((1, 1, blk), lambda bh, i, j: (bh, 0, j))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dh), q.dtype),
        grid=(B * H, T // block_q, T // block_k),
        in_specs=[qblk(block_q), jblk(block_k), jblk(block_k), qblk(block_q),
                  row_i(block_q), row_i(block_q)],
        out_specs=qblk(block_q),
        scratch_shapes=[pltpu.VMEM((block_q, Dh), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal,
                          scale=scale),
        out_shape=(jax.ShapeDtypeStruct((B * H, T, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B * H, T, Dh), v.dtype)),
        grid=(B * H, T // block_k, T // block_q),
        in_specs=[jblk(block_q), qblk(block_k), qblk(block_k), jblk(block_q),
                  row_j(block_q), row_j(block_q)],
        out_specs=(qblk(block_k), qblk(block_k)),
        scratch_shapes=[pltpu.VMEM((block_k, Dh), jnp.float32),
                        pltpu.VMEM((block_k, Dh), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)

    from_bh = lambda t: t.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)  # noqa: E731
    return from_bh(dq), from_bh(dk), from_bh(dv)


# Measured-fastest (block_q, block_k) per sequence length, from a sweep on
# the chip. Empty: no such sweep has run on this chip, so every shape
# falls back to auto_block squares. Rectangular blocks (small q x large k)
# keep the softmax state resident while streaming more K per grid step.
BLOCK_TABLE: dict = {}
# the shape family the sweep measures (q/k/v head dim, element bytes):
# table entries qualify ONLY here — other Dh/itemsize would resolve to
# unmeasured auto blocks. Dispatch (ops/attention.py) and any future
# sweep extension read this, so the qualifying condition lives in one
# place next to the table it scopes.
BLOCK_TABLE_SWEPT_SHAPE = (64, 2)


def _resolve_blocks(T, block_q, block_k, Dh: int = 64, itemsize: int = 2):
    table = BLOCK_TABLE.get(T)
    if block_q is None and block_k is None and table is not None:
        bq, bk = table
        # table entries face the SAME guards the auto path does: lane
        # alignment (Mosaic needs multiples of 128) and scoped VMEM for
        # the larger tile — a mis-adopted (128, 2048) entry must fall
        # back to auto squares, not blow VMEM at chip time
        if ((Dh, itemsize) == BLOCK_TABLE_SWEPT_SHAPE
                and T % bq == 0 and T % bk == 0
                and bq % MIN_BLOCK == 0 and bk % MIN_BLOCK == 0
                and flash_vmem_ok(T, Dh, itemsize, block=max(bq, bk))):
            return bq, bk
    auto = auto_block(T)
    bq = block_q or auto
    bk = block_k or auto
    if bq is None or bk is None or T % bq or T % bk:
        raise ValueError(
            f"flash_attention: T={T} has no block tiling (callers should "
            "gate on flash_shapes_ok and fall back to dense)")
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Flash attention with K-blocked pallas forward AND backward.
    q/k/v (B, T, H, Dh); block sizes default to auto_block's tiling for T;
    requires T % block == 0 (callers fall back to dense otherwise)."""
    interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(
        q.shape[1], block_q, block_k, Dh=q.shape[-1],
        itemsize=jnp.dtype(q.dtype).itemsize)
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k):
    interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(
        q.shape[1], block_q, block_k, Dh=q.shape[-1],
        itemsize=jnp.dtype(q.dtype).itemsize)
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(
        q.shape[1], block_q, block_k, Dh=q.shape[-1],
        itemsize=jnp.dtype(q.dtype).itemsize)
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k, interpret)


flash_attention.defvjp(_fwd, _bwd)


def flash_vmem_ok(T: int, Dh: int, itemsize: int = 2,
                  block: int | None = None) -> bool:
    """K-blocked kernels hold only O(block * Dh) in VMEM, independent of T —
    the round-2 full-K/V staging limit (T~12k at Dh=64 bf16) is gone.
    Retained as a guard against configs where the block pipeline plus
    scratch would still exceed scoped VMEM (huge Dh or oversized explicit
    blocks)."""
    block = block or auto_block(T) or MIN_BLOCK
    # q + double-buffered k/v tiles in the input dtype...
    per_block = (block + 2 * 2 * block) * Dh * itemsize
    # ...plus the f32 m/l/acc scratch rows
    scratch = (2 * 128 + Dh) * block * 4
    return per_block + scratch <= _VMEM_BUDGET


def flash_shapes_ok(T: int, Dh: int, block_q: int | None = None,
                    block_k: int | None = None,
                    itemsize: int = 2) -> bool:
    """Static dispatch guard used by ops.attention.multihead_attention: the
    sequence must tile into whole blocks, Dh must fill lanes reasonably,
    and the requested (or auto) blocks must fit scoped VMEM; T itself is
    unbounded on a single chip (HBM is the ceiling)."""
    bq = block_q or auto_block(T)
    bk = block_k or auto_block(T)
    return (bq is not None and bk is not None
            and T % bq == 0 and T % bk == 0
            and bq % MIN_BLOCK == 0 and bk % MIN_BLOCK == 0
            and (Dh % 128 == 0 or Dh == 64)
            and flash_vmem_ok(T, Dh, itemsize, block=max(bq, bk)))
