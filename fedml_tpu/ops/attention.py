"""Attention ops: dense multihead attention + ring attention over a seq axis.

Single-chip path is plain XLA for short T and the pallas flash kernel
(ops/pallas/flash_attention.py) from the measured crossover up
(``auto_attention_impl``). The ring path
implements blockwise ring attention (Liu et al.) with ``lax.ppermute`` over the ``seq`` mesh axis: each shard
holds a query block, K/V blocks rotate around the ring, and softmax is
accumulated online (running max + normalizer), so memory stays O(T/n per
device) and comms ride ICI. This is the long-context capability the task
brief requires (SURVEY.md §5.7: absent in reference, first-class here).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.telemetry import get_registry


# The lowest length at which the kernel beats XLA's dense attention on the
# chip: PR 27's sweep on the v5e at (B, T, H, Dh) = (8, T, 16, 64) bf16 causal
# (PERF.md section 6) and the LM cell at T = 1024. Nothing shorter was
# measured, so nothing shorter is routed.
FLASH_MIN_T_ON_TPU = 1024
# Off the chip the kernel runs in Pallas interpret mode, where it is slower
# than dense at any length: there it is taken only where the (T, T) logits
# stop being an option.
FLASH_MIN_T_INTERPRETED = 4096
DENSE_SAVED_BYTES_MAX = 512 * 1024**2


def auto_attention_impl(B: int, H: int, T: int, Dh: int,
                        itemsize: int = 2, window: Optional[int] = None) -> str:
    """Pick 'flash' vs 'dense' for (B, T, H, Dh) attention, from the
    platform and the shape alone.

    Speed: on a TPU backend the K-blocked kernel takes every length from
    ``FLASH_MIN_T_ON_TPU`` up; elsewhere it is interpreted, and dense keeps
    everything under ``FLASH_MIN_T_INTERPRETED``.

    Memory, on every platform: dense training saves the (B, H, T, T)
    probabilities for the backward pass PER LAYER — a 12-layer stack at
    B=16 H=16 T=2048 pins 26 GB. Prefer flash whenever one layer's saved
    tensor crosses 512 MB (a meaningful slice of 16 GB HBM once multiplied
    by typical depths).

    Either way the shape has to tile and the kernels' blocks have to fit
    VMEM (``flash_shapes_ok``): ViT's 65 and 197 tokens, a lane-hostile Dh
    and 25 heads of 64 (one 1600-lane tile) stay dense; so does a causal
    band under T that holds no two of the kernels' smallest blocks.
    """
    from .pallas import flash_shapes_ok
    from .pallas.flash_attention import MIN_BLOCK

    min_t = (FLASH_MIN_T_ON_TPU if jax.default_backend() == "tpu"
             else FLASH_MIN_T_INTERPRETED)
    want_flash = (T >= min_t
                  or B * H * T * T * itemsize > DENSE_SAVED_BYTES_MAX)
    band_fits = window is None or window >= min(T, 2 * MIN_BLOCK)
    if want_flash and band_fits and flash_shapes_ok(T, Dh, itemsize=itemsize,
                                                     heads=H):
        return "flash"
    return "dense"


def multihead_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    impl: Optional[str] = None, window: Optional[int] = None,
) -> jax.Array:
    """Attention. q: (B, T, H, Dh); k/v: (B, T, Hkv, Dh) -> (B, T, H, Dh).

    ``impl``: 'flash' (pallas kernel, ops/pallas/flash_attention.py),
    'dense', or None = auto (flash when shapes tile into whole blocks).
    ``window`` (causal only): query i sees key j where ``0 <= i - j <
    window`` (a sliding-window layer); both implementations apply the band,
    and the dispatch rule sends one too narrow for the kernels' blocks to
    dense (``auto_attention_impl``).

    Grouped KV heads (Hkv < H, Hkv | H): each KV head serves H // Hkv
    consecutive query heads. K and V are repeated to H heads here, before
    the dispatch rule, so the rule, its counter and both implementations
    stay one path; the copy is (H // Hkv - 1) x the K/V bytes a call, and
    its gradient the sum over the group.
    """
    T, Dh = q.shape[1], q.shape[-1]
    if window is not None and not causal:
        raise ValueError("a window is a causal band: causal=True")
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2] or v.shape[2] != k.shape[2]:
            raise ValueError(
                f"grouped attention needs the KV heads ({k.shape[2]}, "
                f"{v.shape[2]}) to divide the query heads ({q.shape[2]})")
        groups = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    if impl is None:
        itemsize = jnp.dtype(q.dtype).itemsize
        impl = auto_attention_impl(q.shape[0], q.shape[2], T, Dh, itemsize,
                                   window)
        saved_bytes = q.shape[0] * q.shape[2] * T * T * itemsize
        if impl == "dense" and (T >= 8192
                                or saved_bytes > DENSE_SAVED_BYTES_MAX):
            # loud, not silent: dense wanted flash (long T, or the
            # per-layer saved probabilities alone cross the memory
            # threshold) but flash was refused (untileable T or
            # lane-unfriendly Dh) — the failure will surface later as a
            # generic HBM allocation error; point at the fix NOW.
            import logging

            logging.warning(
                "attention auto-dispatch: falling back to DENSE O(T^2) "
                "attention at T=%d (flash needs T tileable by 128-blocks "
                "and Dh in {64, k*128}; got Dh=%d) — expect ~%.1f GB of "
                "saved probabilities PER LAYER; pad T/Dh to tileable "
                "sizes or shard the sequence with ring/ulysses attention",
                T, Dh, saved_bytes / 2**30)
        if isinstance(q, jax.core.Tracer):
            # what the rule made of this call site: once per call site per
            # trace, nothing at run time. ``seq_len`` keeps a model's few-token
            # ``init`` trace apart from the lengths it trains at.
            get_registry().counter("fedml_attention_dispatch_total",
                                   impl=impl, seq_len=T).inc()
    if impl == "flash":
        from .pallas import flash_attention

        return flash_attention(q, k, v, causal, window=window)
    scale = 1.0 / jnp.sqrt(Dh).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S = logits.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), dtype=bool))
        if window is not None and window < T:  # and j > i - window
            mask = jnp.logical_and(mask, jnp.triu(
                jnp.ones((T, S), dtype=bool), 1 - window))
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, impl: Optional[str] = None,
                    mesh=None, window: Optional[int] = None) -> jax.Array:
    """Attention with no sequence axis, under the mesh the enclosing step is
    partitioned over. GSPMD cannot partition a Mosaic kernel (jax refuses to
    lower one inside a sharded jit), so when the flash path engages under a
    data x model mesh the call is wrapped in shard_map: batch and heads are
    independent, each device runs the kernel on its own (B/dp, T, H/tp, Dh)
    shard (and its Hkv/tp KV heads). The dense path stays plain XLA, which
    GSPMD partitions itself. ``window`` as ``multihead_attention``'s."""
    B, T, H, Dh = q.shape
    if mesh is not None and mesh.size > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import AXIS_DATA, AXIS_MODEL

        b_ax = AXIS_DATA if mesh.shape.get(AXIS_DATA, 1) > 1 else None
        h_ax = AXIS_MODEL if mesh.shape.get(AXIS_MODEL, 1) > 1 else None
        dp = mesh.shape[b_ax] if b_ax else 1
        tp = mesh.shape[h_ax] if h_ax else 1
        if (B % dp == 0 and H % tp == 0 and k.shape[2] % tp == 0
                and (impl or auto_attention_impl(
                    B // dp, H // tp, T, Dh,
                    jnp.dtype(q.dtype).itemsize)) == "flash"):
            spec = P(b_ax, None, h_ax, None)
            return shard_map(
                lambda q, k, v: multihead_attention(
                    q, k, v, causal=causal, impl="flash", window=window),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)
    return multihead_attention(q, k, v, causal=causal, impl=impl,
                               window=window)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    impl: Optional[str] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style). Must run
    inside shard_map with ``axis_name`` bound; q/k/v are local sequence
    shards (B, T_local, H, Dh) with ALL heads present.

    Two collectives instead of the ring's n ppermute hops: an all-to-all
    re-shards from sequence to heads (each device gets the FULL sequence
    for H/n heads), full-sequence attention runs locally — flash-kernel
    eligible, unlike the ring's blockwise accumulation — and a reverse
    all-to-all restores sequence sharding. The axis size must divide the
    head count (n | H). Comms volume per device is ~n/2x LOWER than the
    ring's (ring moves 2*B*T*H*Dh per device over its n K/V hops; the
    four all-to-alls here move ~4*B*(T/n)*H*Dh — each device only ever
    holds H/n heads of the full sequence). Prefer Ulysses when H >= n
    and the per-device full-T attention fits memory; the ring remains
    the extreme-context option where O(T/n) activation memory is the
    constraint.
    """
    n = lax.axis_size(axis_name)
    B, Tl, H, Dh = q.shape
    if H % n != 0:
        raise ValueError(
            f"ulysses needs heads ({H}) divisible by the sequence axis ({n})")

    def seq_to_heads(x):
        # (B, Tl, H, Dh) --all_to_all--> (B, n*Tl, H/n, Dh)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = multihead_attention(qh, kh, vh, causal=causal, impl=impl)
    # (B, n*Tl, H/n, Dh) --all_to_all--> (B, Tl, H, Dh)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Blockwise ring attention. Must run inside shard_map with ``axis_name``
    bound; q/k/v are the local sequence shards (B, T_local, H, Dh).

    Online-softmax accumulation: for each incoming K/V block keep running
    (max, normalizer, weighted-sum) in f32 and rotate K/V with ppermute.
    For ``causal=True`` blocks are masked by global block position (query
    shard i attends to key shard j fully if j < i, diagonally if j == i).
    """
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, T, H, Dh = q.shape
    scale = 1.0 / jnp.sqrt(Dh).astype(jnp.float32)

    qf = q.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    perm = [(i, (i + 1) % n) for i in range(n)]
    tri = jnp.tril(jnp.ones((T, T), dtype=bool))

    def block_logits(kblk, src_idx):
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32)) * scale
        if causal:
            keep_all = src_idx < my_idx
            keep_diag = src_idx == my_idx
            mask = jnp.where(keep_all, True, jnp.where(keep_diag, tri, False))
            logits = jnp.where(mask[None, None], logits, neg)
        return logits

    def step(carry, _):
        kblk, vblk, src_idx, m, l, acc = carry
        logits = block_logits(kblk, src_idx)
        blk_max = jnp.max(logits, axis=-1)            # (B,H,T)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])        # (B,H,T,K)
        new_l = l * correction + p.sum(axis=-1)
        new_acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
        )
        kblk = lax.ppermute(kblk, axis_name, perm)
        vblk = lax.ppermute(vblk, axis_name, perm)
        src_idx = lax.ppermute(src_idx, axis_name, perm)
        return (kblk, vblk, src_idx, new_m, new_l, new_acc), None

    m0 = jnp.full((B, H, T), neg, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    acc0 = jnp.zeros((B, H, T, Dh), jnp.float32)
    (k_, v_, _, m, l, acc), _ = lax.scan(
        step, (k, v, my_idx, m0, l0, acc0), None, length=n
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,T,H,Dh)
