"""Fused aggregation kernels: CPU interpret-mode bit-parity + the
double-buffered arena movement they ship with.

The contract under test is exactness, not tolerance: the fused
quantize+pack kernel must emit the same BYTES as the numpy wire codec, the
fused sanitize+Krum pass must reproduce the sequential
``sanitize_stacked`` → ``krum_aggregate`` bits, and a prefetch-overlapped
run must replay a synchronous run bit-for-bit. On CPU the kernels run in
interpret mode (opted in with ``interpret=True`` — production non-TPU
dispatch takes the bit-identical jnp reference instead), so every
assertion here is ``array_equal`` — any drift is a bug, not noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.comm.codec import (
    _leaf_hash,
    build_stacked_roundtrip,
    pack_int4,
    parse_codec_spec,
    stochastic_quantize,
)
from fedml_tpu.core.robust import (
    fused_sanitize_krum,
    krum_aggregate,
    pairwise_sq_dists,
    sanitize_stacked,
)
from fedml_tpu.ops.pallas import (
    fused_gram,
    fused_quantize_pack,
    quant_shapes_ok,
    robust_shapes_ok,
)
from fedml_tpu.ops.pallas.agg_quant import row_keys
from fedml_tpu.ops.pallas.agg_robust import _reference_gram


def _eq(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ------------------------------------------------ fused quantize + pack

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("C,m", [(3, 256), (5, 700), (4, 257)])
def test_quantize_pack_bit_identical_to_wire(bits, C, m):
    """Kernel bytes == numpy wire codec bytes, row by row, incl. the odd-m
    nibble tail and partial trailing 256-chunks."""
    rng = np.random.default_rng(bits * 100 + C)
    vals = rng.standard_normal((C, m)).astype(np.float32)
    vals[0, :5] = 0.0  # a zero chunk prefix exercises the amax==0 scale
    seed, rnd = 13, 2
    cids = np.arange(10, 10 + C, dtype=np.uint32)
    lh = _leaf_hash("layer/w")
    packed, scales, dec = fused_quantize_pack(
        jnp.asarray(vals), bits, seed, jnp.uint32(rnd),
        jnp.asarray(cids), lh, interpret=True)
    for c in range(C):
        q, s, d = stochastic_quantize(vals[c], bits, seed, rnd,
                                      int(cids[c]), lh)
        wire = pack_int4(q) if bits == 4 else q
        _eq(packed[c], wire, f"row {c} packed bytes")
        _eq(scales[c], s, f"row {c} scales")
        _eq(dec[c], d, f"row {c} decode")


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_pack_kernel_matches_reference_path(bits):
    """interpret-mode pallas_call == the jittable jnp reference fallback."""
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.standard_normal((6, 300)).astype(np.float32))
    cids = jnp.asarray(np.arange(6, dtype=np.uint32))
    args = (vals, bits, 3, jnp.uint32(1), cids, 99)
    pk, sk, dk = fused_quantize_pack(*args, use_kernel=True, interpret=True)
    pr, sr, dr = fused_quantize_pack(*args, use_kernel=False)
    _eq(pk, pr); _eq(sk, sr); _eq(dk, dr)


def test_quant_shapes_ok_bounds():
    assert quant_shapes_ok(8, 512)
    assert quant_shapes_ok(8, 255)  # sub-chunk cols pad up to one chunk
    assert not quant_shapes_ok(0, 256)
    assert not quant_shapes_ok(8, 0)


def test_row_keys_match_wire_key_chain():
    from fedml_tpu.comm.codec import stochastic_key

    cids = np.array([3, 77, 1024], np.uint32)
    h = np.asarray(row_keys(21, jnp.uint32(5), jnp.asarray(cids), 42))
    for i, c in enumerate(cids):
        assert int(h[i]) == stochastic_key(21, 5, int(c), 42)


# ------------------------------------------------ fused sanitize + Krum

def _poisoned_stack(C, seed=0, nan_row=1, boost_row=2):
    rng = np.random.default_rng(seed)
    upd = {
        "layer": {"w": rng.standard_normal((C, 40)).astype(np.float32)},
        "bias": rng.standard_normal((C, 7)).astype(np.float32),
    }
    upd["layer"]["w"][nan_row, 3] = np.nan
    upd["bias"][boost_row] *= 1e6
    return jax.tree.map(jnp.asarray, upd)


def test_gram_kernel_matches_reference():
    """Interpret-mode Pallas Gram tiles == pairwise_sq_dists' untiled vmap
    matmul, bit for bit, incl. the zero-padded partial block (C=10 -> 16).
    Input is nan_to_num'ed first — that's fused_gram's contract (the
    caller sanitizes, mirroring pairwise_sq_dists)."""
    rng = np.random.default_rng(1)
    flat_np = rng.standard_normal((10, 64)).astype(np.float32)
    flat_np[4, 0] = np.inf
    flat_np[7, 1] = np.nan
    flat = jnp.nan_to_num(jnp.asarray(flat_np))
    assert robust_shapes_ok(10, 64)
    g_k = fused_gram(flat, use_kernel=True, interpret=True)
    g_r = _reference_gram(flat)
    _eq(g_k, g_r, "gram")
    # the reference form IS pairwise_sq_dists' exact vmap expression
    _eq(g_r, jax.vmap(lambda r: flat @ r)(flat))


@pytest.mark.parametrize("C,D,vmem_cap", [
    (100, 300, None),   # one 104-row j block
    (130, 257, None),   # two 72-row j blocks, cohort padded to 144
    (100, 300, 8 * (24 * 384 + 16 * 128)),   # VMEM for a 16-row j tile only
])
def test_gram_kernel_block_shapes_match_reference(C, D, vmem_cap, monkeypatch):
    """The j tile's height follows the cohort and the VMEM left by the
    width; every height gives the reference's bits."""
    from fedml_tpu.ops.pallas import agg_robust

    if vmem_cap is not None:
        monkeypatch.setattr(agg_robust, "_VMEM_CAP", vmem_cap)
        assert agg_robust._block_j(C, D) == 16
    flat = jnp.asarray(
        np.random.default_rng(C).standard_normal((C, D)), jnp.float32)
    _eq(fused_gram(flat, interpret=True), _reference_gram(flat), "gram")


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("m,sample_weighted", [(1, False), (3, True)])
def test_fused_sanitize_krum_bit_identical(use_kernel, m, sample_weighted):
    """Fused pass == the simulator's sequential sanitize → krum calls, for
    every output: aggregate leaves, clean weights, quarantine, z, selection."""
    C = 12
    upd = _poisoned_stack(C)
    w = jnp.asarray(np.r_[np.full(C - 1, 8.0), 0.0].astype(np.float32))
    clean, cw, quar, z = sanitize_stacked(upd, w, z_thresh=6.0)
    agg0, sel0 = krum_aggregate(clean, cw, n_byz=2, m=m,
                                sample_weighted=sample_weighted)
    agg1, cw1, quar1, z1, sel1 = fused_sanitize_krum(
        upd, w, z_thresh=6.0, n_byz=2, m=m,
        sample_weighted=sample_weighted, use_kernel=use_kernel,
        interpret=True)
    _eq(cw1, cw); _eq(quar1, quar); _eq(z1, z); _eq(sel1, sel0)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(agg1),
            jax.tree_util.tree_leaves_with_path(agg0)):
        _eq(a, b, f"agg leaf {pa}")


def test_fused_sanitize_krum_padded_cohort_valid_mask():
    """Padded cohorts: valid= threads through sanitize exactly as the
    unfused path (and Krum ignores it there too — asymmetry preserved)."""
    C, real = 16, 13
    upd = _poisoned_stack(C, seed=3)
    valid = np.arange(C) < real
    w_np = np.full(C, 4.0, np.float32)
    w_np[real:] = 0.0  # padding rows carry zero weight
    w = jnp.asarray(w_np)
    clean, cw, quar, z = sanitize_stacked(upd, w, z_thresh=6.0, valid=valid)
    agg0, sel0 = krum_aggregate(clean, cw, n_byz=1, m=2)
    agg1, cw1, quar1, z1, sel1 = fused_sanitize_krum(
        upd, w, z_thresh=6.0, n_byz=1, m=2, valid=valid)
    _eq(cw1, cw); _eq(quar1, quar); _eq(z1, z); _eq(sel1, sel0)
    for a, b in zip(jax.tree_util.tree_leaves(agg1),
                    jax.tree_util.tree_leaves(agg0)):
        _eq(a, b)


def test_fused_sanitize_krum_2device_mesh():
    """Sharded cohort axis (2 CPU devices): fused == unfused under the same
    out_shardings, bit for bit."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh

    from fedml_tpu.parallel.sharding import shard_along

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("client",))
    C = 8
    upd = _poisoned_stack(C, seed=5)
    sh = jax.tree.map(lambda _: shard_along(mesh, "client", 0), upd)
    upd_dev = jax.tree.map(jax.device_put, upd, sh)
    w = jnp.asarray(np.full(C, 2.0, np.float32))
    clean, cw, quar, z = sanitize_stacked(upd_dev, w, out_shardings=sh)
    agg0, sel0 = krum_aggregate(clean, cw, n_byz=1, m=2)
    agg1, cw1, quar1, z1, sel1 = fused_sanitize_krum(
        upd_dev, w, n_byz=1, m=2, out_shardings=sh)
    _eq(cw1, cw); _eq(quar1, quar); _eq(sel1, sel0)
    for a, b in zip(jax.tree_util.tree_leaves(agg1),
                    jax.tree_util.tree_leaves(agg0)):
        _eq(a, b)


# ------------------------------------------------ codec fused encode path

def test_stacked_roundtrip_agg_kernels_bitparity():
    """build_stacked_roundtrip(agg_kernels=True) decodes the same bits as
    the default path — the wire-parity invariant of the fused encoder."""
    rng = np.random.default_rng(11)
    C = 4
    cids = jnp.asarray(np.array([5, 9, 2, 31], np.uint32))
    for spec in ("q8", "q4", "delta|topk:0.25|q4"):
        cs = parse_codec_spec(spec)
        rt0 = build_stacked_roundtrip(spec, seed=13)
        rt1 = build_stacked_roundtrip(spec, seed=13, agg_kernels=True)
        res0 = res1 = ({"w": jnp.zeros((C, 300), jnp.float32)}
                       if cs.topk is not None else ())
        for rnd in range(2):
            upd = {"w": jnp.asarray(
                rng.standard_normal((C, 300)).astype(np.float32))}
            dec0, res0 = rt0(upd, res0, cids, jnp.uint32(rnd))
            dec1, res1 = rt1(upd, res1, cids, jnp.uint32(rnd))
            for a, b in zip(jax.tree_util.tree_leaves((dec0, res0)),
                            jax.tree_util.tree_leaves((dec1, res1))):
                _eq(a, b, spec)


# ------------------------------------------------ partial-tile Krum dists

def test_pairwise_dists_partial_tile_sizes():
    """Any positive tile size works now — the last partial tile is padded
    with zero rows and trimmed (it used to be a hard ValueError)."""
    rng = np.random.default_rng(2)
    upd = {"w": jnp.asarray(rng.standard_normal((10, 33)).astype(np.float32))}
    base = pairwise_sq_dists(upd)
    for t in (3, 4, 7, 10, 16):
        _eq(pairwise_sq_dists(upd, tile_size=t), base, f"tile_size={t}")
    with pytest.raises(ValueError, match="must be positive"):
        pairwise_sq_dists(upd, tile_size=0)


# ------------------------------------------------ double-buffered arena

def _arena(capacity=8, mesh=None):
    from fedml_tpu.simulation.client_store import ClientStateArena

    proto = {"c": jnp.zeros((3,), jnp.float32), "n": jnp.zeros((), jnp.int32)}
    return ClientStateArena(proto, capacity, mesh=mesh)


def test_put_take_matches_scatter_then_gather():
    """put_take == scatter followed by gather, including an overlapping
    client that must come back with its freshly written row."""
    a1, a2 = _arena(), _arena()
    first = [1, 2, 3]
    for a in (a1, a2):
        a.gather(first)
    rows = {"c": jnp.asarray(np.arange(9, dtype=np.float32).reshape(3, 3)),
            "n": jnp.asarray(np.array([7, 8, 9], np.int32))}
    nxt = [3, 4, 4, 5]  # 3 overlaps the put cohort; 4 repeats (padding)
    got = a1.put_take(first, rows, nxt)
    assert got is not None
    a2.scatter(first, rows)
    want = a2.gather(nxt)
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _eq(x, y)
    _eq(got["n"][0], 9)  # client 3's row is the POST-scatter value


def test_put_take_protect_aborts_without_mutation():
    """When the next cohort cannot fit without evicting a pending-scatter
    client, put_take refuses and leaves every slot untouched."""
    a = _arena(capacity=4)
    cur = [0, 1, 2, 3]
    a.gather(cur)
    rows = {"c": jnp.ones((4, 3), jnp.float32),
            "n": jnp.asarray(np.arange(4, dtype=np.int32))}
    before = dict(a._slot_of)
    got = a.put_take(cur, rows, [0, 1, 9, 10])  # 9,10 would evict 2 or 3
    assert got is None
    assert a._slot_of == before and a.spilled_count == 0
    a.scatter(cur, rows)  # the fallback path still works afterwards
    _eq(a.state_of(3)["n"], 3)


def test_put_take_rejects_duplicate_put_ids():
    a = _arena()
    a.gather([1, 2])
    rows = {"c": jnp.zeros((2, 3), jnp.float32),
            "n": jnp.zeros((2,), jnp.int32)}
    with pytest.raises(ValueError, match="unique"):
        a.put_take([1, 1], rows, [2])


def test_prefetcher_peek_is_nonconsuming():
    import time

    from fedml_tpu.simulation.prefetch import RoundPrefetcher

    with RoundPrefetcher(lambda r: f"item{r}", range(3), depth=2) as pf:
        deadline = time.monotonic() + 5.0
        while pf.peek(0) is None and time.monotonic() < deadline:
            time.sleep(0.005)
        assert pf.peek(0) == "item0"
        assert pf.peek(1) is None  # head is round 0, not 1
        assert pf.get(0) == "item0"  # peek did not consume it
        assert pf.get(1) == "item1"
    assert pf.peek(2) is None  # closed: peek is None, never raises


def test_prefetch_overlap_run_is_bit_identical(tmp_path):
    """End to end: a prefetch-overlapped SCAFFOLD run (put_take movement
    engaged) replays the synchronous run bit for bit — history and params."""
    import fedml_tpu
    from fedml_tpu.data.federated import ArrayPair, build_federated_data
    from fedml_tpu.simulation import build_simulator

    pool, spc = 24, 4
    rng = np.random.default_rng(0)
    n = pool * spc
    y = (np.arange(n) % 2).astype(np.int64)
    x = (rng.normal(size=(n, 8)).astype(np.float32)
         + 2.0 * y[:, None].astype(np.float32))
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:16], y[:16]),
        {c: list(range(c * spc, (c + 1) * spc)) for c in range(pool)}, 2)

    def run(prefetch):
        args = fedml_tpu.init(config=dict(
            dataset="blobs", model="lr", client_num_in_total=pool,
            client_num_per_round=8, comm_round=4, learning_rate=0.1,
            epochs=1, batch_size=spc, frequency_of_the_test=10_000,
            random_seed=0, federated_optimizer="SCAFFOLD",
            prefetch=prefetch, prefetch_depth=2))
        sim, _ = build_simulator(args, fed_data=fed)
        hist = sim.run(apply_fn=None, log_fn=None)
        return sim, hist

    s0, h0 = run(False)
    s1, h1 = run(True)
    assert any(r["phases"].get("state_move", 0) > 0 for r in h1), \
        "double-buffered movement never engaged"
    for r0, r1 in zip(h0, h1):
        assert r0["train_loss"] == r1["train_loss"]
    for a, b in zip(jax.tree_util.tree_leaves(s0.params),
                    jax.tree_util.tree_leaves(s1.params)):
        _eq(a, b)


# ------------------------------------------------ native stale-.so guard

def test_native_embedded_hash_matches_source():
    from fedml_tpu import native

    if not native.native_available():
        pytest.skip("no native toolchain in this environment")
    lib = native.get_lib()
    import ctypes

    fn = lib.fedml_native_src_hash
    fn.restype = ctypes.c_char_p
    embedded = fn().decode().split("=", 1)[1]
    assert embedded == native._src_hash()


def test_native_hash_mismatch_falls_back(monkeypatch):
    from fedml_tpu import native

    class _FakeLib:
        pass  # no fedml_native_src_hash symbol: pre-hash binary

    monkeypatch.setattr(native, "_hash_warned", False)
    assert not native._hash_ok(_FakeLib())
    assert native._hash_warned  # warned exactly once, then silent
    assert not native._hash_ok(_FakeLib())
