"""Robust aggregation defenses + LCC secure aggregation + scheduler."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.robust import (
    RobustAggregator,
    coordinate_median,
    global_norm,
    krum_aggregate,
    krum_scores,
    norm_clip_update,
    pairwise_sq_dists,
    sanitize_stacked,
    trimmed_mean,
)
from fedml_tpu.core.scheduler import balanced_client_schedule, dp_schedule, even_client_schedule
from fedml_tpu.core.secure_agg import (
    DEFAULT_PRIME,
    LightSecAggConfig,
    dequantize_tree,
    lagrange_coeffs,
    lcc_decode,
    lcc_encode,
    modular_inv,
    quantize_tree,
    secure_aggregate,
)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def test_norm_clip_bounds_update_norm():
    update = {"w": jnp.full((10,), 3.0), "b": jnp.ones(())}
    clipped = norm_clip_update(update, norm_bound=1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    # direction preserved
    ratio = clipped["w"][0] / clipped["b"]
    assert np.isclose(float(ratio), 3.0, rtol=1e-5)


def test_norm_clip_passthrough_below_bound():
    update = {"w": jnp.full((4,), 0.1)}
    clipped = norm_clip_update(update, norm_bound=10.0)
    np.testing.assert_allclose(np.asarray(clipped["w"]), 0.1, rtol=1e-6)


def test_coordinate_median_rejects_outlier():
    honest = [{"w": jnp.ones(5) * v} for v in (0.9, 1.0, 1.1)]
    byzantine = {"w": jnp.ones(5) * 1e6}
    stacked = _stack(honest + [byzantine])
    agg = coordinate_median(stacked)
    np.testing.assert_allclose(np.asarray(agg["w"]), 1.05, rtol=1e-5)


def test_robust_aggregator_weak_dp_noise_scale():
    ra = RobustAggregator(defense_type="weak_dp", norm_bound=100.0, stddev=0.1)
    stacked = {"w": jnp.ones((8, 1000))}
    agg = ra.aggregate(stacked, jnp.ones(8), rng=jax.random.PRNGKey(0))
    noise = np.asarray(agg["w"]) - 1.0
    assert 0.05 < noise.std() < 0.2


def test_krum_scores_match_numpy_oracle():
    """XLA Krum scores against a direct NumPy transcription of Blanchard et
    al. 2017: score(i) = sum of the C-f-2 smallest ||u_i - u_j||^2, j != i."""
    rng = np.random.default_rng(0)
    updates = rng.normal(size=(7, 13)).astype(np.float32)
    stacked = {"w": jnp.asarray(updates)}
    f = 2
    got = np.asarray(krum_scores(pairwise_sq_dists(stacked), f))
    want = np.empty(7)
    for i in range(7):
        d = np.sort([np.sum((updates[i] - updates[j]) ** 2)
                     for j in range(7) if j != i])
        want[i] = d[: 7 - f - 2].sum()
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_krum_selects_honest_cluster():
    """Classic Krum picks an update from the tight honest cluster, never a
    far-flung byzantine one; multi-Krum averages exactly the m survivors."""
    honest = [{"w": jnp.ones(6) * (1.0 + 0.01 * i)} for i in range(7)]
    byz = [{"w": jnp.ones(6) * 100.0}, {"w": jnp.ones(6) * -80.0}]
    stacked = _stack(honest + byz)
    w = jnp.ones(9)
    agg, selected = krum_aggregate(stacked, w, n_byz=2, m=1)
    sel = np.nonzero(np.asarray(selected))[0]
    assert len(sel) == 1 and sel[0] < 7, sel
    assert 0.9 < float(np.asarray(agg["w"])[0]) < 1.1
    agg_m, selected_m = krum_aggregate(stacked, w, n_byz=2, m=7)
    sel_m = set(np.nonzero(np.asarray(selected_m))[0].tolist())
    assert sel_m == set(range(7)), sel_m
    np.testing.assert_allclose(
        np.asarray(agg_m["w"]),
        np.mean([1.0 + 0.01 * i for i in range(7)]), rtol=1e-5)


def test_robust_aggregator_krum_family_defends():
    """The three Krum-family defense_types all reject a NaN + scaled pair
    of attackers; krum_fedavg weights survivors by sample count."""
    honest = [{"w": jnp.ones(4) * v} for v in (0.9, 1.0, 1.0, 1.1, 1.05)]
    attackers = [{"w": jnp.full(4, jnp.nan)}, {"w": jnp.ones(4) * 500.0}]
    stacked = _stack(honest + attackers)
    w = jnp.asarray([1.0, 2.0, 2.0, 1.0, 1.0, 5.0, 5.0])
    for defense in ("krum", "multi_krum", "krum_fedavg"):
        ra = RobustAggregator(defense_type=defense, sanitize=True,
                              byzantine_n=2)
        agg, info = ra.aggregate_with_info(stacked, w)
        a = np.asarray(agg["w"])
        assert np.isfinite(a).all(), (defense, a)
        assert 0.85 <= a[0] <= 1.15, (defense, a)
        assert np.asarray(info["quarantine"])[5], defense  # the NaN row
    # sample weighting: survivors 0..4 with weights 1,2,2,1,1
    ra = RobustAggregator(defense_type="krum_fedavg", sanitize=True,
                          byzantine_n=2, multi_krum_m=5)
    agg, info = ra.aggregate_with_info(stacked, w)
    sel = np.asarray(info["selected"])[:5]
    vals = np.array([0.9, 1.0, 1.0, 1.1, 1.05])
    ws = np.array([1.0, 2.0, 2.0, 1.0, 1.0]) * sel
    np.testing.assert_allclose(
        np.asarray(agg["w"])[0], (vals * ws).sum() / ws.sum(), rtol=1e-5)


def test_sanitize_quarantines_nonfinite_and_outliers():
    honest = [{"w": jnp.ones(8) * v} for v in (0.9, 1.0, 1.1, 1.0, 0.95)]
    rows = honest + [{"w": jnp.full(8, jnp.nan)}, {"w": jnp.ones(8) * 1e4}]
    stacked = _stack(rows)
    weights = jnp.ones(7)
    clean, w, quar, z = sanitize_stacked(stacked, weights, z_thresh=6.0)
    q = np.asarray(quar)
    assert q.tolist() == [False] * 5 + [True, True]
    # quarantined rows are ZEROED, not just zero-weighted (0 * nan == nan)
    cw = np.asarray(clean["w"])
    assert np.isfinite(cw).all()
    np.testing.assert_allclose(cw[5], 0.0)
    np.testing.assert_allclose(cw[6], 0.0)
    np.testing.assert_allclose(np.asarray(w), [1] * 5 + [0, 0])
    assert np.isinf(np.asarray(z)[5])  # non-finite rows pin z to +inf


def test_sanitize_uniform_cohort_no_false_positives():
    """Near-identical norms (fp jitter only) must not be flagged — the MAD
    floor is relative to the median."""
    rows = [{"w": jnp.ones(16) * (1.0 + 1e-7 * i)} for i in range(8)]
    _, w, quar, _ = sanitize_stacked(_stack(rows), jnp.ones(8))
    assert not np.asarray(quar).any()
    np.testing.assert_allclose(np.asarray(w), 1.0)


def test_sanitize_valid_mask_matches_subset_run():
    """Padded (invalid) rows must not shift the median/MAD statistics: a
    masked 8-row cohort sanitizes identically to the 6-row subset, and the
    pad rows come back unquarantined with z=0."""
    rows = [{"w": jnp.ones(8) * v} for v in (0.9, 1.0, 1.1, 1.0, 0.95, 1e4)]
    # zero pad rows: perfectly plausible "inliers" that would drag the
    # median/MAD if counted (the failure mode the mask exists to prevent)
    pads = [{"w": jnp.zeros(8)}] * 2
    stacked = _stack(rows + pads)
    valid = jnp.asarray([True] * 6 + [False] * 2)
    weights = jnp.asarray([1.0] * 6 + [0.0] * 2)  # pads pre-zeroed upstream
    clean, w, quar, z = sanitize_stacked(stacked, weights, valid=valid)
    c_s, w_s, quar_s, z_s = sanitize_stacked(_stack(rows), jnp.ones(6))
    np.testing.assert_array_equal(np.asarray(quar)[:6], np.asarray(quar_s))
    np.testing.assert_array_equal(np.asarray(clean["w"])[:6],
                                  np.asarray(c_s["w"]))
    np.testing.assert_array_equal(np.asarray(z)[:6], np.asarray(z_s))
    np.testing.assert_array_equal(np.asarray(w)[:6], np.asarray(w_s))
    # pad rows: never quarantined (the padding weight mask already zeroes
    # them), z pinned to 0 so they can't trip callers' z-based logging
    assert not np.asarray(quar)[6:].any()
    np.testing.assert_array_equal(np.asarray(z)[6:], 0.0)
    np.testing.assert_array_equal(np.asarray(w)[6:], 0.0)


def test_pairwise_dists_tiled_matches_untiled():
    """The client-axis tiling (how the sharded Krum path bounds the C x C
    distance matrix working set) computes the untiled matrix — including a
    non-divisor tile, whose last partial block is zero-padded and trimmed.
    Only a non-positive tile is a hard error."""
    import pytest

    rng = np.random.default_rng(1)
    stacked = {"w": jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))}
    base = np.asarray(pairwise_sq_dists(stacked))
    # d_ij = |x_i|^2 + |x_j|^2 - 2 x_i.x_j from a float32 Gram: a tile runs
    # its products in another order, so an entry moves by a few ulps of the
    # NORMS it is the difference of (|x|^2 ~ 5 at width 5, 2.4e-7 measured
    # on the diagonal, which is 0 untiled). rtol alone cannot hold a 0;
    # atol = 8 ulps of the largest squared norm can.
    atol = 8 * np.finfo(np.float32).eps * float(
        (np.asarray(stacked["w"]) ** 2).sum(axis=1).max())
    for t in (1, 2, 3, 4, 8):
        np.testing.assert_allclose(
            np.asarray(pairwise_sq_dists(stacked, tile_size=t)), base,
            rtol=1e-5, atol=atol)
    with pytest.raises(ValueError, match="must be positive"):
        pairwise_sq_dists(stacked, tile_size=0)


def test_pairwise_dists_valid_mask_isolates_pads():
    rng = np.random.default_rng(2)
    stacked = {"w": jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))}
    valid = jnp.asarray([True] * 4 + [False] * 2)
    d = np.asarray(pairwise_sq_dists(stacked, valid=valid))
    base = np.array(pairwise_sq_dists({"w": stacked["w"][:4]}))
    # the valid path pins its diagonal to exactly 0; the plain path leaves
    # fp residue there — compare off-diagonal entries
    np.fill_diagonal(base, 0.0)
    np.testing.assert_allclose(d[:4, :4], base, rtol=1e-5)
    # any pair touching a pad row is pushed to +inf (never a Krum
    # neighbour), except the self-distance diagonal which stays 0
    assert np.isinf(d[4:, :4]).all() and np.isinf(d[:4, 4:]).all()
    np.testing.assert_array_equal(np.diag(d), 0.0)


def test_krum_valid_mask_matches_subset_selection():
    """Krum on a padded cohort (valid mask + n_valid-adjusted neighbour
    count) selects the same clients and aggregates to the same value as
    Krum on the unpadded subset."""
    honest = [{"w": jnp.ones(6) * (1.0 + 0.01 * i)} for i in range(7)]
    byz = [{"w": jnp.ones(6) * 100.0}, {"w": jnp.ones(6) * -80.0}]
    stacked9 = _stack(honest + byz)
    agg9, sel9 = krum_aggregate(stacked9, jnp.ones(9), n_byz=2, m=3)
    pads = [{"w": jnp.full(6, 7e7)}] * 3
    stacked12 = _stack(honest + byz + pads)
    valid = jnp.asarray([True] * 9 + [False] * 3)
    agg12, sel12 = krum_aggregate(stacked12, jnp.ones(12), n_byz=2, m=3,
                                  valid=valid, tile_size=4)
    np.testing.assert_array_equal(np.asarray(sel12)[:9], np.asarray(sel9))
    assert not np.asarray(sel12)[9:].any()
    np.testing.assert_allclose(np.asarray(agg12["w"]),
                               np.asarray(agg9["w"]), rtol=1e-5)


def test_weighted_trimmed_mean_matches_oracle():
    x = np.array([[-50.0], [1.0], [2.0], [3.0], [60.0]], np.float32)
    w = np.array([9.0, 1.0, 2.0, 3.0, 9.0], np.float32)
    got = trimmed_mean({"v": jnp.asarray(x)}, trim_ratio=0.2,
                       weights=jnp.asarray(w))
    # k=1: extremes (and their heavy weights) trimmed; weighted mean of rest
    want = (1.0 * 1 + 2.0 * 2 + 3.0 * 3) / (1 + 2 + 3)
    np.testing.assert_allclose(np.asarray(got["v"])[0], want, rtol=1e-6)
    # unweighted path unchanged: plain mean of the surviving slice
    got_u = trimmed_mean({"v": jnp.asarray(x)}, trim_ratio=0.2)
    np.testing.assert_allclose(np.asarray(got_u["v"])[0], 2.0, rtol=1e-6)


def test_trimmed_mean_tiny_cohort_guard():
    """n=2 with trim_ratio=0.5 would trim everything without the
    k <= (n-1)//2 guard; the slice must stay non-empty."""
    x = jnp.asarray([[1.0], [3.0]])
    got = trimmed_mean({"v": x}, trim_ratio=0.5)
    assert np.isfinite(np.asarray(got["v"])).all()
    np.testing.assert_allclose(np.asarray(got["v"])[0], 2.0)


def test_cross_silo_weak_dp_rng_fresh_per_round():
    """The cross-silo aggregator used to call the weak_dp defense without an
    rng (ValueError on round 0); now it folds a per-aggregation key from the
    run seed, so noise is fresh every round and seeded-reproducible."""
    from types import SimpleNamespace

    from fedml_tpu.cross_silo.aggregator import FedMLAggregator

    def build():
        args = SimpleNamespace(defense_type="weak_dp", norm_bound=100.0,
                               stddev=0.1, random_seed=0)
        return FedMLAggregator(
            None, None, 16, 2, args, {"w": jnp.zeros(400, jnp.float32)})

    agg = build()
    delta = {"w": np.ones(400, np.float32)}
    agg.add_local_trained_result(0, delta, 8)
    agg.add_local_trained_result(1, delta, 8)
    p1 = np.asarray(agg.aggregate()["w"])
    agg.add_local_trained_result(0, delta, 8)
    agg.add_local_trained_result(1, delta, 8)
    p2 = np.asarray(agg.aggregate()["w"])
    n1, n2 = p1 - 1.0, (p2 - p1) - 1.0
    assert 0.05 < n1.std() < 0.2, n1.std()
    assert not np.allclose(n1, n2)  # fresh key per round
    # seeded determinism: a rebuilt aggregator replays the same noise
    agg_b = build()
    agg_b.add_local_trained_result(0, delta, 8)
    agg_b.add_local_trained_result(1, delta, 8)
    np.testing.assert_array_equal(p1, np.asarray(agg_b.aggregate()["w"]))


def test_lagrange_interpolation_identity():
    # encoding at the defining points returns the secret rows
    X = np.arange(12, dtype=np.int64).reshape(3, 4) % DEFAULT_PRIME
    betas = [1, 2, 3]
    out = lcc_encode(X, betas, betas)
    np.testing.assert_array_equal(out, X)


def test_lcc_encode_decode_roundtrip():
    rng = np.random.RandomState(0)
    X = rng.randint(0, DEFAULT_PRIME, size=(4, 6)).astype(np.int64)
    alphas = [11, 12, 13, 14]       # secret points
    betas = [1, 2, 3, 4, 5, 6]      # share points
    shares = lcc_encode(X, betas, alphas)
    # any 4 of the 6 shares reconstruct
    keep = [0, 2, 3, 5]
    recon = lcc_decode(shares[keep], [betas[i] for i in keep], alphas)
    np.testing.assert_array_equal(recon, X)


def test_modular_inv():
    for a in (2, 17, 123456789):
        assert (a * modular_inv(a)) % DEFAULT_PRIME == 1


def test_quantize_dequantize_roundtrip():
    tree = {"w": np.array([[0.5, -0.25], [1.5, 0.0]], np.float32), "b": np.array([-3.0], np.float32)}
    vec = quantize_tree(tree, q_bits=16)
    out = dequantize_tree(vec, tree, q_bits=16)
    np.testing.assert_allclose(out["w"], tree["w"], atol=1e-4)
    np.testing.assert_allclose(out["b"], tree["b"], atol=1e-4)


def test_lightsecagg_end_to_end_sum():
    n = 6
    updates = [
        {"w": np.full((5,), 0.1 * (i + 1), np.float32), "b": np.array([float(i)], np.float32)}
        for i in range(n)
    ]
    cfg = LightSecAggConfig(
        num_clients=n, target_active=4, privacy_guarantee=1,
        model_dimension=6, q_bits=12,
    )
    active = [0, 2, 3, 5]
    agg = secure_aggregate(updates, cfg, active, seed=42)
    expected_w = sum(updates[i]["w"] for i in active)
    expected_b = sum(updates[i]["b"] for i in active)
    np.testing.assert_allclose(agg["w"], expected_w, atol=1e-2)
    np.testing.assert_allclose(agg["b"], expected_b, atol=1e-2)


def test_dp_schedule_respects_memory_and_balances():
    assignment, costs = dp_schedule(
        workloads=[10, 10, 10, 1, 1, 1], constraints=[1.0, 1.0], memory=[100, 100]
    )
    assert sorted(i for a in assignment for i in a) == list(range(6))
    assert abs(costs[0] - costs[1]) <= 10


def test_dp_schedule_infeasible_raises():
    import pytest

    with pytest.raises(ValueError):
        dp_schedule([100], [1.0], [10])


def test_even_schedule_matches_array_split():
    shards = even_client_schedule([3, 1, 4, 1, 5, 9, 2], 3)
    np.testing.assert_array_equal(shards[0], [3, 1, 4])
    assert sum(len(s) for s in shards) == 7


def test_balanced_schedule_rectangular():
    shards = balanced_client_schedule(
        [0, 1, 2, 3, 4], sample_counts=[100, 1, 1, 1, 1], n_shards=2
    )
    widths = {len(s) for s in shards}
    assert len(widths) == 1  # rectangular
    covered = {int(i) for s in shards for i in s}
    assert covered == {0, 1, 2, 3, 4}
