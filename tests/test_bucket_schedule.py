"""Workload-aware cohort scheduling (review #10): the DP bucket scheduler
wired into FedSimulator cuts padded compute for skewed cohorts while
matching the even path's aggregation numerics."""

import time

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.core.scheduler import bucket_schedule, dp_schedule
from fedml_tpu.data import load as load_data
from fedml_tpu.parallel import AXIS_CLIENT, MeshConfig, create_mesh
from fedml_tpu.simulation import build_simulator


def test_bucket_schedule_partitions_and_cuts_padding():
    # 12 tiny clients (1 batch) + 4 huge (32 batches), axis 4
    counts = [1] * 12 + [32] * 4
    buckets = bucket_schedule(counts, axis=4, max_buckets=4)
    covered = np.sort(np.concatenate([p for p, _ in buckets]))
    np.testing.assert_array_equal(covered, np.arange(16))
    # padded cost: even = 16 slots * 32 wide = 512; optimal split
    # {12 small} + {4 big} costs 12*1 + 4*32 = 140
    cost = sum((-(-len(p) // 4)) * 4 * w for p, w in buckets)
    assert cost <= 12 * 1 + 4 * 32
    widths = [w for _, w in buckets]
    assert widths == sorted(widths)


def test_bucket_schedule_single_bucket_uniform():
    buckets = bucket_schedule([5, 5, 5, 5], axis=2, max_buckets=4)
    # widths quantize up to powers of two (compile-cache stability)
    assert len(buckets) == 1 and buckets[0][1] == 8


def test_bucket_schedule_respects_width_cap():
    # a 47-batch client must NOT have its width quantized past the caller's
    # per-client batch cap (that would silently raise its training budget
    # and aggregation weight vs the even path)
    buckets = bucket_schedule([1, 1, 47], axis=1, max_buckets=2, max_width=24)
    assert max(w for _, w in buckets) == 24


def test_dp_schedule_balances_makespan():
    assignment, costs = dp_schedule(
        [10, 9, 8, 1, 1, 1], np.ones(3), np.full(3, np.inf)
    )
    assert sorted(sum(assignment, [])) == list(range(6))
    assert costs.max() <= 11  # LPT bound; optimal makespan is 10


def _skewed_args(schedule: str, rounds: int = 2):
    return fedml_tpu.init(config=dict(
        dataset="synthetic_skewed", model="lr", debug_small_data=True,
        client_num_in_total=32, client_num_per_round=32, comm_round=rounds,
        learning_rate=0.1, epochs=1, batch_size=256,
        frequency_of_the_test=100, random_seed=0,
        cohort_schedule=schedule, backend="TPU",
    ))


@pytest.fixture(scope="module")
def skewed_fed_data():
    """16 clients, heavy-tailed sizes: 12 with ~1 batch, 4 with ~24 batches."""
    from fedml_tpu.data.federated import ArrayPair, build_federated_data

    rng = np.random.default_rng(0)
    # big enough that compute dominates dispatch overhead on the test mesh:
    # even mode pads 24 one-batch clients to the 24-batch width; the 8 heavy
    # clients align with the 8-device axis so the heavy bucket carries no
    # dead slots. INTERLEAVED on purpose: the bucketed schedule reorders
    # this cohort, so the numerics test below proves schedule-independent
    # shuffles/RNG (a sorted fixture would mask ordering bugs).
    sizes = [64, 64, 64, 6100] * 8
    total = sum(sizes)
    x = rng.normal(size=(total, 2048)).astype(np.float32)
    w = rng.normal(size=(2048,))
    y = (x @ w > 0).astype(np.int64)
    idx_map, start = {}, 0
    for c, n in enumerate(sizes):
        idx_map[c] = list(range(start, start + n))
        start += n
    tx = rng.normal(size=(64, 2048)).astype(np.float32)
    ty = (tx @ w > 0).astype(np.int64)
    return build_federated_data(
        ArrayPair(x, y), ArrayPair(tx, ty), idx_map, class_num=2
    )


def _run(schedule, fed_data, mesh):
    args = _skewed_args(schedule)
    sim, apply_fn = build_simulator(args, fed_data=fed_data, mesh=mesh)
    hist = sim.run(apply_fn, log_fn=None)
    return sim, hist


def test_bucketed_matches_even_numerics(skewed_fed_data):
    mesh = create_mesh(MeshConfig(axes=((AXIS_CLIENT, 4),)),
                       devices=jax.devices()[:4])
    sim_even, _ = _run("even", skewed_fed_data, mesh)
    sim_bkt, hist = _run("bucketed", skewed_fed_data, mesh)
    assert sim_bkt._bucketed
    leaves_e = jax.tree.leaves(sim_even.params)
    leaves_b = jax.tree.leaves(sim_bkt.params)
    for a, b in zip(leaves_e, leaves_b):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5,
            err_msg="bucketed aggregation diverged from even path",
        )
    assert np.isfinite(hist[-1]["train_loss"])


@pytest.mark.slow
def test_bucketed_beats_even_on_skewed_cohort(skewed_fed_data):
    """The done-criterion: on the 8-device mesh a skewed cohort's round time
    under the DP schedule beats the even (pad-to-max) placement."""
    mesh = create_mesh(MeshConfig(axes=((AXIS_CLIENT, 8),)),
                       devices=jax.devices()[:8])

    def timed(schedule, rounds=6):
        args = _skewed_args(schedule, rounds=rounds)
        sim, apply_fn = build_simulator(args, fed_data=skewed_fed_data, mesh=mesh)
        # wall-to-wall including compile (run() drains the async dispatch
        # queue before returning, so this wall-clock is honest — per-round
        # timers are not, see FedSimulator.run). The bucketed side compiles
        # MORE programs (one per width class + finalize), so the win below
        # is in spite of its compile handicap.
        t0 = time.perf_counter()
        sim.run(apply_fn, log_fn=None)
        return (time.perf_counter() - t0) / rounds

    t_even = timed("even")
    t_bucketed = timed("bucketed")
    # 24/32 clients are ~24x overpadded in even mode; demand a real win
    assert t_bucketed < 0.75 * t_even, (t_bucketed, t_even)
