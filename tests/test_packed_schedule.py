"""Packed-lane cohort schedule: numeric parity with the even schedule.

The packed executor trains clients back-to-back inside one scan (param reset
at boundaries). Per-client training consumes the same batches in the same
order with the same per-(pos, step) RNG folds as the even path, so final
params must match up to f32 summation order.
"""

import numpy as np
import pytest

import jax

import fedml_tpu
from fedml_tpu.core.scheduler import lane_schedule
from fedml_tpu.simulation import build_simulator


def _args(**kw):
    base = dict(
        dataset="cifar10", model="lr", partition_method="hetero",
        partition_alpha=0.3, debug_small_data=True,
        client_num_in_total=12, client_num_per_round=6, comm_round=3,
        learning_rate=0.05, epochs=1, batch_size=16,
        frequency_of_the_test=3, random_seed=0,
    )
    base.update(kw)
    return fedml_tpu.init(config=base)


def _flat(params):
    return np.concatenate(
        [np.asarray(l, np.float64).ravel() for l in jax.tree.leaves(params)])


def test_lane_schedule_covers_exactly_once():
    counts = [5, 6, 8, 8, 8, 9, 10, 11, 12, 14]
    lanes, L = lane_schedule(counts, axis=1)
    seen = sorted(p for lane in lanes for p in lane)
    assert seen == list(range(10))
    loads = [sum(counts[p] for p in lane) for lane in lanes]
    assert max(loads) == L
    # padded work must beat the trivial one-client-per-lane schedule
    assert len(lanes) * L <= len(counts) * max(counts)


def test_lane_schedule_axis_multiple():
    lanes, L = lane_schedule([4, 4, 7, 9, 3], axis=4)
    assert len(lanes) % 4 == 0
    seen = sorted(p for lane in lanes for p in lane)
    assert seen == list(range(5))


def test_lane_schedule_fewer_clients_than_axis():
    lanes, L = lane_schedule([6, 3], axis=4)
    assert len(lanes) == 4
    assert sorted(p for lane in lanes for p in lane) == [0, 1]
    assert L >= 6


def test_packed_matches_even_sp():
    args_e = _args(cohort_schedule="even")
    sim_e, apply_e = build_simulator(args_e)
    assert not sim_e._packed
    hist_e = sim_e.run(apply_e, log_fn=None)

    args_p = _args(cohort_schedule="packed")
    sim_p, apply_p = build_simulator(args_p)
    assert sim_p._packed
    hist_p = sim_p.run(apply_p, log_fn=None)

    np.testing.assert_allclose(
        _flat(sim_e.params), _flat(sim_p.params), rtol=2e-4, atol=2e-6)
    assert hist_e[-1]["test_acc"] == pytest.approx(
        hist_p[-1]["test_acc"], abs=5e-3)
    assert hist_e[-1]["train_loss"] == pytest.approx(
        hist_p[-1]["train_loss"], rel=2e-3)


def test_packed_forced_lanes_matches_even():
    """packed_lanes pins the lane count (bench-swept knob; per-step cost is
    superlinear in lanes on real chips) without changing numerics."""
    args_e = _args(cohort_schedule="even")
    sim_e, apply_e = build_simulator(args_e)
    sim_e.run(apply_e, log_fn=None)

    for lanes in (1, 2):
        args_p = _args(cohort_schedule="packed", packed_lanes=lanes)
        sim_p, apply_p = build_simulator(args_p)
        assert sim_p._packed and sim_p.cfg.packed_lanes == lanes
        sim_p.run(apply_p, log_fn=None)
        np.testing.assert_allclose(
            _flat(sim_e.params), _flat(sim_p.params), rtol=2e-4, atol=2e-6)


def test_lane_schedule_force_lanes():
    from fedml_tpu.core.scheduler import lane_schedule

    lanes, L = lane_schedule([8, 8, 4, 4], axis=1, force_lanes=2)
    assert len(lanes) == 2 and L == 12
    # force_lanes is rounded up to a multiple of the mesh axis
    lanes, L = lane_schedule([8, 8, 4, 4], axis=2, force_lanes=3)
    assert len(lanes) == 4
    # and clamped to the cohort size
    lanes, _ = lane_schedule([8, 8], axis=1, force_lanes=16)
    assert len(lanes) == 2


def test_packed_matches_even_multiepoch():
    args_e = _args(cohort_schedule="even", epochs=2, comm_round=2)
    sim_e, apply_e = build_simulator(args_e)
    sim_e.run(apply_e, log_fn=None)

    args_p = _args(cohort_schedule="packed", epochs=2, comm_round=2)
    sim_p, apply_p = build_simulator(args_p)
    sim_p.run(apply_p, log_fn=None)

    np.testing.assert_allclose(
        _flat(sim_e.params), _flat(sim_p.params), rtol=2e-4, atol=2e-6)


def test_packed_on_mesh_matches_sp():
    from fedml_tpu.parallel import AXIS_CLIENT, MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(axes=((AXIS_CLIENT, 4),)),
                       devices=jax.devices()[:4])
    args_m = _args(cohort_schedule="packed")
    sim_m, apply_m = build_simulator(args_m, mesh=mesh)
    assert sim_m._packed
    hist_m = sim_m.run(apply_m, log_fn=None)

    args_s = _args(cohort_schedule="packed")
    sim_s, apply_s = build_simulator(args_s)
    hist_s = sim_s.run(apply_s, log_fn=None)

    np.testing.assert_allclose(
        _flat(sim_s.params), _flat(sim_m.params), rtol=2e-4, atol=2e-6)
    assert np.isfinite(hist_m[-1]["test_acc"])


@pytest.mark.slow
def test_packed_mesh_size_sweep_matches_sp():
    """round-3 review #6: the packed path must compose at EVERY mesh size, with
    per-device lane shards scaling as devices grow — 2/4/8-device meshes
    all reproduce the SP result, and the lane grid G divides by the axis
    size (so each device owns G/axis lanes)."""
    from fedml_tpu.parallel import AXIS_CLIENT, MeshConfig, create_mesh

    args_s = _args(cohort_schedule="packed")
    sim_s, apply_s = build_simulator(args_s)
    sim_s.run(apply_s, log_fn=None)
    ref = _flat(sim_s.params)

    shard_lanes = {}
    for n in (2, 4, 8):
        mesh = create_mesh(MeshConfig(axes=((AXIS_CLIENT, n),)),
                           devices=jax.devices()[:n])
        args_m = _args(cohort_schedule="packed")
        sim_m, apply_m = build_simulator(args_m, mesh=mesh)
        assert sim_m._packed
        sim_m.run(apply_m, log_fn=None)
        np.testing.assert_allclose(ref, _flat(sim_m.params),
                                   rtol=2e-4, atol=2e-6)
        g, _ = sim_m._last_packed_shape
        assert g % n == 0, f"lane grid G={g} must divide mesh size {n}"
        shard_lanes[n] = g // n
    # per-device share shrinks (or stays) as the mesh grows
    assert shard_lanes[2] >= shard_lanes[4] >= shard_lanes[8] >= 1


def test_packed_flat_carry_matches_tree_carry():
    """cfg.packed_flat_carry (ravelled-vector lane carry — the v5e perf
    path) must be numerically interchangeable with the pytree carry,
    including momentum (opt-state reset at client boundaries rides the
    flat vector too) and the FedProx proximal term."""
    for extra in (dict(momentum=0.9),
                  dict(federated_optimizer="FedProx", fedprox_mu=0.1)):
        results = {}
        for flat in (False, True):
            args = _args(cohort_schedule="packed", comm_round=2,
                         packed_flat_carry=flat, **extra)
            sim, ap = build_simulator(args)
            assert sim._packed
            sim.run(ap, log_fn=None)
            results[flat] = _flat(sim.params)
        np.testing.assert_allclose(results[False], results[True],
                                   rtol=2e-5, atol=2e-7)


@pytest.mark.slow
def test_packed_flat_carry_conv_model_matches_tree():
    """Flat carry on a CONV model (the bench regime: ~many param leaves,
    the case the flat mode exists for) — parity vs tree carry, and the
    program must compile in reasonable time (regression guard for the
    unravel-in-scan path).

    Tolerance note: unlike the LR model (bit-close), conv backward
    accumulation orders differ under the re-fused flat program, and f32
    rounding differences amplify chaotically through GN/ReLU over the
    ~24 training steps — measured drift is ~6e-4 absolute after 2
    rounds, same class as the packed-vs-even tolerance."""
    results = {}
    for flat in (False, True):
        args = _args(dataset="cifar10", model="resnet8",
                     cohort_schedule="packed", comm_round=2, momentum=0.9,
                     client_num_in_total=4, client_num_per_round=3,
                     batch_size=8, packed_flat_carry=flat)
        sim, ap = build_simulator(args)
        assert sim._packed
        sim.run(ap, log_fn=None)
        results[flat] = _flat(sim.params)
    np.testing.assert_allclose(results[False], results[True],
                               rtol=1e-2, atol=2e-3)


def test_packed_with_momentum_and_prox():
    """Optimizer state reset at client boundaries: momentum must not leak
    across clients — parity vs the even path proves the reset is right."""
    for extra in (dict(momentum=0.9), dict(federated_optimizer="FedProx",
                                           fedprox_mu=0.1)):
        args_e = _args(cohort_schedule="even", comm_round=2, **extra)
        sim_e, a_e = build_simulator(args_e)
        sim_e.run(a_e, log_fn=None)
        args_p = _args(cohort_schedule="packed", comm_round=2, **extra)
        sim_p, a_p = build_simulator(args_p)
        assert sim_p._packed
        sim_p.run(a_p, log_fn=None)
        np.testing.assert_allclose(
            _flat(sim_e.params), _flat(sim_p.params), rtol=2e-4, atol=2e-6)


def test_packed_client_dropout_matches_even():
    """Dropped clients are excluded from lanes host-side; training result
    AND metric semantics (loss divided by the full cohort, dropped rows
    zero) must still match the even path, which masks them in-program."""
    args_e = _args(cohort_schedule="even", client_dropout_rate=0.5,
                   comm_round=3)
    sim_e, a_e = build_simulator(args_e)
    hist_e = sim_e.run(a_e, log_fn=None)

    args_p = _args(cohort_schedule="packed", client_dropout_rate=0.5,
                   comm_round=3)
    sim_p, a_p = build_simulator(args_p)
    hist_p = sim_p.run(a_p, log_fn=None)

    np.testing.assert_allclose(
        _flat(sim_e.params), _flat(sim_p.params), rtol=2e-4, atol=2e-6)
    for he, hp in zip(hist_e, hist_p):
        assert he["train_loss"] == pytest.approx(hp["train_loss"], rel=2e-3)


def test_packed_rejects_ineligible():
    with pytest.raises(ValueError, match="packed"):
        args = _args(cohort_schedule="packed",
                     federated_optimizer="SCAFFOLD")
        build_simulator(args)


def test_packed_checkpoint_resume_matches_uninterrupted(tmp_path):
    """Orbax resume composes with the packed executor: interrupted-at-3
    equals uninterrupted-6 exactly (round-indexed RNG/sampling)."""
    cfg = dict(
        dataset="cifar10", model="lr", partition_method="hetero",
        partition_alpha=0.3, debug_small_data=True,
        client_num_in_total=12, client_num_per_round=6, comm_round=6,
        learning_rate=0.05, epochs=1, batch_size=16,
        frequency_of_the_test=100, random_seed=0, cohort_schedule="packed",
    )
    args = fedml_tpu.init(config=dict(cfg))
    sim, _ = build_simulator(args)
    assert sim._packed
    sim.run(apply_fn=None, log_fn=None)
    full = _flat(sim.params)

    ck = str(tmp_path / "ck")
    args1 = fedml_tpu.init(config=dict(cfg, comm_round=3, checkpoint_dir=ck,
                                       checkpoint_frequency=1))
    sim1, _ = build_simulator(args1)
    sim1.run(apply_fn=None, log_fn=None)
    args2 = fedml_tpu.init(config=dict(cfg, comm_round=6, checkpoint_dir=ck,
                                       checkpoint_frequency=1))
    sim2, _ = build_simulator(args2)
    hist2 = sim2.run(apply_fn=None, log_fn=None)
    assert hist2[0]["round"] == 3
    np.testing.assert_allclose(full, _flat(sim2.params), atol=1e-5)
