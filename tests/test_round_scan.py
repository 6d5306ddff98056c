"""Compiled multi-round dispatch (``rounds_per_dispatch``): bit-exact
parity with the per-round engine, block planning at hook boundaries,
checkpoint resume from mid-block indices, typed incompatibility errors,
amortized phase accounting, and the one-compile-per-(R, shapes) guard.
"""

from __future__ import annotations

import math
import tempfile

import numpy as np
import pytest

import jax

import fedml_tpu
from fedml_tpu.core import telemetry
from fedml_tpu.simulation import build_simulator
from fedml_tpu.simulation.fed_sim import ScanIncompatibleError

# timing keys vary run to run; everything else must match bit for bit
TIMING_KEYS = {"round_time", "dispatch_time", "pack_time", "pack_wait",
               "overlap", "phases", "scan_rounds"}


def _args(**kw):
    base = dict(
        dataset="cifar10", model="lr", partition_method="hetero",
        partition_alpha=0.3, debug_small_data=True,
        client_num_in_total=12, client_num_per_round=6, comm_round=7,
        learning_rate=0.05, epochs=1, batch_size=16,
        frequency_of_the_test=100, random_seed=0,
    )
    base.update(kw)
    return fedml_tpu.init(config=base)


def _flat(params):
    return np.concatenate(
        [np.asarray(l, np.float64).ravel() for l in jax.tree.leaves(params)])


def _run(**kw):
    sim, apply_fn = build_simulator(_args(**kw))
    hist = sim.run(apply_fn, log_fn=None)
    stripped = [{k: v for k, v in r.items() if k not in TIMING_KEYS}
                for r in hist]
    return sim, hist, stripped


# ------------------------------------------------------------ bit-exactness

@pytest.mark.parametrize("kw", [
    pytest.param(dict(sanitize_updates=True), id="fedavg_sanitize"),
    pytest.param(dict(federated_optimizer="SCAFFOLD"), id="scaffold_arena"),
    pytest.param(dict(comm_codec="delta|topk:0.01|q8"), id="codec_ef_carry"),
    pytest.param(dict(client_dropout_rate=0.3), id="dropout"),
])
def test_scanned_history_bit_exact_vs_per_round(kw):
    # eval fires at round 0 and the last round, so the 7-round plan holds
    # a length-1 block, a full block, and a truncated tail block — SCAFFOLD
    # arena rows and codec EF residuals must carry across all three
    s1, _, h1 = _run(**kw)
    s4, _, h4 = _run(rounds_per_dispatch=4, **kw)
    assert np.array_equal(_flat(s1.params), _flat(s4.params))
    assert h1 == h4


def test_scan_blocks_split_at_eval_rounds():
    kw = dict(sanitize_updates=True, frequency_of_the_test=2)
    s1, _, h1 = _run(**kw)
    s4, raw4, h4 = _run(rounds_per_dispatch=4, **kw)
    assert np.array_equal(_flat(s1.params), _flat(s4.params))
    assert h1 == h4
    # eval rounds (0, 2, 4, 6) each end their block: the plan is
    # [0], [1,2], [3,4], [5,6] — never a scanned block spanning an eval
    by_round = {r["round"]: r for r in raw4}
    assert "scan_rounds" not in by_round[0]          # length-1 → per-round
    for r in (1, 2, 3, 4, 5, 6):
        assert by_round[r]["scan_rounds"] == 2


def test_scan_blocks_split_at_checkpoint_rounds(tmp_path):
    def kw(sub):
        d = tmp_path / sub
        d.mkdir()
        return dict(federated_optimizer="SCAFFOLD", checkpoint_dir=str(d),
                    checkpoint_frequency=3, frequency_of_the_test=1000,
                    resume=False)

    s1, _, h1 = _run(**kw("per_round"))
    s4, raw4, h4 = _run(rounds_per_dispatch=4, **kw("scan"))
    assert np.array_equal(_flat(s1.params), _flat(s4.params))
    assert h1 == h4
    # round 0 always evals, checkpoints land after rounds 2 and 5 → the
    # plan is [0], [1,2], [3,4,5], [6]
    by_round = {r["round"]: r for r in raw4}
    assert "scan_rounds" not in by_round[0]
    assert "scan_rounds" not in by_round[6]
    for r in (1, 2):
        assert by_round[r]["scan_rounds"] == 2
    for r in (3, 4, 5):
        assert by_round[r]["scan_rounds"] == 3


def test_checkpoint_resume_mid_plan_matches_per_round():
    outs = {}
    for tag, rpd in (("per_round", 1), ("scan", 4)):
        with tempfile.TemporaryDirectory() as d:
            kw = dict(federated_optimizer="SCAFFOLD", checkpoint_dir=d,
                      checkpoint_frequency=3, rounds_per_dispatch=rpd)
            _run(comm_round=3, **kw)  # writes the round-2 checkpoint
            # resume restarts at round 3 — NOT a multiple of R=4, so the
            # scan plan must re-anchor mid-block
            s, _, h = _run(comm_round=7, resume=True, **kw)
            outs[tag] = (_flat(s.params), h)
    assert np.array_equal(outs["per_round"][0], outs["scan"][0])
    assert outs["per_round"][1] == outs["scan"][1]


def test_arena_capacity_overflow_falls_back_per_round():
    # a 4-round slot union larger than the arena forces the block onto the
    # per-round path — still bit-exact, never a wrong-slot scatter
    kw = dict(federated_optimizer="SCAFFOLD", client_state_capacity=7)
    s1, _, h1 = _run(**kw)
    s4, raw4, h4 = _run(rounds_per_dispatch=4, **kw)
    assert np.array_equal(_flat(s1.params), _flat(s4.params))
    assert h1 == h4
    assert all("scan_rounds" not in r for r in raw4)


def test_block_packer_matches_per_round_packing():
    # the vectorized block packer must reproduce the per-round packer's
    # rectangles exactly: same shuffles, same dropout, same index rows
    sim, _ = build_simulator(_args(client_dropout_rate=0.25,
                                   rounds_per_dispatch=3))
    rounds = (1, 2, 3)
    blk = sim.build_block_inputs(rounds)
    for k, r in enumerate(rounds):
        ri = sim.build_round_inputs(r)
        c_real = len(ri.client_ids)
        assert np.array_equal(blk.ids[k], np.asarray(ri.client_ids))
        assert np.array_equal(blk.xs["idx"][k, :c_real],
                              ri.payload["idx"].astype(np.int32))
        ns = np.asarray(ri.payload["num_samples"])
        assert np.array_equal(blk.xs["num_samples"][k, :c_real],
                              ns.astype(np.int32))
        # the in-scan mask rebuild: arange(bs) < num_samples row-wise
        nb, bs = ri.payload["mask"].shape[1:]
        rebuilt = (np.arange(nb * bs)[None, :]
                   < ns[:, None]).astype(np.float32).reshape(-1, nb, bs)
        assert np.array_equal(rebuilt, ri.payload["mask"])


# ------------------------------------------------------- typed incompatibility

@pytest.mark.parametrize("kw", [
    pytest.param(dict(watchdog_factor=3.0), id="watchdog"),
    pytest.param(dict(attack_type="sign_flip", byzantine_client_num=1),
                 id="attack_transform"),
    pytest.param(dict(federated_optimizer="SCAFFOLD",
                      client_state_capacity=8,
                      client_state_spill_dir="__tmp_spill__"),
                 id="disk_spill_arena"),
    pytest.param(dict(federated_optimizer="SCAFFOLD",
                      client_state_backend="dict"), id="dict_state_backend"),
    pytest.param(dict(cohort_schedule="packed"), id="packed_schedule"),
    pytest.param(dict(async_mode=True), id="async_engine"),
])
def test_incompatible_configs_rejected_typed(kw, tmp_path):
    if "client_state_spill_dir" in kw:
        kw = dict(kw, client_state_spill_dir=str(tmp_path))
    with pytest.raises(ScanIncompatibleError):
        build_simulator(_args(rounds_per_dispatch=4, **kw))


def test_scan_incompatible_error_is_a_value_error():
    # callers catching the PR-6 mesh-refusal pattern keep working
    assert issubclass(ScanIncompatibleError, ValueError)


def test_rounds_per_dispatch_below_one_rejected():
    with pytest.raises(ValueError):
        build_simulator(_args(rounds_per_dispatch=0))


def test_rounds_per_dispatch_typo_rejected_at_config_load():
    # a YAML typo fails at load_arguments naming the key, not as a
    # TypeError deep inside SimConfig construction
    with pytest.raises(ValueError, match="rounds_per_dispatch"):
        fedml_tpu.init(config=dict(rounds_per_dispatch="4x"))


def test_multi_tenant_round_gate_rejected_at_run():
    sim, apply_fn = build_simulator(_args(rounds_per_dispatch=4))
    sim._round_gate = lambda r: None  # what multi_run's scheduler installs
    with pytest.raises(ScanIncompatibleError):
        sim.run(apply_fn, log_fn=None)


def test_robust_defense_stays_scan_compatible():
    # a Krum-family robust aggregator is pure XLA inside the round body —
    # must NOT be refused, and must stay bit-exact under fusion
    kw = dict(federated_optimizer="FedAvg_Robust", defense_type="krum",
              byzantine_n=1)
    s1, _, h1 = _run(**kw)
    s4, raw4, h4 = _run(rounds_per_dispatch=4, **kw)
    assert any(r.get("scan_rounds") for r in raw4)
    assert np.array_equal(_flat(s1.params), _flat(s4.params))
    assert h1 == h4


# ------------------------------------------------------------- telemetry

def test_amortized_phases_sum_exactly_to_round_time():
    reg = telemetry.get_registry()
    blocks_before = reg.counter("fedml_scan_blocks_total").value
    _, raw4, _ = _run(rounds_per_dispatch=4, sanitize_updates=True)
    scanned = [r for r in raw4 if "scan_rounds" in r]
    assert scanned, "expected at least one fused block"
    for r in raw4:
        assert math.isclose(sum(r["phases"].values()), r["round_time"],
                            rel_tol=1e-6, abs_tol=1e-9)
    for r in scanned:
        assert {"pack_wait", "scan_pack", "dispatch",
                "device"} <= set(r["phases"])
    # plan for 7 rounds with eval at 0 and 6: [0], [1..4], [5,6] → 2 fused
    blocks = reg.counter("fedml_scan_blocks_total").value - blocks_before
    assert blocks == 2


def test_one_compilation_per_R_and_shapes():
    # the same (R, shapes) pair across MORE blocks must not compile again:
    # 13 rounds plan [0],[1-4],[5-8],[9-12] reuses the length-4 program
    # twice more than 7 rounds' [0],[1-4],[5,6] adds a length-2 tail
    def _compiles(comm_round):
        reg = telemetry.get_registry()
        before = reg.counter_total("fedml_jax_programs_total")
        _run(rounds_per_dispatch=4, comm_round=comm_round)
        return reg.counter_total("fedml_jax_programs_total") - before

    base = _compiles(7)    # block lengths {1, 4, 2}
    again = _compiles(15)  # block lengths {1, 4, 4, 4, 2} — same programs
    assert 0 < base and again <= base


def test_default_rounds_per_dispatch_is_classic_path():
    sim, _ = build_simulator(_args())
    assert sim._scan_rounds == 1
    _, raw, _ = _run()
    assert all("scan_rounds" not in r for r in raw)
