"""Client-state arena + sharded cohort axis: parity, spill, scale.

The arena (simulation/client_store.py) replaces the legacy per-client dict
with fixed-capacity stacked device buffers behind a ``client_id → slot``
map. Everything here is a parity claim against the dict path it replaced —
same metrics, same params, same per-client states, bit-for-bit — plus the
scaling properties that motivated it: one jitted gather/scatter per round,
LRU spill past capacity, a mesh-sharded cohort axis, and a 1k-client round
that completes inside a tier-1 wall-clock budget.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import fedml_tpu
from fedml_tpu.data.federated import ArrayPair, build_federated_data
from fedml_tpu.parallel.mesh import AXIS_CLIENT, MeshConfig, create_mesh
from fedml_tpu.simulation import build_simulator
from fedml_tpu.simulation.client_store import ClientStateArena, cohort_local_update
from fedml_tpu.simulation.sampling import sample_clients

TIMING_KEYS = {"round_time", "dispatch_time", "pack_time", "pack_wait",
               "overlap", "phases"}


def _args(**kw):
    base = dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=12, client_num_per_round=4, comm_round=3,
        learning_rate=0.1, epochs=1, batch_size=32,
        frequency_of_the_test=2, random_seed=0,
        partition_method="hetero", partition_alpha=0.5,
        federated_optimizer="SCAFFOLD",
    )
    base.update(kw)
    return fedml_tpu.init(config=base)


def _run(**kw):
    sim, apply_fn = build_simulator(_args(**kw))
    hist = sim.run(apply_fn, log_fn=None)
    return sim, hist


def _strip_timing(hist):
    return [{k: v for k, v in rec.items() if k not in TIMING_KEYS}
            for rec in hist]


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_history_matches_across_layouts(hist_ref, hist):
    """A client mesh reduces each shard and then combines the shards: the
    cohort sum runs in another order than the unsharded one, and float32
    addition is not associative. So across LAYOUTS the history's floats are
    held to 1e-5 (losses of order 1: a few ulps a round, measured 6e-8 at
    round 2) and everything that is not a float to ``==``. Runs of one
    layout (resume, replay, spill, scan against R = 1) stay bit-exact."""
    assert len(hist_ref) == len(hist)
    for r1, r2 in zip(_strip_timing(hist_ref), _strip_timing(hist)):
        assert r1.keys() == r2.keys()
        for k in r1:
            if isinstance(r1[k], float):
                assert abs(r1[k] - r2[k]) < 1e-5, (k, r1[k], r2[k])
            else:
                assert r1[k] == r2[k], (k, r1[k], r2[k])


def _mesh2():
    return create_mesh(MeshConfig(axes=((AXIS_CLIENT, 2),)),
                       devices=jax.devices()[:2])


# --- the shared cohort vmap -------------------------------------------------


def test_cohort_local_update_matches_raw_vmap():
    def local_update(params, state, batch, rng):
        return params * batch["x"].sum() + state + jax.random.uniform(rng)

    params = jnp.asarray(2.0)
    states = jnp.arange(4, dtype=jnp.float32)
    cohort = {"x": jnp.arange(8, dtype=jnp.float32).reshape(4, 2)}
    rngs = jax.random.split(jax.random.PRNGKey(0), 4)
    got = cohort_local_update(local_update, params, states, cohort, rngs)
    want = jax.vmap(local_update, in_axes=(None, 0, 0, 0))(
        params, states, cohort, rngs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # stacked params / shared state (the hierarchical/decentralized shape)
    sp = jnp.arange(4, dtype=jnp.float32)
    got2 = cohort_local_update(local_update, sp, jnp.asarray(0.5), cohort,
                               rngs, params_axis=0, state_axis=None)
    want2 = jax.vmap(local_update, in_axes=(0, None, 0, 0))(
        sp, jnp.asarray(0.5), cohort, rngs)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


# --- arena vs dict: bit-exact parity ----------------------------------------


def test_arena_matches_dict_backend_bit_exact():
    """Same history, params, and per-client states as the dict path, to the
    bit — the arena is a storage layout change, not a numeric one."""
    sim_a, hist_a = _run()
    sim_d, hist_d = _run(client_state_backend="dict")
    assert sim_a._arena is not None and sim_d._arena is None
    assert _strip_timing(hist_a) == _strip_timing(hist_d)
    _assert_tree_equal(sim_a.params, sim_d.params)
    assert sim_d.client_states  # SCAFFOLD is stateful — dict path populated
    for cid, st in sim_d.client_states.items():
        _assert_tree_equal(sim_a._arena.state_of(cid), st)


def test_arena_spill_and_reload_bit_exact(tmp_path):
    """Capacity below the touched-client count forces LRU eviction to host
    RAM and (host_capacity == capacity) to msgpack files; resampled clients
    reload through both tiers with no numeric trace."""
    sim_d, hist_d = _run(client_state_backend="dict", comm_round=6)
    sim_a, hist_a = _run(comm_round=6, client_state_capacity=5,
                         client_state_spill_dir=str(tmp_path / "spill"))
    arena = sim_a._arena
    assert arena.capacity == 5
    assert arena.spilled_count > 0, "run never exercised the spill tier"
    assert _strip_timing(hist_a) == _strip_timing(hist_d)
    _assert_tree_equal(sim_a.params, sim_d.params)
    for cid, st in sim_d.client_states.items():
        _assert_tree_equal(arena.state_of(cid), st)


def test_arena_reload_actually_round_trips(tmp_path):
    """Unit-level spill/reload: scatter distinct rows through a 2-slot
    arena, then read every client back — including ones that went through
    the disk tier."""
    proto = {"a": jnp.zeros((3,)), "b": jnp.zeros(())}
    arena = ClientStateArena(proto, 2, spill_dir=str(tmp_path),
                             host_capacity=2)
    for cid in range(6):
        arena.gather([cid])
        arena.scatter([cid], {"a": jnp.full((1, 3), float(cid)),
                              "b": jnp.asarray([float(cid) * 10])})
    assert arena.spilled_count == 4
    for cid in range(6):
        st = arena.state_of(cid)
        np.testing.assert_array_equal(np.asarray(st["a"]), np.full(3, cid))
        np.testing.assert_array_equal(np.asarray(st["b"]), cid * 10)
    # batched re-gather of the two disk-tier clients (0 and 1 are the LRU
    # victims pushed past host_capacity) loads them back in one scatter
    stacked = arena.gather([0, 1])
    np.testing.assert_array_equal(
        np.asarray(stacked["b"]), np.asarray([0.0, 10.0]))
    # an oversize cohort is a hard error, not silent thrash
    with pytest.raises(ValueError, match="slots"):
        arena.gather(list(range(6)))


def test_arena_discard_reclaims_every_tier_including_stale_files(tmp_path):
    """Permanent departure (cross-device churn) must not leak: the slot,
    the host row, the live spill file, AND the stale-but-inert file left
    behind when a disk-tier client was merely read back all go away."""
    proto = {"a": jnp.zeros((3,))}
    arena = ClientStateArena(proto, 2, spill_dir=str(tmp_path),
                             host_capacity=2)
    for cid in range(6):
        arena.gather([cid])
        arena.scatter([cid], {"a": jnp.full((1, 3), float(cid))})
    # reading client 0 back promotes it to a device slot but deliberately
    # leaves its file on disk (inert — _on_disk is the source of truth)
    arena.gather([0])
    files = lambda: sorted(p.name for p in tmp_path.glob("client_*.msgpack"))
    assert "client_0.msgpack" in files()
    before = arena.spilled_count
    # clients 0 (resident again, stale file), 1 and 2 (disk tier) depart;
    # duplicate and never-seen ids are harmless
    reclaimed = arena.discard([0, 1, 2, 2, 99])
    assert reclaimed == 3          # 0's stale file + 1's and 2's live files
    assert files() == []           # every file for the departed is gone
    assert arena.spilled_count < before
    # departed clients are fully forgotten: they read back as fresh proto
    for cid in (0, 1, 2):
        np.testing.assert_array_equal(
            np.asarray(arena.state_of(cid)["a"]), np.zeros(3))
    # survivors are untouched across all tiers
    for cid in (3, 4, 5):
        np.testing.assert_array_equal(
            np.asarray(arena.state_of(cid)["a"]), np.full(3, cid))


def test_arena_checkpoint_resume_bit_exact(tmp_path):
    """Interrupted-at-2 resume == uninterrupted run: the checkpoint carries
    the whole arena (slots, map, clock, spilled rows)."""
    kw = dict(comm_round=4, frequency_of_the_test=100)
    sim_full, _ = _run(**kw)
    ck = str(tmp_path / "ck")
    _run(**dict(kw, comm_round=2, checkpoint_dir=ck, checkpoint_frequency=1))
    sim_res, hist_res = _run(**dict(kw, checkpoint_dir=ck,
                                    checkpoint_frequency=1))
    assert hist_res[0]["round"] == 2
    _assert_tree_equal(sim_full.params, sim_res.params)
    for cid in range(12):
        _assert_tree_equal(sim_full._arena.state_of(cid),
                           sim_res._arena.state_of(cid))


def test_arena_capacity_below_cohort_rejected():
    with pytest.raises(ValueError, match="client_state_capacity"):
        build_simulator(_args(client_state_capacity=3))


def test_arena_watchdog_plus_disk_spill_rejected():
    with pytest.raises(ValueError, match="watchdog"):
        build_simulator(_args(client_state_spill_dir="/tmp/never",
                              watchdog_factor=3.0))


def test_arena_unknown_backend_rejected():
    with pytest.raises(ValueError, match="client_state_backend"):
        build_simulator(_args(client_state_backend="redis"))


def test_arena_selfheal_rollback_parity():
    """The watchdog snapshot/restore covers the arena: a run under the
    watchdog (no rollbacks triggered at sane thresholds) matches dict."""
    kw = dict(watchdog_factor=100.0, comm_round=3)
    sim_a, hist_a = _run(**kw)
    sim_d, hist_d = _run(client_state_backend="dict", **kw)
    assert _strip_timing(hist_a) == _strip_timing(hist_d)
    _assert_tree_equal(sim_a.params, sim_d.params)


# --- mesh-sharded cohort axis -----------------------------------------------


def test_mesh_history_bit_identical_and_never_unsharded():
    """2-device client mesh: the round history of the unsharded run (floats
    to reduction-order noise, the rest equal: the helper says why), and the
    stacked update entering aggregation is asserted (via sharding
    inspection inside the compiled step) to never materialize unsharded."""
    sim1, hist1 = _run()
    seen = {}
    mesh = _mesh2()
    sim2, apply_fn = build_simulator(_args(), mesh=mesh)
    sim2._sharding_probe = lambda tag, s: seen.setdefault(tag, s)
    hist2 = sim2.run(apply_fn, log_fn=None)
    assert not seen["update"].is_fully_replicated, \
        "stacked update materialized unsharded inside the round step"
    assert seen["agg"].is_fully_replicated
    _assert_history_matches_across_layouts(hist1, hist2)
    # params agree to cross-device reduction-order noise (the mesh run
    # reduces per-shard then combines; same tolerance class as the
    # pre-arena mesh path)
    for a, b in zip(jax.tree.leaves(sim1.params), jax.tree.leaves(sim2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_mesh_padded_cohort_matches_unsharded():
    """per_round=5 on a 2-device mesh pads the cohort to 6; the padded row
    carries zero weight and a duplicated id, so results match the unsharded
    5-client run."""
    kw = dict(client_num_per_round=5, federated_optimizer="FedAvg")
    _, hist1 = _run(**kw)
    sim2, apply_fn = build_simulator(_args(**kw), mesh=_mesh2())
    assert sim2._cohort_pad == 1
    hist2 = sim2.run(apply_fn, log_fn=None)
    _assert_history_matches_across_layouts(hist1, hist2)


def test_mesh_padding_with_attack_rejected():
    """Padded rows entering a custom update transform would corrupt it —
    the combination must refuse at build time, not silently mis-aggregate."""
    with pytest.raises(ValueError, match="padding|multiple"):
        build_simulator(
            _args(client_num_per_round=5, federated_optimizer="FedAvg",
                  attack_type="scale"),
            mesh=_mesh2())


# --- pure per-round sampling ------------------------------------------------


def test_sample_clients_pure_and_deterministic():
    before = np.random.get_state()
    a = sample_clients(7, 3, 1000, 10)
    after = np.random.get_state()
    # no draw from (or reseed of) the process-global stream
    assert before[0] == after[0]
    np.testing.assert_array_equal(before[1], after[1])
    assert before[2:] == after[2:]
    np.testing.assert_array_equal(a, sample_clients(7, 3, 1000, 10))
    assert len(np.unique(a)) == 10 and a.max() < 1000
    # distinct rounds and seeds draw distinct cohorts
    assert not np.array_equal(a, sample_clients(7, 4, 1000, 10))
    assert not np.array_equal(a, sample_clients(8, 3, 1000, 10))
    np.testing.assert_array_equal(
        sample_clients(7, 0, 10, 10), np.arange(10))


# --- scale smoke ------------------------------------------------------------


def test_thousand_client_round_under_budget():
    """1000-client sampled SCAFFOLD round (arena gather → vmap → sharded-
    style aggregation → scatter) completes — compile included — inside a
    tier-1 budget."""
    pool, spc, dim = 2000, 8, 16
    rng = np.random.default_rng(0)
    n = pool * spc
    y = (np.arange(n) % 2).astype(np.int64)
    x = rng.normal(size=(n, dim)).astype(np.float32) \
        + 2.0 * y[:, None].astype(np.float32)
    net_map = {c: list(range(c * spc, (c + 1) * spc)) for c in range(pool)}
    fed = build_federated_data(
        ArrayPair(x, y), ArrayPair(x[:64], y[:64]), net_map, 2)
    args = _args(client_num_in_total=pool, client_num_per_round=1000,
                 comm_round=1, batch_size=spc, frequency_of_the_test=100,
                 dataset="synthetic_blobs")
    t0 = time.perf_counter()
    sim, _ = build_simulator(args, fed_data=fed)
    assert sim._arena is not None
    hist = sim.run(apply_fn=None, log_fn=None)
    wall = time.perf_counter() - t0
    assert len(hist) == 1 and np.isfinite(hist[0]["train_loss"])
    assert sim._arena.resident_count == 1000
    assert wall < 60.0, f"1k-client round took {wall:.1f}s (budget 60s)"
