"""2-D federated mesh: model-axis sharding of params, opt-state, aggregation.

The simulator's mesh is promoted from 1-D (``client``) to 2-D (``client`` ×
``model``): per-leaf PartitionSpecs are inferred by the shared
largest-divisible-dim rule (parallel/sharding.py:auto_partition_specs), and
the persistent round state — global params, server opt-state, stacked
per-client rows, EF residuals, the cohort update stack, and the aggregate —
lives on the model axis end-to-end. Local training consumes a TRANSIENT
replicated view (Xu et al., arXiv:2004.13336 lazy weight gather) behind an
explicit propagation barrier, so every claim here is a parity claim: the
round history and final params are BIT-IDENTICAL to the 1-D mesh and the
unsharded path, while placement probes prove the persistent chain never
materializes unsharded.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import fedml_tpu
from fedml_tpu.parallel.mesh import AXIS_CLIENT, AXIS_MODEL, MeshConfig, create_mesh
from fedml_tpu.parallel.sharding import auto_partition_specs, shard_along
from fedml_tpu.simulation import build_simulator

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


def _run(mesh=None, **kw):
    sim, apply_fn = build_simulator(_args(**kw), mesh=mesh)
    hist = sim.run(apply_fn, log_fn=None)
    return sim, hist


def _strip_timing(hist):
    return [{k: v for k, v in rec.items() if k not in TIMING_KEYS}
            for rec in hist]


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _mesh1():
    return create_mesh(MeshConfig(axes=((AXIS_CLIENT, 2),)),
                       devices=jax.devices()[:2])


def _mesh2x2():
    return create_mesh(
        MeshConfig(axes=((AXIS_CLIENT, 2), (AXIS_MODEL, 2))),
        devices=jax.devices()[:4])


def _mesh2x4():
    return create_mesh(
        MeshConfig(axes=((AXIS_CLIENT, 2), (AXIS_MODEL, 4))),
        devices=jax.devices()[:8])


# --- spec inference: the largest-divisible-dim rule -------------------------


def test_shard_along_validates_axis_and_dim():
    mesh = _mesh1()
    sh = shard_along(mesh, AXIS_CLIENT, 0)
    assert sh.spec == P(AXIS_CLIENT)
    with pytest.raises(ValueError, match="no axis"):
        shard_along(mesh, "tensor", 0)
    with pytest.raises(ValueError, match="non-negative int"):
        shard_along(mesh, AXIS_CLIENT, -1)
    with pytest.raises(ValueError, match="non-negative int"):
        shard_along(mesh, AXIS_CLIENT, "0")


def test_auto_specs_largest_divisible_dim():
    tree = {
        "kernel": jnp.zeros((784, 10)),   # both divisible; 784 is largest
        "bias": jnp.zeros((10,)),         # divisible -> sharded
        "tall": jnp.zeros((6, 8)),        # 8 > 6 -> dim 1
        "tie": jnp.zeros((4, 4)),         # tie -> lowest dim index
        "scalar": jnp.zeros(()),          # no dims -> replicated
    }
    specs = auto_partition_specs(tree, "model", 2, warn=False)
    assert specs["kernel"] == P("model")
    assert specs["bias"] == P("model")
    assert specs["tall"] == P(None, "model")
    assert specs["tie"] == P("model")
    assert specs["scalar"] == P()


def test_auto_specs_accepts_shape_structs():
    # the simulator infers update-stack specs at trace time from
    # ShapeDtypeStructs — np.shape would choke on them
    tree = {"w": jax.ShapeDtypeStruct((16, 6), jnp.float32)}
    specs = auto_partition_specs(tree, "model", 4, warn=False)
    assert specs["w"] == P("model")


def test_auto_specs_single_warning_lists_all_fallbacks():
    tree = {"a": jnp.zeros((7,)), "b": jnp.zeros((10, 3)), "c": jnp.zeros((8,))}
    with pytest.warns(UserWarning) as rec:
        specs = auto_partition_specs(tree, "model", 4)
    ours = [w for w in rec if "auto_partition_specs" in str(w.message)]
    assert len(ours) == 1
    msg = str(ours[0].message)
    assert "'a'" in msg and "'b'" in msg
    assert specs["a"] == P() and specs["b"] == P()
    assert specs["c"] == P("model")
    # axis size 1: nothing shards, and nothing warns
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        flat = auto_partition_specs(tree, "model", 1)
    assert all(s == P() for s in jax.tree.leaves(
        flat, is_leaf=lambda x: isinstance(x, P)))


def test_auto_specs_overrides():
    tree = {"kernel": jnp.zeros((784, 10)), "bias": jnp.zeros((10,))}
    specs = auto_partition_specs(
        tree, "model", 2, overrides={"kernel": 1, "bias": None}, warn=False)
    assert specs["kernel"] == P(None, "model")
    assert specs["bias"] == P()
    with pytest.raises(ValueError, match="names dim"):
        auto_partition_specs(tree, "model", 2, overrides={"bias": 3})
    with pytest.raises(ValueError, match="not divisible"):
        auto_partition_specs(tree, "model", 4, overrides={"bias": 0})


def test_auto_specs_deterministic():
    tree = {"z": jnp.zeros((8, 4)), "a": jnp.zeros((4, 8)),
            "m": {"x": jnp.zeros((2, 2))}}
    s1 = auto_partition_specs(tree, "model", 2, warn=False)
    s2 = auto_partition_specs(tree, "model", 2, warn=False)
    assert jax.tree.structure(s1, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(s2, is_leaf=lambda x: isinstance(x, P))
    assert jax.tree.leaves(s1, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.leaves(s2, is_leaf=lambda x: isinstance(x, P))


# --- bit-identity: 2-D mesh vs 1-D mesh vs unsharded ------------------------


def test_2d_mesh_history_bit_identical():
    """The whole point of the lazy-gather design: model-axis sharding is a
    LAYOUT change, not a numerics change. History and final params from the
    2×2 mesh match the 1-D mesh bit-for-bit, with the stateful SCAFFOLD
    algorithm (server c + per-client c_local rows all live on the model
    axis). Against the UNSHARDED run the client axis splits the cohort sum
    (each shard reduced, then the shards combined): another order of float32
    additions, so that history's floats are held to 1e-5 (losses of order 1;
    the runs differ in the last ulp from round 2 on) and the rest to ==."""
    sim0, h0 = _run()
    sim1, h1 = _run(mesh=_mesh1())
    sim2, h2 = _run(mesh=_mesh2x2())
    assert _strip_timing(h1) == _strip_timing(h2)
    for r0, r1 in zip(_strip_timing(h0), _strip_timing(h1), strict=True):
        assert r0.keys() == r1.keys()
        for k in r0:
            if isinstance(r0[k], float):
                assert abs(r0[k] - r1[k]) < 1e-5, (k, r0[k], r1[k])
            else:
                assert r0[k] == r1[k], (k, r0[k], r1[k])
    # param BITS are compared mesh-to-mesh: the model axis itself must not
    # perturb a single bit
    _assert_tree_equal(sim1.params, sim2.params)
    _assert_tree_equal(sim1.server_state, sim2.server_state)
    # and the 2-D run really engaged the model axis
    assert sim2._model_axis == AXIS_MODEL
    assert sim1._model_axis is None


def test_2d_mesh_codec_ef_bit_identical():
    """EF residual arena rows carry cohort×model; the codec roundtrip is
    elementwise + exact top-k selection, so the lossy-wire history is still
    bit-identical between the 1-D and 2-D meshes."""
    common = dict(federated_optimizer="FedAvg",
                  comm_codec="delta|topk:0.25|q8")
    sim1, h1 = _run(mesh=_mesh1(), **common)
    sim2, h2 = _run(mesh=_mesh2x2(), **common)
    assert _strip_timing(h1) == _strip_timing(h2)
    _assert_tree_equal(sim1.params, sim2.params)
    assert sim2._codec_arena is not None
    for leaf in sim2._codec_arena._leaves:
        assert _spec_has_axis(leaf.sharding.spec, AXIS_MODEL)


# --- placement probes: the persistent chain never materializes unsharded ---


def _spec_has_axis(spec, axis):
    flat = []
    for part in spec:
        if isinstance(part, tuple):
            flat.extend(part)
        else:
            flat.append(part)
    return axis in flat


def test_2d_mesh_placement_probes():
    mesh = _mesh2x2()
    args = _args(comm_round=2)
    sim, apply_fn = build_simulator(args, mesh=mesh)
    seen = {}
    sim._sharding_probe = lambda tag, s: seen.setdefault(tag, s)
    sim.run(apply_fn, log_fn=None)
    # in-program probes (inspect_array_sharding reports the compiler's
    # positional form — compare semantically against the expected named
    # layout): the sharded donated jit keeps params in/out, the stacked
    # update, the aggregate, and the server opt-state on the model axis —
    # nothing in the persistent chain is ever fully replicated. Probes fire
    # on the largest leaf: the lr kernel (784, 10) -> P('model'), its
    # stacked cohort form (4, 784, 10) -> P('client', 'model').
    expect = {
        "params_in": NamedSharding(mesh, P(AXIS_MODEL)),
        "update": NamedSharding(mesh, P(AXIS_CLIENT, AXIS_MODEL)),
        "agg": NamedSharding(mesh, P(AXIS_MODEL)),
        "params_out": NamedSharding(mesh, P(AXIS_MODEL)),
        "opt_state_out": NamedSharding(mesh, P(AXIS_MODEL)),
    }
    ndim = {"update": 3}
    for tag, want in expect.items():
        assert tag in seen, f"probe {tag!r} never fired (tags: {sorted(seen)})"
        got = seen[tag]
        assert not got.is_fully_replicated, tag
        assert got.is_equivalent_to(want, ndim.get(tag, 2)), (tag, got)
    # at-rest placement between rounds matches the probes
    for tree in (sim.params, sim.server_state):
        big = max(jax.tree.leaves(tree),
                  key=lambda l: int(np.prod(l.shape)))
        assert _spec_has_axis(big.sharding.spec, AXIS_MODEL)
    # per-client arena rows: cohort axis on dim 0, model axis on the rows
    big = max(sim._arena._leaves, key=lambda l: int(np.prod(l.shape)))
    assert _spec_has_axis(big.sharding.spec, AXIS_MODEL)
    assert _spec_has_axis(big.sharding.spec, AXIS_CLIENT)


# --- sharded checkpoint: interrupt/resume stays bit-exact -------------------


def test_sharded_checkpoint_resume_parity(tmp_path):
    mesh = _mesh2x2()
    ck = dict(checkpoint_dir=str(tmp_path / "ck"), checkpoint_frequency=2,
              comm_round=4)
    sim_full, h_full = _run(mesh=_mesh2x2(), comm_round=4)
    # interrupted run: stop after round 1 (checkpoint fires at idx 1) ...
    _run(mesh=mesh, **{**ck, "comm_round": 2})
    # ... then a FRESH simulator resumes rounds 2-3 from the sharded
    # checkpoint; restore re-places host arrays under the sim's shardings
    sim_res, h_res = _run(mesh=_mesh2x2(), **ck)
    assert [r["round"] for r in h_res] == [2, 3]
    assert _strip_timing(h_res) == _strip_timing(h_full)[2:]
    _assert_tree_equal(sim_res.params, sim_full.params)
    _assert_tree_equal(sim_res.server_state, sim_full.server_state)
    big = max(jax.tree.leaves(sim_res.params),
              key=lambda l: int(np.prod(l.shape)))
    assert _spec_has_axis(big.sharding.spec, AXIS_MODEL)


# --- indivisible leaves: one warning, replicated fallback, same numerics ----


def test_indivisible_leaf_warns_once_and_stays_exact():
    """model axis 4: the lr bias (10,) has no divisible dim -> replicated
    fallback, announced by exactly ONE UserWarning naming the path; the
    kernel (784, 10) still shards (784 % 4 == 0) and the history stays
    bit-identical to the unsharded run."""
    sim1, h1 = _run(mesh=_mesh1())
    with pytest.warns(UserWarning) as rec:
        sim4, h4 = _run(mesh=_mesh2x4())
    ours = [w for w in rec if "auto_partition_specs" in str(w.message)]
    assert len(ours) == 1
    assert "bias" in str(ours[0].message)
    assert _strip_timing(h1) == _strip_timing(h4)
    _assert_tree_equal(sim1.params, sim4.params)
    leaves = {l.shape: l for l in jax.tree.leaves(sim4.params)}
    assert _spec_has_axis(leaves[(784, 10)].sharding.spec, AXIS_MODEL)
    assert leaves[(10,)].sharding.spec == P()


def test_reshard_phase_and_hbm_gauge(monkeypatch):
    """The 2-D path adds a 'reshard' phase (cohort device_put + eval params
    gather) without breaking the invariant that named phases + host_other
    sum exactly to round_time; the per-device HBM peak gauge is set when the
    backend reports memory_stats and silently absent when it doesn't (CPU
    returns None — the gauge loop must not crash on it)."""
    from fedml_tpu.core import telemetry
    telemetry.configure(enabled=True, reset=True)
    try:
        _, hist = _run(mesh=_mesh2x2())
    finally:
        snap = telemetry.get_registry().snapshot()
        telemetry.configure(enabled=False, reset=True)
    # the final round's record finalizes after the loop (deferred readback)
    # and may carry only drain-time phases — seed behavior; the reshard
    # stamps must show up across the run and NEVER break the sum invariant
    assert any("reshard" in rec["phases"] for rec in hist)
    for rec in hist:
        assert sum(rec["phases"].values()) == pytest.approx(
            rec["round_time"], rel=0.05, abs=2e-4)
    has_stats = any((jax.devices()[0].memory_stats() or {})
                    .get("peak_bytes_in_use") is not None for _ in (0,))
    gauges = [k for k in snap["gauges"]
              if k.startswith("fedml_device_hbm_peak_bytes")]
    assert bool(gauges) == has_stats


def test_resident_state_bytes_scale_inverse_model_axis():
    """A count: what a device keeps between rounds (params, server
    opt-state, the arena's per-client rows) divides by the model axis.
    Client axis 2 throughout; 25% of slack for the small leaves that no
    axis divides and that stay replicated (the lr bias, SCAFFOLD's)."""
    def resident_max(mesh):
        sim, _ = _run(mesh=mesh, comm_round=1)
        per_device = {}
        trees = [sim.params, sim.server_state, list(sim._arena._leaves)]
        for leaf in jax.tree.leaves(trees):
            for shard in leaf.addressable_shards:
                per_device[shard.device] = (
                    per_device.get(shard.device, 0) + shard.data.nbytes)
        return max(per_device.values())

    base = resident_max(_mesh1())
    for m, mesh in ((2, _mesh2x2()), (4, _mesh2x4())):
        assert resident_max(mesh) <= 1.25 * base / m, m


def test_model_shard_axis_off_disables_sharding():
    # "none" pins everything to the 1-D behavior even on a 2-D mesh
    sim, _ = _run(mesh=_mesh2x2(), comm_round=1, model_shard_axis="none")
    assert sim._model_axis is None
    for leaf in jax.tree.leaves(sim.params):
        assert not _spec_has_axis(leaf.sharding.spec, AXIS_MODEL)
