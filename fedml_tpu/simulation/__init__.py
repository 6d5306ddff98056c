"""Simulator facades, dispatching on ``args.federated_optimizer``/backend.

Parity: reference ``python/fedml/simulation/simulator.py`` —
``SimulatorSingleProcess:23``, ``SimulatorMPI:54``, ``SimulatorNCCL:206``.
Here both facades drive the same ``FedSimulator`` engine; the TPU facade
additionally builds a client-axis mesh (``SimulatorTPU`` also answers to the
reference names MPI/NCCL so reference configs run unchanged).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax

from .. import data as data_mod
from .. import models as models_mod
from ..algorithms import LocalTrainConfig, get_algorithm
from ..algorithms.local_sgd import infer_loss_kind as _infer_loss_kind
from ..parallel.mesh import AXIS_CLIENT, AXIS_MODEL, MeshConfig, create_mesh
from .async_engine import AsyncFedSimulator
from .fed_sim import (
    FedSimulator,
    SimConfig,
    pads_cohort,
    reference_client_sampling,
)
from .hierarchical import HierarchicalFedSimulator
from .decentralized import DecentralizedSimulator
from .multi_run import MultiTenantSimDriver, TenantJob, TenantRunResult

__all__ = [
    "AsyncFedSimulator",
    "FedSimulator",
    "SimConfig",
    "SimulatorSingleProcess",
    "SimulatorTPU",
    "HierarchicalFedSimulator",
    "DecentralizedSimulator",
    "MultiTenantSimDriver",
    "TenantJob",
    "TenantRunResult",
    "reference_client_sampling",
    "build_simulator",
]


def build_simulator(args, fed_data=None, model=None, mesh=None) -> tuple:
    """Shared assembly: data + model + algorithm + FedSimulator.

    ``mesh`` is a mesh, None, or a function ``pads -> mesh`` called once
    the engine is known: ``pads`` says whether that engine pads a cohort
    the client axis does not divide (:func:`fed_sim.pads_cohort`; the
    two-level and serverless engines never do).

    Returns (simulator, apply_fn).
    """
    mesh_for = ((lambda pads: mesh)
                if mesh is None or isinstance(mesh, jax.sharding.Mesh)
                else mesh)
    if fed_data is None:
        fed_data, output_dim = data_mod.load(args)
    else:
        output_dim = fed_data.class_num
    if model is None:
        model = models_mod.create(args, output_dim)
    sample = models_mod.sample_input_for(args, fed_data)
    rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
    variables = models_mod.init_params(model, rng, sample)

    def apply_fn(vars_, x, train=False, rngs=None, mutable=False):
        return model.apply(vars_, x, train=train, rngs=rngs, mutable=mutable)

    has_batch_stats = "batch_stats" in variables

    cfg = LocalTrainConfig(
        lr=float(getattr(args, "learning_rate", 0.03)),
        epochs=int(getattr(args, "epochs", 1)),
        client_optimizer=str(getattr(args, "client_optimizer", "sgd")),
        momentum=float(getattr(args, "momentum", 0.0)),
        weight_decay=float(getattr(args, "weight_decay", 0.0)),
        prox_mu=(
            None if getattr(args, "fedprox_mu", None) is None
            else float(args.fedprox_mu)
        ),
        dp_l2_clip=(
            None if getattr(args, "dp_l2_clip", None) is None
            else float(args.dp_l2_clip)
        ),
        dp_noise_multiplier=float(getattr(args, "dp_noise_multiplier", None)
                                  or 0.0),
        loss_kind=_infer_loss_kind(args, fed_data),
    )
    model_name = str(getattr(args, "model", "lr"))
    # models with live Dropout layers need a 'dropout' rng threaded through
    # training (cnn = CNN_DropOut; efficientnet-b* head dropout)
    needs_dropout = model_name in ("cnn",) or model_name.startswith("efficientnet-")
    optimizer_name = str(getattr(args, "federated_optimizer", "FedAvg"))
    sim_cfg = SimConfig(
        # the reference simulator runs 10 rounds out of the box; live
        # cross-silo managers deliberately default to a single round —
        # graftcheck: disable=config-drift
        comm_round=int(getattr(args, "comm_round", 10)),
        client_num_in_total=int(getattr(args, "client_num_in_total", 10)),
        client_num_per_round=int(getattr(args, "client_num_per_round", 10)),
        batch_size=int(getattr(args, "batch_size", 32)),
        frequency_of_the_test=int(getattr(args, "frequency_of_the_test", 5)),
        seed=int(getattr(args, "random_seed", 0)),
        checkpoint_dir=getattr(args, "checkpoint_dir", None),
        checkpoint_frequency=int(getattr(args, "checkpoint_frequency", 10)),
        resume=bool(getattr(args, "resume", True)),
        client_dropout_rate=float(getattr(args, "client_dropout_rate", 0.0)),
        cohort_schedule=str(getattr(args, "cohort_schedule", "auto")),
        packed_lanes=(
            None if getattr(args, "packed_lanes", None) is None
            else int(args.packed_lanes)
        ),
        packed_flat_carry=bool(getattr(args, "packed_flat_carry", False)),
        max_width_buckets=int(getattr(args, "max_width_buckets", 4)),
        loss_kind=cfg.loss_kind,
        local_test_on_all_clients=bool(
            getattr(args, "local_test_on_all_clients", False)),
        prefetch=bool(getattr(args, "prefetch", True)),
        prefetch_depth=int(getattr(args, "prefetch_depth", 2)),
        agg_kernels=bool(getattr(args, "agg_kernels", False)),
        sanitize_updates=bool(getattr(args, "sanitize_updates", False)),
        sanitize_z_thresh=float(getattr(args, "sanitize_z_thresh", 6.0)),
        watchdog_factor=float(getattr(args, "watchdog_factor", 0.0) or 0.0),
        watchdog_window=int(getattr(args, "watchdog_window", 5)),
        max_rollbacks=int(getattr(args, "max_rollbacks", 2)),
        rollback_z_thresh=float(getattr(args, "rollback_z_thresh", 3.0)),
        client_state_capacity=(
            None if getattr(args, "client_state_capacity", None) is None
            else int(args.client_state_capacity)
        ),
        client_state_spill_dir=getattr(args, "client_state_spill_dir", None),
        client_state_backend=str(getattr(args, "client_state_backend", "arena")),
        cohort_shard_axis=str(getattr(args, "cohort_shard_axis", AXIS_CLIENT)),
        # "none"/"off" disables model-axis sharding even on a 2-D mesh
        model_shard_axis=(
            None
            if str(getattr(args, "model_shard_axis", AXIS_MODEL) or "").lower()
            in ("", "none", "off")
            else str(getattr(args, "model_shard_axis", AXIS_MODEL))
        ),
        model_spec_overrides=getattr(args, "model_spec_overrides", None),
        # only an EXPLICIT spec engages the in-sim codec ("auto" resolves
        # per wire backend and the simulator has none; comm_quantize is a
        # cross-silo knob and must not silently change sim numerics)
        comm_codec=(
            None
            if str(getattr(args, "comm_codec", "") or "").lower()
            in ("", "none", "off", "auto")
            else str(args.comm_codec)
        ),
        # buffered-async aggregation (simulation/async_engine.py): off by
        # default — the default path stays byte-identical to the
        # synchronous engine
        async_mode=bool(getattr(args, "async_mode", False)),
        async_buffer_size=(
            None if getattr(args, "async_buffer_size", None) is None
            else int(args.async_buffer_size)
        ),
        async_staleness_alpha=float(
            getattr(args, "async_staleness_alpha", 0.5)),
        async_delay_base_s=float(getattr(args, "async_delay_base_s", 1.0)),
        async_delay_skew=float(getattr(args, "async_delay_skew", 0.0) or 0.0),
        async_delay_jitter=float(getattr(args, "async_delay_jitter", 0.2)),
        rounds_per_dispatch=int(getattr(args, "rounds_per_dispatch", 1)),
    )

    attack_type = getattr(args, "attack_type", None)
    if attack_type and optimizer_name.lower() in (
            "hierarchicalfl", "tieredfl", "decentralized"):
        raise ValueError(
            f"attack_type is wired into the FedSimulator aggregation path; "
            f"the '{optimizer_name}' engine does not support injected "
            f"attackers (running it clean would silently fake a robustness "
            f"result)")
    # two-level and serverless variants use dedicated engines
    if optimizer_name.lower() == "hierarchicalfl":
        from ..algorithms import make_local_update

        sim = HierarchicalFedSimulator(
            fed_data,
            make_local_update(apply_fn, cfg, needs_dropout, has_batch_stats),
            variables,
            sim_cfg,
            group_num=int(getattr(args, "group_num", 2)),
            group_comm_round=int(getattr(args, "group_comm_round", 2)),
            mesh=mesh_for(False),
        )
        return sim, apply_fn
    if optimizer_name.lower() == "tieredfl":
        from ..algorithms import make_local_update
        from .federation import TierConfig, TieredFedSimulator

        sim = TieredFedSimulator(
            fed_data,
            make_local_update(apply_fn, cfg, needs_dropout, has_batch_stats),
            variables,
            sim_cfg,
            tier=TierConfig.from_args(args),
            mesh=mesh_for(False),
        )
        return sim, apply_fn
    if optimizer_name.lower() == "decentralized":
        from ..algorithms import make_local_update
        from ..comm.topology import SymmetricTopologyManager

        tm = SymmetricTopologyManager(
            sim_cfg.client_num_in_total,
            neighbor_num=int(getattr(args, "topology_neighbor_num", 2)),
            seed=sim_cfg.seed,
        )
        tm.generate_topology()
        sim = DecentralizedSimulator(
            fed_data,
            make_local_update(apply_fn, cfg, needs_dropout, has_batch_stats),
            variables,
            sim_cfg, mixing_matrix=tm.topology,
            mode=str(getattr(args, "decentralized_mode", "dsgd")),
            mesh=mesh_for(False),
        )
        return sim, apply_fn

    alg = get_algorithm(
        optimizer_name,
        apply_fn,
        cfg,
        needs_dropout=needs_dropout,
        has_batch_stats=has_batch_stats,
        server_lr=float(getattr(args, "server_lr", 1.0)),
        server_optimizer=str(getattr(args, "server_optimizer", "sgd")),
        server_momentum=float(getattr(args, "server_momentum", 0.9)),
        client_fraction=float(getattr(args, "client_num_per_round", 10))
        / max(float(getattr(args, "client_num_in_total", 10)), 1.0),
        defense_type=getattr(args, "defense_type", None),
        norm_bound=float(getattr(args, "norm_bound", 5.0)),
        stddev=float(getattr(args, "stddev", 0.0)),
        trim_ratio=float(getattr(args, "trim_ratio", 0.1)),
        byzantine_n=int(getattr(args, "byzantine_n", 0)),
        multi_krum_m=(
            None if getattr(args, "multi_krum_m", None) is None
            else int(args.multi_krum_m)
        ),
        dp_seed=int(getattr(args, "random_seed", 0)),
    )
    update_transform = _make_attack_transform(alg, args) if attack_type else None
    sim_cls = AsyncFedSimulator if sim_cfg.async_mode else FedSimulator
    sim = sim_cls(
        fed_data, alg, variables, sim_cfg,
        mesh=mesh_for(pads_cohort(alg, update_transform)),
        # raw pieces for the packed cohort schedule's in-scan batch step
        packed_ctx=(apply_fn, cfg, needs_dropout, has_batch_stats),
        # reference test_on_the_server hook: an object with that method
        # (ServerAggregator subclass) replaces the default eval when truthy
        server_tester=getattr(args, "server_tester", None),
        hook_args=args,
        # MLOpsProfilerEvent (or None): emits host_pack/round_dispatch spans
        profiler=getattr(args, "profiler", None),
        update_transform=update_transform,
    )
    return sim, apply_fn


def _make_attack_transform(alg, args):
    """Adversarial-client simulation: build the ``update_transform`` hook the
    simulator applies to the stacked client updates BEFORE the sanitizer and
    any defense run (a real byzantine upload is corrupted at the client, not
    inside the server's aggregation). Deterministic attacks only
    (scale/sign_flip/nan) — the round step is traced once, so a gaussian
    attacker would freeze to one noise draw; use the library API outside jit
    for that threat model."""
    from ..core.security import FedMLAttacker

    attack_type = str(args.attack_type)
    if attack_type not in ("scale", "sign_flip", "nan"):
        raise ValueError(
            f"simulator-injected attacks support scale/sign_flip/nan, got "
            f"'{attack_type}' (gaussian needs per-round rng; drive it via "
            f"core.security outside the compiled round)")
    if not getattr(alg, "update_is_params", True):
        raise ValueError(
            f"attack injection needs params-shaped client updates; "
            f"'{alg.name}' ships a structured update (e.g. FedNova's "
            f"tau) that the attack transforms would corrupt")
    atk = FedMLAttacker(
        attack_type,
        attacker_ratio=float(getattr(args, "attacker_ratio", 0.2)),
        boost=float(getattr(args, "attack_boost", 10.0)),
        strength=float(getattr(args, "attack_strength", 1.0)),
        seed=int(getattr(args, "random_seed", 0)),
    )

    def attack_transform(stacked_updates, weights):
        return atk.attack(stacked_updates, int(weights.shape[0]))

    return attack_transform


class SimulatorSingleProcess:
    """Reference ``SimulatorSingleProcess`` (simulator.py:23)."""

    def __init__(self, args, device=None, dataset=None, model=None):
        self.sim, self.apply_fn = build_simulator(args, dataset, model, mesh=None)

    def run(self):
        return self.sim.run(self.apply_fn)


def _client_mesh(args, pads: bool):
    """The client-axis mesh over this process's devices
    (``args.model_axis_size > 1`` adds the ``model`` axis; the client axis
    takes the remaining devices). Where the engine cannot pad (``pads``
    False) the axis shrinks to a divisor of the cohort, and the devices
    that leaves out are named in a warning."""
    n_dev = len(jax.devices())
    model_axis = int(getattr(args, "model_axis_size", 1) or 1)
    if n_dev % model_axis != 0:
        raise ValueError(
            f"model_axis_size={model_axis} must divide the device "
            f"count ({n_dev})")
    n_cli = n_dev // model_axis
    per_round = int(getattr(args, "client_num_per_round", 10))
    # client axis can't exceed cohort size
    axis = min(n_cli, per_round) if per_round > 0 else n_cli
    while not pads and per_round % axis != 0:
        axis -= 1
    devices = jax.devices()[: axis * model_axis]
    axes = ((AXIS_CLIENT, axis),) + (
        ((AXIS_MODEL, model_axis),) if model_axis > 1 else ())
    mesh = create_mesh(MeshConfig(axes=axes), devices=devices)
    idle = jax.devices()[len(devices):]
    (logging.warning if idle else logging.info)(
        "SimulatorTPU: mesh %s over %d of %d devices, cohort of %d%s",
        dict(mesh.shape), len(devices), n_dev, per_round,
        f"; left idle: {idle}" if idle else "")
    return mesh


class SimulatorTPU:
    """Parrot-TPU: clients sharded over the ICI mesh (replaces SimulatorMPI /
    SimulatorNCCL, simulator.py:54,206). Without a ``mesh`` of the caller's
    it builds :func:`_client_mesh` once the engine is chosen."""

    def __init__(self, args, device=None, dataset=None, model=None, mesh=None):
        self.sim, self.apply_fn = build_simulator(
            args, dataset, model,
            mesh=mesh or functools.partial(_client_mesh, args))
        self.mesh = self.sim.mesh

    def run(self):
        return self.sim.run(self.apply_fn)
