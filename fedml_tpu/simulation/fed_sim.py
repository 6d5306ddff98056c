"""The federated simulator: one engine, two placements.

Replaces all three reference simulators (SURVEY.md §2.3):

- **SP** (`mesh=None`): the whole cohort's local training is one XLA program —
  ``vmap(local_update)`` over the client axis + weighted-mean aggregation +
  server update, jitted together. Reference equivalent:
  ``simulation/sp/fedavg/fedavg_api.py:81`` (a sequential Python loop there).
- **Parrot-TPU** (`mesh=Mesh(..., 'client')`): the *same* jitted round step
  with cohort arrays sharded over the ``client`` mesh axis and params
  replicated; GSPMD turns the weighted mean into an ICI all-reduce. This is
  the reference NCCL simulator (``nccl/base_framework/Server.py:153``:
  broadcast -> schedule -> local train -> SUM reduce) collapsed into one
  compiled program: the broadcast is sharding, the reduce is a psum.

Client sampling is ``sampling.sample_clients`` — a pure function of
(seed, round) drawing from a per-round ``np.random.default_rng`` stream, so
cohorts are reproducible without touching the process-global RNG (the
reference's global ``np.random.seed(round_idx)`` sampler lives on in
``sampling.reference_client_sampling`` for the cross-silo server and parity
harnesses).

Per-client algorithm state (SCAFFOLD control variates etc.) lives in a
``client_store.ClientStateArena`` when available: a fixed-capacity stacked
device arena whose cohort gather/scatter is two jitted index ops, with LRU
spill to host RAM / disk for registries larger than
``client_state_capacity``. ``client_state_backend="dict"`` keeps the legacy
per-client host dict as the bit-exactness oracle.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry, trace_plane
from ..core.algframe import FedAlgorithm
from ..data.federated import FederatedData
from ..algorithms.local_sgd import make_eval_fn
from ..parallel.mesh import AXIS_CLIENT, AXIS_MODEL
from ..parallel.sharding import (
    auto_partition_specs,
    prepend_axis,
    replicated,
    shard_along,
    tree_shardings,
)
from .client_store import ClientStateArena, cohort_local_update
from .sampling import (  # noqa: F401 (re-export)
    client_permutation_list,
    client_permutations,
    reference_client_sampling,
    sample_clients,
)

PyTree = Any


@dataclasses.dataclass
class SimConfig:
    comm_round: int = 10
    client_num_in_total: int = 10
    client_num_per_round: int = 10
    batch_size: int = 32
    frequency_of_the_test: int = 5
    eval_batch_size: int = 256
    seed: int = 0
    # fix the per-client batch count for a stable compiled shape; None =
    # derive from the largest client (padding+mask covers the rest)
    num_local_batches: Optional[int] = None
    # packed schedule: force the lane count (None = the G*L cost search in
    # core/scheduler.lane_schedule). The hypothesis it serves (ROADMAP S1,
    # its evidence predates the ledger): per-step cost is superlinear in
    # lane count (per-lane weights lower to grouped convs, whose thin
    # per-group channels starve the MXU), so fewer, longer lanes could beat
    # the padded-work optimum. No benchmark cell sets it.
    packed_lanes: Optional[int] = None
    # flat-carry packed executor: the lane scan carries params/opt-state/
    # delta as ONE ravelled vector instead of a ~170-leaf pytree;
    # numerically parity-exact (same elementwise math,
    # tests/test_packed_schedule.py). Off by default and turned on by tests
    # only: which carry is faster end to end is not measured (ROADMAP D2).
    packed_flat_carry: bool = False
    # checkpoint/resume (orbax; the reference has none — SURVEY.md §5.4)
    checkpoint_dir: Optional[str] = None
    checkpoint_frequency: int = 10
    resume: bool = True
    # fault injection (ours; reference has no fault injection — SURVEY.md
    # §5.3): each round, each sampled client crashes with this probability —
    # its weight and mask zero out, so it contributes nothing, like a worker
    # dying mid-round. At least one client always survives.
    client_dropout_rate: float = 0.0
    # device-resident data: upload the global train arrays to HBM once and
    # gather each round's cohort INSIDE the compiled step from a small index
    # tensor — the per-round host->device transfer drops from the full
    # cohort (e.g. ~180 MB for 10 CIFAR clients) to a few KB of indices.
    # Auto-disabled when the dataset exceeds the byte budget or per-client
    # arrays diverge from the global ones (poisoned clients).
    device_data: bool = True
    device_data_max_bytes: int = 4 << 30
    # cohort scheduling (reference core/schedule/scheduler.py role):
    # "even"     — one rectangular program, every client padded to the
    #              cohort-max batch count (fastest for uniform cohorts);
    # "bucketed" — split the cohort into width-classes via the exact DP in
    #              core.scheduler.bucket_schedule and run one partial-agg
    #              program per class: skewed cohorts stop paying the
    #              max-width padding for every small client (a Dirichlet
    #              CIFAR cohort averages ~8 batches/client but pads to the
    #              ~24-batch max — a 3x compute waste bucketing removes);
    # "packed"   — ONE compiled program per round: clients are packed
    #              back-to-back into a few balanced lanes
    #              (core.scheduler.lane_schedule) and a single scan trains
    #              them sequentially per lane, resetting params/opt state at
    #              client boundaries and accumulating weighted deltas
    #              in-scan. Padding drops to the lane-length imbalance
    #              (~5-10% vs ~30% bucketed on Dirichlet cohorts) and the
    #              4-5 sequential bucket programs collapse to one with
    #              ~3x fewer, fatter sequential steps. Requires the
    #              device-resident data path and a plain mean-aggregating,
    #              stateless algorithm (FedAvg/FedProx family).
    # "auto"     — packed when eligible and the dataset's client sizes are
    #              skewed (max >= 2x median); else bucketed when skewed and
    #              the algorithm mean-aggregates; else even.
    cohort_schedule: str = "auto"
    max_width_buckets: int = 4
    # eval loss family — must match LocalTrainConfig.loss_kind
    # ("ce" | "mse" | "bce")
    loss_kind: str = "ce"
    # asynchronous host-side cohort pipeline: build round r+1's cohort
    # tensors on a background thread while round r's compiled step runs on
    # the device (simulation/prefetch.py). Packing is a pure function of
    # (seed, round_idx) — every RNG stream it consumes is round-indexed —
    # so lookahead packing is bit-exact vs the synchronous path; history
    # gains pack_time / pack_wait / overlap per round. prefetch_depth
    # bounds the handoff queue (1-2 is plenty; each slot holds one round's
    # host tensors).
    prefetch: bool = True
    prefetch_depth: int = 2
    # fused aggregation hot path (ops/pallas): the q8/q4 codec stage runs
    # as one fused quantize+pack kernel pass per leaf, and a Krum-family
    # defense with the sanitizer on collapses sanitize + pairwise distances
    # + selection into one read of the stacked update
    # (core.robust.fused_sanitize_krum). Bit-identical to the unfused
    # paths — round history, codec bytes, and quarantine/z telemetry are
    # unchanged; off (default) preserves the exact unfused programs.
    agg_kernels: bool = False
    # per-client local-test evaluation at eval rounds (reference
    # ``_local_test_on_all_clients``, fedavg_api.py:188-246): every client's
    # local train AND local test split is evaluated under the current global
    # params; history records the reference's weighted aggregates plus the
    # per-client vectors. One compiled segmented pass per split (per-sample
    # stats scatter-added into per-client accumulators) — not a per-client
    # Python loop. Off by default: it roughly doubles eval cost.
    local_test_on_all_clients: bool = False
    # --- self-healing round pipeline -----------------------------------
    # update sanitizer (core/robust.sanitize_stacked): quarantine non-finite
    # and norm-outlier client updates inside the compiled round step; the
    # quarantine set lands in history[i]["quarantined"]. Forces the even
    # cohort schedule (the defense needs the full stacked cohort).
    sanitize_updates: bool = False
    sanitize_z_thresh: float = 6.0
    # divergence watchdog: > 0 arms it — a round whose train loss exceeds
    # watchdog_factor x the median of the last watchdog_window accepted
    # losses (or is non-finite, or produces non-finite params) is rolled
    # back to the last-good state and re-run with the suspect clients
    # excluded, at most max_rollbacks times per round. Watchdog mode
    # implies the sanitizer (a re-run is only safe with poisoned rows
    # zeroed) and runs rounds synchronously (no prefetch pipeline) — the
    # verdict must land before the next round dispatches.
    watchdog_factor: float = 0.0
    watchdog_window: int = 5
    max_rollbacks: int = 2
    # exclusion threshold on the failed round's robust z-scores; clients at
    # or above it are dropped from the re-run (fallback: the single worst)
    rollback_z_thresh: float = 3.0
    # --- million-client cohorts ----------------------------------------
    # client-state arena (simulation/client_store.py): device slots holding
    # stacked per-client algorithm state, LRU-spilled to host RAM beyond
    # this many residents. None = every registered client stays resident
    # (capacity = client_num_in_total). Must be >= client_num_per_round.
    client_state_capacity: Optional[int] = None
    # optional on-disk tier for spilled states (msgpack files); when set,
    # the host-RAM tier is bounded at the device capacity and overflow
    # goes to disk. Incompatible with the divergence watchdog (rollback
    # cannot snapshot the disk tier).
    client_state_spill_dir: Optional[str] = None
    # "arena" — vectorized gather/scatter (default); "dict" — the legacy
    # per-client host dict, kept as the bit-exactness oracle
    client_state_backend: str = "arena"
    # mesh axis the cohort (batch, stacked states, per-client RNGs, and
    # the stacked update inside aggregation) shards over; cohorts are
    # padded to a multiple of this axis' size (zero-weight rows)
    cohort_shard_axis: str = AXIS_CLIENT
    # --- 2-D federated mesh (client × model) ---------------------------
    # mesh axis the GLOBAL model state shards over: per-leaf PartitionSpecs
    # are inferred by parallel.sharding.auto_partition_specs (largest-
    # divisible-dim rule, replicated fallback with one warning) and engage
    # only when the mesh actually carries this axis with size > 1. Global
    # params, server opt-state, per-client arena rows, codec EF residuals,
    # and the stacked cohort update all keep the model axis through the
    # round jit; local training consumes a transient gathered copy (the
    # lazy weight gather of Xu et al., arXiv:2004.13336), so round history
    # stays bit-identical to the 1-D client mesh and the unsharded path.
    # None/absent axis = 1-D behavior, unchanged.
    model_shard_axis: Optional[str] = AXIS_MODEL
    # per-leaf spec overrides, {path-substring: dim-index | None}: matched
    # against jax.tree_util.keystr leaf paths (sorted patterns, first match
    # wins); an int shards that dim over the model axis, None pins the
    # leaf replicated
    model_spec_overrides: Optional[dict] = None
    # --- compressed update plane ---------------------------------------
    # wire-codec spec (comm/codec.py grammar, e.g. "delta|topk:0.01|q8"):
    # apply the cross-silo uplink codec's lossy encode+decode to every
    # client's update inside the compiled round step, with per-client
    # error-feedback residuals in a ClientStateArena when the spec has a
    # top-k stage. Forces the even schedule (the roundtrip needs the full
    # stacked cohort) and a params-shaped client update. EF residuals are
    # NOT snapshotted by the watchdog — a rolled-back round's residual
    # carry survives the re-run, same as a real client re-encoding.
    # None = updates flow uncompressed (bit-identical to pre-codec runs).
    comm_codec: Optional[str] = None
    # --- buffered-async aggregation (simulation/async_engine.py) --------
    # FedBuff-style server: client updates fold into a staleness-weighted
    # buffer as they (virtually) complete and a new model version commits
    # every async_buffer_size updates — no cohort barrier. Off (default)
    # keeps the synchronous engine byte-identical.
    async_mode: bool = False
    # commit threshold K; None = the full cohort (the bit-exact fallback
    # regime when the delay plan has zero skew)
    async_buffer_size: Optional[int] = None
    # stale-update down-weight exponent: weight *= 1/(1+staleness)^alpha,
    # where staleness = commits since the update's base model version; the
    # same factor scales the sanitizer's robust-z norms (staleness-aware
    # outlier detection)
    async_staleness_alpha: float = 0.5
    # seeded heavy-tail per-client completion-time plan (virtual seconds;
    # comm/resilience.ClientDelayPlan): skew <= 0 disables the plan (every
    # client completes in async_delay_base_s exactly)
    async_delay_base_s: float = 1.0
    async_delay_skew: float = 0.0
    async_delay_jitter: float = 0.2
    # --- compiled multi-round dispatch ---------------------------------
    # fuse this many consecutive rounds into ONE donated jit containing a
    # lax.scan over the round index: the whole block's cohort tensors are
    # staged in a single upload, per-client arena state and codec EF
    # residuals are carried device-side between the scanned rounds, and
    # pack_wait/dispatch are paid once per block instead of once per round.
    # Blocks split automatically so eval/checkpoint hooks fire on exact
    # round indices (those rounds run the per-round program). Histories are
    # bit-exact vs rounds_per_dispatch=1; 1 (default) keeps the per-round
    # path byte-identical to previous releases. Incompatible features
    # (watchdog, custom aggregates, attack transforms, disk-spill arena,
    # packed/bucketed schedules, async mode, host-resident data or dict
    # state backends) raise ScanIncompatibleError at construction.
    rounds_per_dispatch: int = 1


@dataclasses.dataclass
class RoundInputs:
    """One round's host-built cohort tensors (all numpy — device conversion
    happens at dispatch on the main thread). Produced by
    ``FedSimulator.build_round_inputs``, possibly on the prefetch worker."""

    round_idx: int
    client_ids: np.ndarray
    drop: Optional[np.ndarray]
    kind: str  # "even" | "bucketed" | "packed"
    payload: Any
    pack_time: float  # host seconds spent building (wherever it ran)


class ScanIncompatibleError(ValueError):
    """``rounds_per_dispatch > 1`` combined with a feature the scanned block
    cannot carry. Raised at construction (or at ``run`` for runtime-only
    conflicts like the multi-tenant gate) — the engine refuses rather than
    silently running a different path, mirroring the mesh refusals."""


@dataclasses.dataclass
class BlockInputs:
    """One scanned block's host-built tensors: ``rounds_per_dispatch``
    consecutive rounds' cohort index rectangles stacked along a leading
    round axis. Pure in (seed, rounds) — built on the prefetch worker.
    Arena slot vectors are NOT here: residency is mutable simulator state,
    assigned on the main thread at dispatch."""

    rounds: tuple  # consecutive round indices
    ids: np.ndarray  # (L, client_num_per_round) sampled cohorts, pre-pad
    xs: Dict[str, np.ndarray]  # stacked scan inputs (idx/num_samples/round…)
    pack_time: float


def _gather_from_device(data: Dict[str, Any], x_all, y_all) -> Dict[str, Any]:
    """Device-resident data path: replace the cohort's index rectangle with
    x/y gathered from the HBM-resident global arrays, zeroing padded rows
    (padded rows gather index 0; zeroing keeps both packing paths feeding
    identical batches — BatchNorm statistics see every row, masked or not)."""
    idx = data.pop("idx")
    m = data["mask"]

    def _masked(gathered):
        mb = m.reshape(m.shape + (1,) * (gathered.ndim - m.ndim))
        return gathered * mb.astype(gathered.dtype)

    data["x"] = _masked(x_all[idx])
    data["y"] = _masked(y_all[idx])
    return data


def _cohort_outputs(alg: FedAlgorithm, params, cohort, client_states, rng):
    """vmap the algorithm's local_update over the cohort; each client's RNG
    stream is keyed by its global cohort position ("pos") so any schedule
    that reorders clients (bucketed) draws identical randomness."""
    data = dict(cohort)
    pos = data.pop("pos")
    rngs = jax.vmap(lambda p: jax.random.fold_in(rng, p))(pos)
    return cohort_local_update(alg.local_update, params, client_states,
                               data, rngs)


def pads_cohort(algorithm, update_transform) -> bool:
    """Whether the engine may pad a cohort its mesh's client axis does not
    divide. Padded rows are zero-weight: invisible to the plain weighted
    mean (and to the packed / bucketed schedules' lane and slot padding),
    seen by a custom aggregate or an injected attack. The one rule both
    the engine's own check and the facade's choice of mesh go by."""
    return algorithm.aggregate is None and update_transform is None


class _Phase:
    """What ``FedSimulator._phase`` yields. The body may set ``name`` before
    the exit: the time is filed under the name it has then. After the exit
    ``end`` is the closing stamp and ``wall`` the whole bracket, the phases
    opened inside it included."""

    __slots__ = ("name", "start", "end", "inner")

    def __init__(self, name: str):
        self.name = name
        self.inner = 0.0  # wall of the phases opened inside this one
        self.start = self.end = time.perf_counter()

    @property
    def wall(self) -> float:
        return self.end - self.start


class FedSimulator:
    """Generic over FedAlgorithm; placement decided by ``mesh``."""

    def __init__(
        self,
        fed_data: FederatedData,
        algorithm: FedAlgorithm,
        init_variables: PyTree,
        cfg: SimConfig,
        mesh=None,
        packed_ctx: Optional[tuple] = None,
        server_tester=None,
        hook_args=None,
        profiler=None,
        update_transform: Optional[Callable] = None,
    ):
        self.fed = fed_data
        self.alg = algorithm
        self.cfg = cfg
        self.mesh = mesh
        self.params = init_variables
        self.server_state = algorithm.init_server_state(init_variables)
        # per-client persistent state lives on host, stacked per cohort on use
        self.client_states: Dict[int, PyTree] = {}
        if algorithm.init_client_state is not None:
            proto = algorithm.init_client_state(init_variables)
            self._client_state_proto = proto
        else:
            self._client_state_proto = ()
        self.history: List[Dict[str, float]] = []
        self._eval_fn = None
        # reference test_on_the_server hook (ServerAggregator/ModelTrainer
        # subclass or any object with that method): a truthy return at eval
        # rounds REPLACES the default evaluation, exactly like the MPI
        # aggregator (FedAVGAggregator.py:130 `if self.trainer.test_on_the_
        # server(...): return`); a dict return is merged into the record
        self._server_tester = server_tester
        self._hook_args = hook_args  # original args object, for the hook
        self._local_eval_fn = None
        self._local_eval_cache: Dict[str, Any] = {}
        # observability: an MLOpsProfilerEvent-shaped object (span()) gets
        # host_pack spans from the builder (prefetch worker included) and
        # round_dispatch spans from the round loop
        self._profiler = profiler
        self._prefetcher = None  # live only inside run()
        # double-buffered arena movement: (round_idx, gather-ids key, stack)
        # produced by put_take under the previous round's device shadow
        self._pregathered_state = None
        self._pregathered_codec = None
        # packed schedule: round-independent lane structure per (cohort,
        # drop) pattern — full-participation runs hit every round
        self._lane_plan_cache: Dict[Any, Dict[str, Any]] = {}
        # phase attribution: seconds by phase accrued since the last
        # round-completion stamp. Written by _phase alone; drained into
        # rec["phases"] by _drain_phases, so the named phases + host_other
        # sum to round_time. _phase_stack holds the phases now open.
        self._phase_acc: Dict[str, float] = {}
        self._phase_stack: List[_Phase] = []
        # sanitizer readback: the last dispatched round's (2, C) device
        # array of [quarantine flag, robust z] plus its cohort ids; drained
        # into the round record by _defer_rec
        self._last_qz = None
        self._last_cohort_ids = None
        self._finite_fn = None  # built lazily by the watchdog loop
        # test hook: when set, the round step calls
        # jax.debug.inspect_array_sharding on the stacked update / aggregate
        # and feeds the observed shardings here. None (default) leaves the
        # traced program untouched.
        self._sharding_probe: Optional[Callable[[str, Any], None]] = None
        # multi-tenant round gate (simulation/multi_run.py): called with the
        # round index at the top of every round-loop iteration, BEFORE the
        # round's own timing starts — the fair scheduler blocks here until
        # this job's turn on the mesh. The gate may wait inside
        # ``_phase("tenant_wait")`` so the wait is attributed rather than
        # lumped into host_other. None (default) = single-tenant, zero
        # behavior change.
        self._round_gate: Optional[Callable[[int], None]] = None
        # commit→publish hook (serving plane): called with
        # ``(version, params_copy)`` after each round's params commit —
        # attach via attach_publisher. None (default) = no serving, zero
        # behavior change (the disabled path never copies params).
        self._publisher: Optional[Callable[[int, Any], Any]] = None

        sizes = [len(v) for v in fed_data.train_data_local_dict.values()]
        if cfg.num_local_batches is None:
            self.num_local_batches = max(1, -(-max(sizes) // cfg.batch_size))
        else:
            self.num_local_batches = cfg.num_local_batches

        train = fed_data.train_data_global
        self._use_device_data = bool(
            cfg.device_data
            and fed_data._global_index is not None
            and (train.x.nbytes + train.y.nbytes) <= cfg.device_data_max_bytes
        )
        if self._use_device_data:
            if mesh is not None:
                # replicate over the mesh ONCE here — a single-device array
                # would be re-replicated (full copy) on every step call
                self._x_dev = jax.device_put(train.x, replicated(mesh))
                self._y_dev = jax.device_put(train.y, replicated(mesh))
            else:
                self._x_dev = jnp.asarray(train.x)
                self._y_dev = jnp.asarray(train.y)
        self._axis_size = (
            1 if mesh is None else int(mesh.shape[cfg.cohort_shard_axis]))
        # --- 2-D mesh: model-axis sharding of the global state -----------
        # everything below is None on a 1-D/absent mesh, and every use site
        # falls back to the replicated 1-D behavior in that case
        self._model_axis: Optional[str] = None
        self._param_specs = None   # per-leaf P(...) for params-shaped trees
        self._param_sh = None      # NamedSharding tree for params/aggregate
        self._server_sh = None     # NamedSharding tree for server opt-state
        self._state_specs = None   # per-leaf P(...) for one client's state
        self._state_sh = None      # cohort×model shardings for stacked state
        self._update_sh = None     # cohort×model shardings for the stack
        if (mesh is not None and cfg.model_shard_axis
                and cfg.model_shard_axis in mesh.axis_names
                and int(mesh.shape[cfg.model_shard_axis]) > 1):
            maxis = cfg.model_shard_axis
            msize = int(mesh.shape[maxis])
            self._model_axis = maxis
            # the one warning about replicated-fallback leaves comes from
            # THIS call; server/client-state inference below warns nothing
            # (their leaves mirror or derive from the params')
            self._param_specs = auto_partition_specs(
                init_variables, maxis, msize,
                overrides=cfg.model_spec_overrides)
            self._param_sh = tree_shardings(mesh, self._param_specs)
            self.params = jax.device_put(self.params, self._param_sh)
            if jax.tree_util.tree_leaves(self.server_state):
                srv_specs = auto_partition_specs(
                    self.server_state, maxis, msize,
                    overrides=cfg.model_spec_overrides, warn=False)
                self._server_sh = tree_shardings(mesh, srv_specs)
                self.server_state = jax.device_put(
                    self.server_state, self._server_sh)
            if self._client_state_proto != ():
                self._state_specs = auto_partition_specs(
                    self._client_state_proto, maxis, msize,
                    overrides=cfg.model_spec_overrides, warn=False)
                self._state_sh = tree_shardings(
                    mesh, prepend_axis(self._state_specs,
                                       cfg.cohort_shard_axis))
            # params-shaped update stacks (and the codec's EF residual
            # rows) mirror params with a leading cohort axis; algorithms
            # with custom update structures (SCAFFOLD's {delta, delta_c})
            # get their stack specs inferred at trace time instead
            if getattr(algorithm, "update_is_params", True):
                self._update_sh = tree_shardings(
                    mesh, prepend_axis(self._param_specs,
                                       cfg.cohort_shard_axis))
        self._batch_counts = {
            c: max(1, -(-len(v) // cfg.batch_size))
            for c, v in fed_data.train_data_local_dict.items()
        }
        # bucketed partial aggregation needs the plain weighted mean; custom
        # aggregates (median/trimmed...) see the full stacked cohort only in
        # the even path
        # packed eligibility: one-program-per-round lane execution needs the
        # raw (apply_fn, LocalTrainConfig) to build its in-scan batch step,
        # a plain weighted-mean aggregation, params-shaped stateless updates,
        # device-resident data, and none of the features that hook the
        # per-client rectangle (SCAFFOLD state, DP-SGD per-example pass,
        # BatchNorm collection threading).
        self._packed_ctx = packed_ctx
        # adversarial-update hook (simulation/__init__._make_attack_transform)
        # plus the sanitizer both operate on the full stacked cohort, so they
        # pin the even schedule (packed/bucketed never materialize the stack)
        self._update_transform = update_transform
        self._detect = bool(cfg.sanitize_updates or cfg.watchdog_factor > 0)
        # compressed update plane: the wire codec's lossy roundtrip runs per
        # client inside the round step — the simulator half of the parity
        # harness for the cross-silo uplink codec (same spec grammar, same
        # stochastic-rounding streams keyed by (seed, round, client id))
        self._codec_spec = None
        self._codec_rt = None
        self._codec_arena: Optional[ClientStateArena] = None
        self._codec_record = None
        self._codec_wire = (0, 0)
        if cfg.comm_codec:
            from ..comm import codec as wire_codec

            self._codec_spec = wire_codec.parse_codec_spec(cfg.comm_codec)
            if not getattr(algorithm, "update_is_params", True):
                raise ValueError(
                    "comm_codec compresses params-shaped client updates; "
                    f"algorithm {type(algorithm).__name__} produces a "
                    "custom update structure")
            self._codec_rt = wire_codec.build_stacked_roundtrip(
                self._codec_spec, cfg.seed,
                # 2-D mesh: decoded updates + EF carry stay cohort×model
                update_shardings=self._update_sh,
                agg_kernels=bool(cfg.agg_kernels))
            self._codec_record = wire_codec.record_codec
            self._codec_wire = wire_codec.spec_wire_nbytes(
                self._codec_spec, init_variables)
        force_even = (self._detect or update_transform is not None
                      or self._codec_spec is not None
                      # model-axis sharding pins the stacked update to the
                      # params' specs — only the even path materializes it
                      or self._model_axis is not None)
        mean_agg = (
            algorithm.aggregate is None
            and getattr(algorithm, "update_is_params", True)
            and not force_even
        )
        packed_ok = (
            packed_ctx is not None
            and mean_agg
            and self._use_device_data
            and self._client_state_proto == ()
            and algorithm.prepare_client_state is None
            and not packed_ctx[1].use_scaffold
            and packed_ctx[1].dp_l2_clip is None
            and not packed_ctx[3]  # has_batch_stats
        )
        schedule = cfg.cohort_schedule
        if force_even and schedule in ("packed", "bucketed"):
            raise ValueError(
                f"cohort_schedule='{schedule}' is incompatible with the "
                "update sanitizer / watchdog / injected attacks / "
                "comm_codec / model-axis sharding — those need the full "
                "stacked cohort (use 'even' or 'auto')")
        if force_even:
            schedule = "even"
        if int(cfg.rounds_per_dispatch) > 1:
            if schedule in ("packed", "bucketed"):
                raise ScanIncompatibleError(
                    f"cohort_schedule='{schedule}' cannot run inside a "
                    "scanned block — its lane/bucket plans are rebuilt on "
                    "the host every round; use 'even'/'auto' or "
                    "rounds_per_dispatch=1")
            schedule = "even"  # auto resolves to the rectangular program
        if schedule == "auto":
            counts = np.asarray(list(self._batch_counts.values()))
            skewed = counts.max() >= 2 * max(np.median(counts), 1)
            if skewed:
                schedule = "packed" if packed_ok else "bucketed"
            else:
                schedule = "even"
        if schedule == "packed" and not packed_ok:
            raise ValueError(
                "cohort_schedule='packed' requires a stateless "
                "mean-aggregating algorithm, device-resident data, and no "
                "SCAFFOLD/DP-SGD/BatchNorm (use 'bucketed' or 'auto')")
        self._packed = schedule == "packed"
        self._bucketed = schedule == "bucketed" and mean_agg
        # even-schedule cohorts are padded to a multiple of the mesh axis
        # (zero-weight, zero-mask rows duplicating the last client's slot)
        # so GSPMD shards the client axis evenly. Padded rows are invisible
        # to the plain weighted mean and to the sanitizer (static valid
        # mask), but a custom aggregate / injected attack would see them.
        self._cohort_pad = 0
        if mesh is not None and not self._packed and not self._bucketed:
            self._cohort_pad = (-cfg.client_num_per_round) % self._axis_size
        if self._cohort_pad and not pads_cohort(self.alg, update_transform):
            raise ValueError(
                f"client_num_per_round={cfg.client_num_per_round} is not a "
                f"multiple of the '{cfg.cohort_shard_axis}' mesh axis size "
                f"({self._axis_size}): cohort padding supports only the "
                "plain weighted-mean aggregation (a custom aggregate or "
                "injected attack would see the padded rows) — pick a "
                "divisible cohort size")
        if cfg.client_state_backend not in ("arena", "dict"):
            raise ValueError(
                f"client_state_backend={cfg.client_state_backend!r} "
                "(expected 'arena' or 'dict')")
        self._arena: Optional[ClientStateArena] = None
        self._prepare_fn = None
        if (self._client_state_proto != ()
                and cfg.client_state_backend == "arena"):
            capacity = cfg.client_state_capacity or cfg.client_num_in_total
            if capacity < cfg.client_num_per_round:
                raise ValueError(
                    f"client_state_capacity={capacity} < "
                    f"client_num_per_round={cfg.client_num_per_round}: the "
                    "whole sampled cohort must fit in the arena")
            if cfg.watchdog_factor > 0 and cfg.client_state_spill_dir:
                raise ValueError(
                    "watchdog rollback cannot snapshot the on-disk spill "
                    "tier — drop client_state_spill_dir or raise "
                    "client_state_capacity")
            # the simulated population is fixed (client_num_in_total) and
            # every client may be resampled, so spill rows stay live for
            # the whole run — no departure event exists to reclaim on
            # graftcheck: disable=resource-leak
            self._arena = ClientStateArena(
                self._client_state_proto, capacity,
                spill_dir=cfg.client_state_spill_dir,
                host_capacity=(capacity if cfg.client_state_spill_dir
                               else None),
                mesh=mesh, axis_name=cfg.cohort_shard_axis,
                row_specs=self._state_specs)
            if algorithm.prepare_client_state is not None:
                # same per-client prepare as the dict path, vectorized over
                # the stacked cohort (pure restructuring — bit-exact); on a
                # mesh the output must stay on the cohort axis (vmap can
                # broadcast server-state-derived leaves to replicated, which
                # the round step's in_shardings would then reject)
                prep_sh = (self._state_sh if self._state_sh is not None
                           else shard_along(mesh, cfg.cohort_shard_axis, 0)
                           if mesh is not None else None)
                self._prepare_fn = jax.jit(
                    jax.vmap(algorithm.prepare_client_state, in_axes=(None, 0)),
                    **({} if prep_sh is None else {"out_shardings": prep_sh}))
        if self._codec_spec is not None and self._codec_spec.topk is not None:
            # per-client error-feedback residuals: f32 params-shaped rows in
            # their own arena (same slot machinery as algorithm state, but
            # the two trees have different protos so they cannot share one)
            capacity = max(cfg.client_state_capacity or cfg.client_num_in_total,
                           cfg.client_num_per_round)
            res_proto = jax.tree.map(
                lambda p: np.zeros(np.shape(p), np.float32), init_variables)
            self._codec_arena = ClientStateArena(
                res_proto, capacity, mesh=mesh,
                axis_name=cfg.cohort_shard_axis,
                # EF residual rows are params-shaped: same model layout
                row_specs=self._param_specs)
        # --- compiled multi-round dispatch: eligibility ------------------
        self._scan_rounds = int(cfg.rounds_per_dispatch)
        if self._scan_rounds < 1:
            raise ValueError(
                f"rounds_per_dispatch={cfg.rounds_per_dispatch} "
                "(expected >= 1)")
        if self._scan_rounds > 1:
            why = None
            if cfg.async_mode:
                why = ("the buffered-async engine commits on update "
                       "arrival, not on a fixed round barrier to fuse")
            elif cfg.watchdog_factor > 0:
                why = ("the divergence watchdog needs each round's verdict "
                       "on the host before the next round may dispatch")
            elif update_transform is not None:
                why = ("injected attack/update transforms are host-"
                       "supplied closures the engine cannot audit for "
                       "scan-safety")
            elif (algorithm.aggregate is not None
                  and getattr(algorithm, "robust", None) is None):
                why = ("a custom aggregate is host-supplied code; only the "
                       "built-in robust defenses are known scan-safe")
            elif cfg.client_state_spill_dir:
                why = ("the disk-spill arena tier moves rows through the "
                       "host between rounds, but a scanned block carries "
                       "them device-side")
            elif (self._client_state_proto != ()
                  and cfg.client_state_backend != "arena"):
                why = ("client_state_backend='dict' keeps per-client state "
                       "in host Python between rounds")
            elif not self._use_device_data:
                why = ("device-resident data is required — a block ships "
                       "index rectangles, not R full cohort batches")
            if why is not None:
                raise ScanIncompatibleError(
                    f"rounds_per_dispatch={self._scan_rounds}: {why} — "
                    "run with rounds_per_dispatch=1")
        # compiled scan steps keyed by block length (hook-boundary splits
        # produce a handful of distinct lengths; each compiles once)
        self._scan_steps: Dict[int, Callable] = {}
        self._idx_registry = None  # lazy (rows, sizes, lut) for block packs
        self._round_step = self._build_round_step()
        if self._packed:
            self._packed_step = self._build_packed_step()
        if self._bucketed:
            self._partial_step = self._build_partial_step()
            self._finalize_step = self._build_finalize_step()
        # which of the engine's paths this configuration engaged — the one
        # line chip_smoke.py (and anyone reading a run's log) checks
        logging.info(
            "FedSimulator: schedule=%s carry=%s device_data=%s mesh=%s",
            "packed" if self._packed else
            "bucketed" if self._bucketed else "even",
            ("flat" if cfg.packed_flat_carry else "tree")
            if self._packed else "n/a",
            self._use_device_data,
            None if mesh is None else dict(mesh.shape))

    # --- compiled pieces ---------------------------------------------------

    def _make_round_body(self) -> Callable:
        """The traced math of ONE round (local train -> codec roundtrip ->
        attack -> sanitize/defense -> aggregate -> server update), shared
        verbatim between the per-round jit (``_build_round_step``) and the
        multi-round scan body (``_build_scan_step``) so the two paths cannot
        drift numerically."""
        alg = self.alg
        transform = self._update_transform
        detect = self._detect
        z_thresh = float(self.cfg.sanitize_z_thresh)
        pad = self._cohort_pad
        c_real = int(self.cfg.client_num_per_round)
        mesh = self.mesh
        cohort_sh = (shard_along(mesh, self.cfg.cohort_shard_axis, 0)
                     if mesh is not None else None)
        # static (host) validity mask over cohort rows: padded rows must be
        # invisible to the sanitizer's median/MAD (a zero-update row is a
        # perfectly plausible inlier that would drag the statistics)
        valid_np = (np.arange(c_real + pad) < c_real) if pad else None
        # agg_kernels + sanitizer + a Krum-family defense (whose aggregator
        # does not run its own second sanitize): collapse the
        # sanitize->Krum pair into core.robust.fused_sanitize_krum
        fuse_robust = bool(
            self.cfg.agg_kernels and detect
            and getattr(alg, "robust", None) is not None
            and alg.robust.defense_type in type(alg.robust).KRUM_FAMILY
            and not alg.robust.sanitize)

        def _probe(tag, tree):
            if self._sharding_probe is not None:
                probe = self._sharding_probe
                leaves = jax.tree_util.tree_leaves(tree)
                if not leaves:
                    return
                # probe the LARGEST leaf: small leaves (biases) legitimately
                # fall back to replicated under the model axis, so they say
                # nothing about whether the big tensors stayed sharded
                big = max(leaves, key=lambda l: math.prod(l.shape))
                jax.debug.inspect_array_sharding(
                    big, callback=lambda s, tag=tag: probe(tag, s))

        codec_rt = self._codec_rt
        codec_ef = self._codec_arena is not None
        update_sh = self._update_sh  # per-leaf cohort×model (or None on 1-D)
        mdl = self._model_axis is not None
        rep_sh = replicated(mesh) if mesh is not None else None
        maxis = self._model_axis
        msize = int(mesh.shape[maxis]) if mdl else 1
        overrides = self.cfg.model_spec_overrides

        def _pin(tree, sh):
            """Per-leaf with_sharding_constraint (sh a matching tree)."""
            return jax.tree.map(
                lambda u, s: jax.lax.with_sharding_constraint(u, s), tree, sh)

        def _infer_sh(tree, leading_cohort: bool):
            """Trace-time model-axis shardings for an arbitrary tree (the
            update/aggregate structure is algorithm-defined, so its specs
            come from the traced shapes — same largest-divisible-dim rule
            as the init-time params/opt-state inference, minus the leading
            cohort dim for stacked trees)."""
            shapes = jax.tree.map(
                lambda u: jax.ShapeDtypeStruct(
                    u.shape[1:] if leading_cohort else u.shape, u.dtype),
                tree)
            specs = auto_partition_specs(
                shapes, maxis, msize, overrides=overrides, warn=False)
            if leading_cohort:
                specs = prepend_axis(specs, self.cfg.cohort_shard_axis)
            return tree_shardings(mesh, specs)

        def round_body(params, server_state, cohort, client_states, rng,
                       codec_res=(), cids_u32=None, round_u32=None):
            if mdl:
                _probe("params_in", params)
                # Xu et al. (arXiv:2004.13336) lazy weight gather: local
                # training computes on a TRANSIENT replicated view; the
                # persistent params (donated input, updated output) never
                # leave the model-axis layout, so per-client math is
                # bit-identical to the 1-D path while the resident
                # footprint stays 1/model_axis
                train_params = jax.tree.map(
                    lambda p: jax.lax.with_sharding_constraint(p, rep_sh),
                    params)
                # same lazy gather for the stacked per-client rows
                # (SCAFFOLD's broadcast c / c_local): persistent on
                # cohort×model, consumed through a transient 1-D-layout
                # view so the local-update math lowers identically to the
                # 1-D mesh
                train_client_states = jax.tree.map(
                    lambda s: jax.lax.with_sharding_constraint(s, cohort_sh),
                    client_states)
            else:
                train_params = params
                train_client_states = client_states
            # the stages' scopes are metadata: they name the round's ops in
            # a device trace (fed.local_update, fed.codec, fed.sanitize,
            # fed.aggregate, fed.server_update) and change no arithmetic
            with jax.named_scope("fed.local_update"):
                outs = _cohort_outputs(alg, train_params, cohort,
                                       train_client_states, rng)
            update = outs.update
            w = outs.weight.astype(jnp.float32)
            upd_sh = None
            if mesh is not None:
                # pin the stacked update to the cohort axis (and, on a 2-D
                # mesh, each leaf's trailing dims to their model specs):
                # everything below reduces over clients, and without the
                # constraint GSPMD may all-gather the full stack onto
                # every device before sanitize/Krum/mean see it
                if mdl:
                    # TWO pins, deliberately. Pinning straight to the
                    # cohort×model layout lets GSPMD propagate the model
                    # axis BACKWARD into local training, re-partitioning
                    # softmax/contraction reductions and breaking bit
                    # parity with the 1-D program. The first pin holds the
                    # stack on the cohort axis only (replicated over model
                    # — the exact 1-D layout), acting as a propagation
                    # barrier; the second reshards to cohort×model, which
                    # is a pure slice with no arithmetic.
                    update = jax.tree.map(
                        lambda u: jax.lax.with_sharding_constraint(
                            u, cohort_sh),
                        update)
                    upd_sh = _infer_sh(update, leading_cohort=True)
                    update = _pin(update, upd_sh)
                else:
                    update = jax.tree.map(
                        lambda u: jax.lax.with_sharding_constraint(
                            u, cohort_sh),
                        update)
            if codec_rt is not None:
                # lossy wire roundtrip FIRST: the attacker corrupts what the
                # server decodes (cross-silo decompress-then-corrupt order)
                # and the sanitizer sees what the attacker produced
                with jax.named_scope("fed.codec"):
                    update, codec_res = codec_rt(
                        update, codec_res, cids_u32, round_u32)
            # adversarial corruption first, sanitizer second — the defense
            # must see exactly what a byzantine client would upload
            if transform is not None:
                update = transform(update, w)
            qz = None
            if detect and fuse_robust:
                # agg_kernels fast path: sanitize + Krum distances +
                # selection in one read of the stacked update
                # (core.robust.fused_sanitize_krum mirrors the
                # sanitize_stacked -> aggregate pair below bit for bit)
                from ..core.robust import fused_sanitize_krum

                ra = alg.robust
                f_byz, m_krum = ra._krum_fm(c_real + pad)
                with jax.named_scope("fed.sanitize"):
                    agg, w, quar, z, _sel = fused_sanitize_krum(
                        update, w, z_thresh=z_thresh, n_byz=f_byz, m=m_krum,
                        sample_weighted=ra.defense_type == "krum_fedavg",
                        valid=valid_np, out_shardings=upd_sh)
                qz = jnp.stack([quar.astype(jnp.float32),
                                jnp.nan_to_num(z, posinf=1e30)])
            elif detect:
                from ..core.robust import sanitize_stacked

                with jax.named_scope("fed.sanitize"):
                    update, w, quar, z = sanitize_stacked(
                        update, w, z_thresh, valid=valid_np,
                        out_shardings=upd_sh)
                # one (2, C) row pair [quarantine flag, robust z] rides back
                # with the metrics — a single extra host transfer per round
                qz = jnp.stack([quar.astype(jnp.float32),
                                jnp.nan_to_num(z, posinf=1e30)])
            if detect and fuse_robust:
                pass  # aggregate already folded into the fused pass
            else:
                if mdl and (codec_rt is not None or transform is not None):
                    # codec/attack stages are elementwise over rows but carry
                    # no layout promise — re-pin before the reduction
                    update = _pin(update, upd_sh)
                _probe("update", update)
                with jax.named_scope("fed.aggregate"):
                    if alg.aggregate is not None:
                        agg = alg.aggregate(update, w)
                    else:
                        from ..core.algframe import weighted_mean

                        agg = weighted_mean(update, w)
            if mdl:
                # the client-axis reduction leaves each aggregate leaf on
                # its model layout — pin it so the optimizer apply below
                # runs sharded (Krum's gather notwithstanding, its RESULT
                # comes back to the model axis here)
                agg = _pin(agg, _infer_sh(agg, leading_cohort=False))
            _probe("agg", agg)
            with jax.named_scope("fed.server_update"):
                new_params, new_server_state = alg.server_update(
                    params, agg, server_state)
            if mdl:
                _probe("params_out", new_params)
                _probe("opt_state_out", new_server_state)
            # reduce metrics to ONE tiny vector inside the program: each
            # separate host read is a device round trip, so the round's
            # metrics come back in a single (2,) transfer — [mean
            # train_loss, train_acc]
            m = outs.metrics
            if pad:
                # padded rows are zero-loss/zero-valid; divide by the REAL
                # cohort size so the loss matches the unpadded program
                loss = (m["train_loss"].sum()
                        / jnp.float32(c_real)).astype(jnp.float32)
            else:
                loss = m["train_loss"].mean().astype(jnp.float32)
            metrics_vec = jnp.stack([
                loss,
                (m["train_correct"].sum()
                 / jnp.maximum(m["train_valid"].sum(), 1.0)).astype(jnp.float32),
            ])
            new_cstate = outs.state
            if mdl and self._state_sh is not None:
                # same barrier as the update stack: hold the new client
                # rows on the 1-D layout first so the model-sharded
                # out_shardings can't propagate back into training, then
                # reshard to cohort×model
                new_cstate = jax.tree.map(
                    lambda s: jax.lax.with_sharding_constraint(s, cohort_sh),
                    new_cstate)
                new_cstate = _pin(new_cstate, self._state_sh)
            ret = (new_params, new_server_state, new_cstate, metrics_vec)
            if detect:
                ret += (qz,)
            if codec_ef:
                ret += (codec_res,)
            return ret

        return round_body

    def _build_round_step(self) -> Callable:
        round_body = self._make_round_body()
        mesh = self.mesh
        codec_rt = self._codec_rt
        codec_ef = self._codec_arena is not None
        detect = self._detect
        mdl = self._model_axis is not None
        update_sh = self._update_sh
        cohort_sh = (shard_along(mesh, self.cfg.cohort_shard_axis, 0)
                     if mesh is not None else None)

        if self._use_device_data:
            # device-resident path: the cohort carries only an index
            # rectangle (host->device per round = a few KB of indices)
            if codec_rt is not None:
                def round_step(params, server_state, cohort, client_states,
                               rng, codec_res, cids_u32, round_u32,
                               x_all, y_all):
                    data = _gather_from_device(dict(cohort), x_all, y_all)
                    return round_body(params, server_state, data,
                                      client_states, rng, codec_res,
                                      cids_u32, round_u32)
            else:
                def round_step(params, server_state, cohort, client_states,
                               rng, x_all, y_all):
                    data = _gather_from_device(dict(cohort), x_all, y_all)
                    return round_body(params, server_state, data,
                                      client_states, rng)
        else:
            round_step = round_body

        # donate params/server_state: the old round's buffers are dead the
        # moment the new ones exist — saves an HBM copy of the model per round
        n_extra = 2 if self._use_device_data else 0
        if mesh is not None:
            rep = replicated(mesh)
            # 2-D mesh: params/server-state enter and leave on their
            # model-axis layouts; stacked client state and EF residuals
            # carry cohort×model. 1-D mesh: everything global replicated,
            # cohort trees on the client axis — unchanged.
            p_sh = self._param_sh if mdl else rep
            s_sh = (self._server_sh if (mdl and self._server_sh is not None)
                    else rep)
            st_sh = (self._state_sh if (mdl and self._state_sh is not None)
                     else cohort_sh)
            res_sh = update_sh if mdl else cohort_sh
            in_sh = (p_sh, s_sh, cohort_sh, st_sh, rep)
            if codec_rt is not None:
                # residual stack + client-id vector ride the cohort axis;
                # the round scalar is replicated
                in_sh += (res_sh, cohort_sh, rep)
            in_sh += (rep,) * n_extra
            out_sh = (p_sh, s_sh, st_sh, rep)
            if detect:
                out_sh += (rep,)
            if codec_ef:
                out_sh += (res_sh,)
            return jax.jit(
                round_step,
                in_shardings=in_sh,
                out_shardings=out_sh,
                donate_argnums=(0, 1),
            )
        return jax.jit(round_step, donate_argnums=(0, 1))

    def _build_scan_step(self, block_len: int) -> Callable:
        """ONE donated jit running ``block_len`` consecutive rounds as a
        ``lax.scan`` over the round index.

        The scan body is the SAME ``round_body`` the per-round jit traces —
        plus, moved device-side, everything the host round loop used to do
        between dispatches: the cohort mask is rebuilt from ``num_samples``,
        per-round RNG keys fold inside the program, and per-client arena
        state / codec EF residuals are carried as full arena leaves with an
        in-scan gather (``leaves[slots]``) and scatter
        (``leaves.at[slots].set``) per round — bit-identical to the
        ``ClientStateArena`` take/put jits, so a block boundary can land
        anywhere without changing a single carried bit. Params, server
        state, and both arenas' leaves are donated: the block updates the
        model and arenas in place, and the only per-block host traffic is
        the stacked index rectangles in and an (L, 2) metrics vector (+ the
        (L, 2, C) sanitize readback) out.
        """
        round_body = self._make_round_body()
        cfg = self.cfg
        mesh = self.mesh
        pad = self._cohort_pad
        c_real = int(cfg.client_num_per_round)
        cohort_n = c_real + pad
        nb, bs = self.num_local_batches, cfg.batch_size
        cap = nb * bs
        detect = self._detect
        codec_rt = self._codec_rt
        codec_ef = self._codec_arena is not None
        stateful = self._arena is not None
        prepare = self.alg.prepare_client_state
        state_treedef = self._arena._treedef if stateful else None
        res_treedef = self._codec_arena._treedef if codec_ef else None
        pos_np = np.arange(cohort_n, dtype=np.uint32)
        x_all, y_all = self._x_dev, self._y_dev

        def body(carry, x):
            params, server_state, arena_leaves, codec_leaves, base_rng = carry
            ns = x["num_samples"]
            # bit-identical to the host packer's mask: row-major position <
            # num_samples (dropped clients ship num_samples=0, pad rows too)
            mask = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                    < ns[:, None])
            cohort = {
                "idx": x["idx"],
                "mask": mask.astype(jnp.float32).reshape(cohort_n, nb, bs),
                "num_samples": ns,
                "pos": jnp.asarray(pos_np),
            }
            data = _gather_from_device(cohort, x_all, y_all)
            # same fold as the host loop's per-round step_rng
            rng = jax.random.fold_in(base_rng, x["round"])
            if stateful:
                slots = x["slots"]
                states = jax.tree_util.tree_unflatten(
                    state_treedef, [l[slots] for l in arena_leaves])
                if prepare is not None:
                    states = jax.vmap(prepare, in_axes=(None, 0))(
                        server_state, states)
            else:
                states = ()
            codec_res, cids_u32, round_u32 = (), None, None
            if codec_rt is not None:
                cids_u32, round_u32 = x["cids_u32"], x["round"]
                if codec_ef:
                    cslots = x["codec_slots"]
                    codec_res = jax.tree_util.tree_unflatten(
                        res_treedef, [l[cslots] for l in codec_leaves])
            out = round_body(params, server_state, data, states, rng,
                             codec_res, cids_u32, round_u32)
            if codec_ef:
                *out, new_res = out
            if detect:
                *out, qz = out
            params, server_state, new_states = out[0], out[1], out[2]
            metrics_vec = out[3]
            if stateful:
                # only real rows scatter back (pad rows duplicate the last
                # client's slot — writing them would race its real row)
                wslots = slots[:c_real]
                arena_leaves = [
                    l.at[wslots].set(r[:c_real]) for l, r in zip(
                        arena_leaves,
                        jax.tree_util.tree_leaves(new_states))]
            if codec_ef:
                wc = cslots[:c_real]
                codec_leaves = [
                    l.at[wc].set(r[:c_real]) for l, r in zip(
                        codec_leaves, jax.tree_util.tree_leaves(new_res))]
            ys = (metrics_vec,) + ((qz,) if detect else ())
            return ((params, server_state, arena_leaves, codec_leaves,
                     base_rng), ys)

        def scan_step(params, server_state, arena_leaves, codec_leaves,
                      base_rng, xs):
            carry = (params, server_state, arena_leaves, codec_leaves,
                     base_rng)
            carry, ys = jax.lax.scan(body, carry, xs, length=block_len)
            params, server_state, arena_leaves, codec_leaves, _ = carry
            return params, server_state, arena_leaves, codec_leaves, ys

        if mesh is not None:
            rep = replicated(mesh)
            mdl = self._model_axis is not None
            p_sh = self._param_sh if mdl else rep
            s_sh = (self._server_sh if (mdl and self._server_sh is not None)
                    else rep)
            arena_sh = list(self._arena._row_sh or []) if stateful else []
            if stateful and not arena_sh:
                arena_sh = [rep] * len(self._arena._leaves)
            codec_sh = (list(self._codec_arena._row_sh or [])
                        if codec_ef else [])
            if codec_ef and not codec_sh:
                codec_sh = [rep] * len(self._codec_arena._leaves)
            blk = shard_along(mesh, cfg.cohort_shard_axis, 1)
            xs_sh = {"idx": blk, "num_samples": blk, "round": rep}
            if stateful:
                xs_sh["slots"] = blk
            if codec_rt is not None:
                xs_sh["cids_u32"] = blk
                if codec_ef:
                    xs_sh["codec_slots"] = blk
            in_sh = (p_sh, s_sh, arena_sh, codec_sh, rep, xs_sh)
            out_sh = (p_sh, s_sh, arena_sh, codec_sh,
                      (rep,) + ((rep,) if detect else ()))
            return jax.jit(scan_step, in_shardings=in_sh,
                           out_shardings=out_sh,
                           donate_argnums=(0, 1, 2, 3))
        return jax.jit(scan_step, donate_argnums=(0, 1, 2, 3))

    def _build_packed_step(self) -> Callable:
        """ONE compiled program per round: lanes of back-to-back clients.

        Each lane scans its batch sequence; at a client's last batch the
        lane flushes ``weight * (params - global)`` into an f32 delta
        accumulator and resets params + optimizer state to global. The
        weighted mean + server update happen in the same program, so a
        skewed 10-client round that the bucketed schedule runs as 4-5
        programs / ~48 sequential steps becomes one program with ~L
        (= max lane load, ~total/G) fatter steps.

        Numerics: identical per-client training to the even/bucketed paths
        (same batches, same order, same per-(pos, step) RNG fold for
        non-dropout models; dropout draws differ only in the step index
        basis). Aggregation is the same f32 weighted mean modulo summation
        order. Compiled once per (lanes, padded length) shape — the host
        quantizes lengths to multiples of 4 to keep that set small.

        FLAT CARRY (round 4, ``cfg.packed_flat_carry``): the lane scan
        carries params/optimizer state/delta accumulator as ONE ravelled
        vector per lane, not a ~170-leaf pytree — measured on the v5e the
        per-leaf update/flush/reset machinery dominated the step (a
        depth-56 net's full step cost 5.1 ms vs 3.2 ms flat at 2 lanes;
        the conv math itself is a minority). The model still sees a
        pytree: the loss wrapper unravels per step, and grads flow back
        through the unravel as one vector. SGD/momentum/Adam are
        elementwise, so flat updates are numerically identical per leaf.
        """
        import optax
        from jax.flatten_util import ravel_pytree

        from ..algorithms.local_sgd import make_loss_fn, tree_scale

        apply_fn, lcfg, needs_dropout, _ = self._packed_ctx
        opt = lcfg.make_optimizer()
        loss_fn = make_loss_fn(apply_fn, needs_dropout, lcfg.loss_kind)
        prox_mu = 0.0 if lcfg.prox_mu is None else lcfg.prox_mu
        alg = self.alg
        flat_mode = bool(self.cfg.packed_flat_carry)
        if flat_mode:
            # unravel spec from the CURRENT params (static across rounds)
            _, unravel = ravel_pytree(self.params)

            def loss_entry(flat, x, y, mask_t, key):
                return loss_fn(unravel(flat), x, y, mask_t, key)
        else:
            loss_entry = loss_fn

        grad_fn = jax.value_and_grad(loss_entry, has_aux=True)

        def packed_round(params, server_state, cohort, rng, cohort_n,
                         x_all, y_all):
            if flat_mode:
                gparams, _ = ravel_pytree(params)
            else:
                gparams = params
            # every in-scan tree.map below treats a bare array as a
            # single-leaf pytree, so the step body is shared between modes
            dsum0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), gparams)
            opt0 = opt.init(gparams)

            def lane_scan(seq):
                def step(carry, inputs):
                    lp, lopt, dsum, wsum, closs, csteps, lsum, corr, val = carry
                    idx_t, mask_t, bnd_t, w_t, pos_t, sic_t = inputs
                    mb = mask_t.reshape(
                        mask_t.shape + (1,) * (x_all.ndim - mask_t.ndim))
                    x = x_all[idx_t] * mb.astype(x_all.dtype)
                    y = y_all[idx_t] * mask_t.reshape(
                        mask_t.shape + (1,) * (y_all.ndim - mask_t.ndim)
                    ).astype(y_all.dtype)
                    key = jax.random.fold_in(
                        jax.random.fold_in(rng, pos_t), sic_t)
                    (loss, (correct, valid)), grads = grad_fn(
                        lp, x, y, mask_t, key)
                    bw = (mask_t.sum() > 0).astype(jnp.float32)
                    if prox_mu > 0.0:
                        grads = jax.tree.map(
                            lambda g, p, gp: g + prox_mu * (p - gp),
                            grads, lp, gparams)
                    grads = tree_scale(grads, bw)
                    updates, lopt = opt.update(grads, lopt, lp)
                    lp = optax.apply_updates(lp, updates)
                    closs = closs + loss * bw
                    csteps = csteps + bw
                    corr = corr + correct
                    val = val + valid
                    # client boundary: flush weighted delta, reset the lane
                    is_b = bnd_t
                    dsum = jax.tree.map(
                        lambda d, p, gp: d + (w_t * is_b) * (
                            p.astype(jnp.float32)
                            - gp.astype(jnp.float32)),
                        dsum, lp, gparams)
                    wsum = wsum + w_t * is_b
                    lsum = lsum + is_b * closs / jnp.maximum(csteps, 1.0)
                    lp = jax.tree.map(
                        lambda p, gp: jnp.where(is_b > 0, gp, p),
                        lp, gparams)
                    lopt = jax.tree.map(
                        lambda s, s0: jnp.where(is_b > 0, s0, s), lopt, opt0)
                    closs = closs * (1.0 - is_b)
                    csteps = csteps * (1.0 - is_b)
                    return (lp, lopt, dsum, wsum, closs, csteps,
                            lsum, corr, val), None

                z = jnp.float32(0.0)
                init = (gparams, opt0, dsum0, z, z, z, z, z, z)
                (_, _, dsum, wsum, _, _, lsum, corr, val), _ = jax.lax.scan(
                    step, init,
                    (seq["idx"], seq["mask"], seq["boundary"], seq["bweight"],
                     seq["pos"], seq["sic"]),
                )
                return dsum, wsum, lsum, corr, val

            dsum, wsum, lsum, corr, val = jax.vmap(lane_scan)(cohort)
            total_w = jnp.maximum(wsum.sum(), 1.0)
            if flat_mode:
                # unravel is dtype-polymorphic on homogeneous trees (it
                # does NOT cast), so restore each leaf's dtype explicitly
                # exactly like the tree path
                agg = jax.tree.map(
                    lambda a, p: a.astype(p.dtype),
                    unravel(dsum.sum(axis=0) / total_w), params)
            else:
                agg = jax.tree.map(
                    lambda d, p: (d.sum(axis=0) / total_w).astype(p.dtype),
                    dsum, params)
            new_params, new_server_state = alg.server_update(
                params, agg, server_state)
            # divisor = FULL cohort size (dropped clients are zero-loss
            # rows), matching the even/bucketed paths' loss semantics
            metrics_vec = jnp.stack([
                (lsum.sum() / jnp.maximum(cohort_n, 1.0)).astype(jnp.float32),
                (corr.sum() / jnp.maximum(val.sum(), 1.0)).astype(jnp.float32),
            ])
            return new_params, new_server_state, metrics_vec

        if self.mesh is not None:
            mesh = self.mesh
            cohort_sh = shard_along(mesh, self.cfg.cohort_shard_axis, 0)
            rep = replicated(mesh)
            return jax.jit(
                packed_round,
                in_shardings=(rep, rep, cohort_sh, rep, rep, rep, rep),
                out_shardings=(rep, rep, rep),
                donate_argnums=(0, 1),
            )
        return jax.jit(packed_round, donate_argnums=(0, 1))

    def _build_partial_step(self) -> Callable:
        """One width-bucket's local training + weighted partial sums (f32).
        Compiled once per distinct (slots, width) shape — the bucket
        scheduler bounds those to ``max_width_buckets`` per cohort."""
        alg = self.alg

        def partial_body(params, cohort, client_states, rng):
            outs = _cohort_outputs(alg, params, cohort, client_states, rng)
            w = outs.weight.astype(jnp.float32)
            sum_wu = jax.tree.map(
                lambda u: jnp.tensordot(w, u.astype(jnp.float32), axes=(0, 0)),
                outs.update,
            )
            return sum_wu, w.sum(), outs.state, outs.metrics

        if self._use_device_data:
            def partial_step(params, cohort, client_states, rng, x_all, y_all):
                data = _gather_from_device(dict(cohort), x_all, y_all)
                return partial_body(params, data, client_states, rng)
        else:
            partial_step = partial_body

        n_extra = 2 if self._use_device_data else 0
        if self.mesh is not None:
            cohort_sh = shard_along(self.mesh, self.cfg.cohort_shard_axis, 0)
            rep = replicated(self.mesh)
            return jax.jit(
                partial_step,
                in_shardings=(rep, cohort_sh, cohort_sh, rep) + (rep,) * n_extra,
                out_shardings=(rep, rep, cohort_sh, cohort_sh),
            )
        return jax.jit(partial_step)

    def _build_finalize_step(self) -> Callable:
        """Combine bucket partial sums into the weighted mean + server update.
        Requires the update pytree to mirror the params pytree (true for the
        mean-aggregating algorithms bucketing supports)."""
        alg = self.alg

        def finalize(params, server_state, sum_wu, total_w):
            total = jnp.maximum(total_w, 1.0)
            agg = jax.tree.map(
                lambda s, p: (s / total).astype(p.dtype), sum_wu, params
            )
            return alg.server_update(params, agg, server_state)

        # sum_wu (arg 2) is donated too: the partial sums are dead once the
        # mean exists, and at model scale they are a full f32 param copy
        if self.mesh is not None:
            rep = replicated(self.mesh)
            return jax.jit(
                finalize,
                in_shardings=(rep, rep, rep, rep),
                out_shardings=(rep, rep),
                donate_argnums=(0, 1, 2),
            )
        return jax.jit(finalize, donate_argnums=(0, 1, 2))

    def _build_eval(self, apply_fn):
        eval_fn = make_eval_fn(apply_fn, self.cfg.loss_kind)

        def eval_batches(params, xs, ys, ms):
            def body(carry, batch):
                x, y, m = batch
                loss_sum, correct, valid = eval_fn(params, x, y, m)
                l, c, n = carry
                return (l + loss_sum, c + correct, n + valid), None

            (l, c, n), _ = jax.lax.scan(body, (0.0, 0.0, 0.0), (xs, ys, ms))
            return l, c, n

        return jax.jit(eval_batches)

    # --- host-side round loop ---------------------------------------------

    def _cohort_states(self, client_ids: np.ndarray) -> PyTree:
        states = []
        for c in client_ids:
            s = self.client_states.get(int(c))
            if s is None:
                s = self._client_state_proto
            if self.alg.prepare_client_state is not None:
                s = self.alg.prepare_client_state(self.server_state, s)
            states.append(s)
        if not states or states[0] == ():
            return jax.tree.map(lambda *_: None, ())  # empty tuple states
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def _store_states(self, client_ids: np.ndarray, stacked_states) -> None:
        if stacked_states == ():
            return
        for i, c in enumerate(client_ids):
            self.client_states[int(c)] = jax.tree.map(lambda x: x[i], stacked_states)

    def _take_pregathered(self, attr: str, round_idx: int, key: bytes):
        """Consume a pregathered (double-buffered) arena stack if it matches
        this round's gather ids; any non-match is dropped so a stale stack
        can never be fed to the wrong cohort."""
        pg = getattr(self, attr)
        setattr(self, attr, None)
        if pg is not None and pg[0] == round_idx and pg[1] == key:
            return pg[2]
        return None

    def _try_move(self, arena, attr: str, next_inputs, ids: np.ndarray,
                  new_rows) -> bool:
        """Dispatch this round's scatter fused with round r+1's gather
        (``ClientStateArena.put_take``) while the round step is still in
        flight. False (arena untouched) when the next cohort cannot be made
        resident without evicting a row whose scatter is pending — the
        caller then scatters now and round r+1 gathers normally."""
        nids = next_inputs.client_ids
        npad = self._cohort_pad
        g = nids if not npad else np.concatenate(
            [nids, np.repeat(nids[-1], npad)])
        stacked = arena.put_take(ids, new_rows, g)
        if stacked is None:
            return False
        setattr(self, attr, (next_inputs.round_idx, g.tobytes(), stacked))
        return True

    def _gather_states(self, client_ids: np.ndarray) -> PyTree:
        """Stacked, prepared cohort states. Arena backend: one jitted take
        (+ the vectorized prepare); dict backend: the legacy per-client
        loop, kept as the bit-exactness oracle."""
        if self._arena is None:
            return self._cohort_states(client_ids)
        stacked = self._arena.gather(client_ids)
        if self._prepare_fn is not None:
            stacked = self._prepare_fn(self.server_state, stacked)
        return stacked

    def _scatter_states(self, client_ids: np.ndarray, stacked_states) -> None:
        if stacked_states == ():
            return
        if self._arena is None:
            self._store_states(client_ids, stacked_states)
            return
        self._arena.scatter(client_ids, stacked_states)

    def run(self, apply_fn=None, log_fn=print) -> List[Dict[str, float]]:
        cfg = self.cfg
        base_rng = jax.random.PRNGKey(cfg.seed)
        start_round, ckpt = 0, None
        if cfg.checkpoint_dir:
            from ..utils.checkpoint import CheckpointManager, restore_simulator_state

            ckpt = CheckpointManager(cfg.checkpoint_dir)
            if cfg.resume and ckpt.latest_step() is not None:
                start_round = restore_simulator_state(ckpt, self)
                if log_fn:
                    log_fn(f"[resume] from round {start_round} @ {cfg.checkpoint_dir}")
        rounds = range(start_round, cfg.comm_round)
        if cfg.watchdog_factor > 0:
            # self-healing mode: every round is synchronous (its watchdog
            # verdict gates the next dispatch), so no prefetch pipeline and
            # no deferred readback
            self._last_round_end = time.perf_counter()
            self._run_selfheal(rounds, base_rng, apply_fn, ckpt, log_fn)
            # end-of-run drain: wall-clock must cover in-flight device work
            # — graftcheck: disable=host-sync
            jax.block_until_ready(self.params)
            if ckpt is not None:
                ckpt.close()
            telemetry.flush()
            return self.history
        if self._scan_rounds > 1:
            if self._round_gate is not None:
                raise ScanIncompatibleError(
                    "rounds_per_dispatch > 1 under the multi-tenant round "
                    "gate — fair mesh sharing needs per-round dispatches; "
                    "run with rounds_per_dispatch=1")
            self._run_scan(rounds, base_rng, apply_fn, ckpt, log_fn)
            # end-of-run drain, same contract as the per-round loop —
            # graftcheck: disable=host-sync
            jax.block_until_ready(self.params)
            if ckpt is not None:
                ckpt.close()
            telemetry.flush()
            return self.history
        if cfg.prefetch and len(rounds) > 0:
            from .prefetch import RoundPrefetcher

            self._prefetcher = RoundPrefetcher(
                self.build_round_inputs, rounds, depth=cfg.prefetch_depth)
        pending = None  # deferred round record awaiting its metric readback
        self._last_round_end = time.perf_counter()
        try:
            for round_idx in rounds:
                if self._round_gate is not None:
                    self._round_gate(round_idx)
                # host stall on packing: with the pipeline warm this is a
                # queue pop (~µs) while pack_time was spent on the worker
                # under the PREVIOUS round's device compute
                with self._phase("pack_wait") as pw:
                    if self._prefetcher is not None:
                        inputs = self._prefetcher.get(round_idx)
                    else:
                        inputs = self.build_round_inputs(round_idx)
                step_rng = jax.random.fold_in(base_rng, round_idx)
                with self._phase("dispatch", str(round_idx)):
                    if inputs.kind == "packed":
                        metrics_vec = self._dispatch_packed(inputs, step_rng)
                    elif inputs.kind == "bucketed":
                        metrics_vec = self._dispatch_bucketed(inputs, step_rng)
                    else:
                        metrics_vec = self._dispatch_even(inputs, step_rng)
                pending = self._defer_rec(
                    round_idx, pw.start, metrics_vec, pending, apply_fn,
                    ckpt, log_fn, self._pack_timing(inputs.pack_time, pw.wall),
                )
        finally:
            # pregathered stacks are only valid within one prefetched run
            self._pregathered_state = self._pregathered_codec = None
            if self._prefetcher is not None:
                self._prefetcher.close()
                self._prefetcher = None
        if pending is not None:
            self._finalize_rec(pending, apply_fn, ckpt, log_fn)
        # drain the async dispatch queue: per-round host reads (metric
        # scalars) can complete before the executables fully retire, so
        # without this the caller's wall-clock over run() — and the last
        # rounds' attribution — would under-count device work still in
        # flight; once per run, not per round — graftcheck: disable=host-sync
        jax.block_until_ready(self.params)
        if ckpt is not None:
            ckpt.close()
        telemetry.flush()
        return self.history

    def _run_selfheal(self, rounds, base_rng, apply_fn, ckpt, log_fn) -> None:
        """Divergence watchdog + rollback round loop.

        Each round runs synchronously; its train loss (computed from the
        params the round STARTED from) is checked against
        ``watchdog_factor x median(last watchdog_window accepted losses)``,
        and the round's OUTPUT params against non-finiteness. On a verdict
        of bad, the state is restored — to the last-good snapshot when the
        start params are suspect (loss spike / non-finite loss), or to this
        round's own start state when only the output is damaged — and the
        round re-runs with the suspect clients (robust z >=
        ``rollback_z_thresh`` on the failed attempt, else the single worst)
        excluded, at most ``max_rollbacks`` times. A round whose metrics
        validate its start params promotes that start state to last-good.

        Snapshots COPY every leaf: the round step donates its params/server
        -state buffers, so a bare reference would die at the next dispatch.
        Host RNG needs no snapshot — every stream is round-indexed
        (``build_round_inputs`` is pure in (seed, round)), so a re-run draws
        identical randomness by construction.
        """
        cfg = self.cfg
        reg = telemetry.get_registry()

        def snap():
            return (jax.tree.map(jnp.copy, self.params),
                    jax.tree.map(jnp.copy, self.server_state),
                    dict(self.client_states),
                    None if self._arena is None else self._arena.snapshot())

        def restore(state):
            params, server_state, client_states, arena_snap = state
            # re-copy: the restored arrays get donated by the next dispatch,
            # and the same snapshot may need restoring again later
            self.params = jax.tree.map(jnp.copy, params)
            self.server_state = jax.tree.map(jnp.copy, server_state)
            self.client_states = dict(client_states)
            if arena_snap is not None:
                self._arena.restore(arena_snap)

        if self._finite_fn is None:
            from ..core.robust import tree_finite

            # same last-good gate the serving canary applies to committed
            # versions (core/robust.tree_finite) — one shared definition of
            # "this model is servable"
            self._finite_fn = jax.jit(tree_finite)
        last_good = snap()
        window: List[float] = []
        for round_idx in rounds:
            if self._round_gate is not None:
                self._round_gate(round_idx)
            excluded: set = set()  # cohort positions, grows across retries
            attempts = 0
            t0 = time.perf_counter()
            while True:
                with self._phase("pack_wait"):
                    inputs = self.build_round_inputs(
                        round_idx, exclude=excluded)
                start_state = snap()
                step_rng = jax.random.fold_in(base_rng, round_idx)
                with self._phase("dispatch", str(round_idx)):
                    metrics_vec = self._dispatch_even(inputs, step_rng)
                # sync by design: the watchdog verdict gates the next round's
                # dispatch, so self-heal mode cannot defer this readback
                mvec = np.asarray(metrics_vec)  # graftcheck: disable=host-sync
                qz = np.asarray(self._last_qz)  # graftcheck: disable=host-sync
                loss = float(mvec[0])
                spike = (len(window) > 0 and np.isfinite(loss)
                         and loss > cfg.watchdog_factor * float(
                             np.median(window)))
                start_suspect = not np.isfinite(loss) or spike
                bad = start_suspect or not bool(self._finite_fn(self.params))
                if not bad or attempts >= cfg.max_rollbacks:
                    if bad and log_fn:
                        log_fn(f"[watchdog] round {round_idx}: still "
                               f"degraded after {attempts} rollbacks — "
                               f"accepting (loss={loss:.4g})")
                    break
                new_excl = {int(i) for i in np.nonzero(
                    qz[1] >= cfg.rollback_z_thresh)[0]} - excluded
                if not new_excl:
                    z = qz[1].copy()
                    if excluded:
                        z[list(excluded)] = -np.inf
                    cand = int(np.argmax(z))
                    if np.isfinite(z[cand]) and cand not in excluded:
                        new_excl = {cand}
                if (not new_excl
                        or len(excluded | new_excl) >= len(inputs.client_ids)):
                    if log_fn:
                        log_fn(f"[watchdog] round {round_idx}: diverged but "
                               f"no (further) suspects to exclude — "
                               f"accepting (loss={loss:.4g})")
                    break
                excluded |= new_excl
                attempts += 1
                restore(last_good if start_suspect else start_state)
                if reg.enabled:
                    reg.counter("fedml_rollbacks_total").inc()
                trace_plane.record_instant(
                    "rollback", round_idx=round_idx,
                    attrs={"attempt": attempts,
                           "excluded": sorted(
                               int(inputs.client_ids[p]) for p in excluded)})
                trace_plane.flight_dump("watchdog_rollback")
                if log_fn:
                    ids = sorted(int(inputs.client_ids[p]) for p in excluded)
                    log_fn(f"[watchdog] round {round_idx}: rollback "
                           f"#{attempts} (loss={loss:.4g}, "
                           f"{'start' if start_suspect else 'output'} "
                           f"suspect) — re-running without clients {ids}")
            rec = {
                "round": round_idx,
                "dispatch_time": time.perf_counter() - t0,
                "_mvec": metrics_vec,
                "_qz": self._last_qz,
                "_cohort_ids": inputs.client_ids,
                "rollbacks": attempts,
            }
            self._last_qz = self._last_cohort_ids = None
            if excluded:
                rec["_extra_quarantined"] = [
                    int(inputs.client_ids[p]) for p in excluded]
            if not bad:
                last_good = start_state
                window.append(loss)
                del window[:-max(1, cfg.watchdog_window)]
            self._finalize_rec(rec, apply_fn, ckpt, log_fn)

    def attach_publisher(self, publish_fn) -> None:
        """Arm the commit→publish hook: ``publish_fn(version, params)`` runs
        after every round's params commit with a COPIED pytree (the round
        step donates ``self.params`` into the next dispatch, so the
        published reference must own its buffers — the watchdog snapshot
        discipline). ``version`` is the committed model version (rounds
        folded so far). ``None`` detaches; detached (the default) the round
        loop is byte-identical to a build without serving."""
        self._publisher = publish_fn

    def _publish_params(self, version: int) -> None:
        if self._publisher is None:
            return
        with self._phase("publish"):
            self._publisher(int(version), jax.tree.map(jnp.copy, self.params))

    # The one alias: the phase ``dispatch`` of rec["phases"] is the span,
    # and the MLOps event, ``round_dispatch``.
    _PHASE_SPAN = {"dispatch": "round_dispatch"}
    # The MLOps wire carries these two events and no other.
    _MLOPS_EVENTS = frozenset({"round_dispatch", "host_pack"})
    # Runs on the prefetch thread when there is one, beside the round and not
    # inside it (its share of round_time is what pack_wait waited): a span
    # and an event, never an entry of the accumulator, which only the round
    # loop's thread touches.
    _BESIDE_THE_ROUND = "host_pack"

    @contextlib.contextmanager
    def _phase(self, name: str, value: Optional[str] = None):
        """The one clock of the round loop's host side. Every named phase
        runs inside it: a span of the telemetry tracer (``fedml:<name>`` on
        a device trace; nothing with telemetry off), for ``_MLOPS_EVENTS``
        the profiler's started / ended events, and on exit the phase's OWN
        time (its wall less that of the phases opened inside it) added to
        the accumulator that ``_drain_phases`` empties into
        ``rec["phases"]``. Yields the :class:`_Phase`."""
        span_name = self._PHASE_SPAN.get(name, name)
        accrues = name != self._BESIDE_THE_ROUND
        mlops = (self._profiler.span(span_name, event_value=value)
                 if self._profiler is not None
                 and span_name in self._MLOPS_EVENTS
                 else contextlib.nullcontext())
        ph = _Phase(name)
        if accrues:
            self._phase_stack.append(ph)
        try:
            with telemetry.get_tracer().span(span_name, value=value), mlops:
                yield ph
        finally:
            ph.end = time.perf_counter()
            if accrues:
                self._phase_stack.pop()
                if self._phase_stack:
                    self._phase_stack[-1].inner += ph.wall
                self._phase_acc[ph.name] = (
                    self._phase_acc.get(ph.name, 0.0) + ph.wall - ph.inner)

    def _drain_phases(self) -> Dict[str, float]:
        """Everything the host did between the previous completion stamp and
        this one, keyed by phase; the accumulator starts again empty."""
        phases, self._phase_acc = self._phase_acc, {}
        return phases

    @staticmethod
    def _pack_timing(pack_time: float, pack_wait: float) -> Dict[str, float]:
        return {
            "pack_time": pack_time,
            "pack_wait": pack_wait,
            # fraction of this round's host packing hidden behind earlier
            # device work (0 when synchronous)
            "overlap": (max(0.0, 1.0 - pack_wait / pack_time)
                        if pack_time > 0 else 0.0),
        }

    def _paused_prefetch(self):
        """Sync point: guarantees the prefetch worker is quiescent for the
        block (eval hooks / checkpoint writes must never race a background
        build — see prefetch.py's contract)."""
        if self._prefetcher is not None:
            return self._prefetcher.paused()
        return contextlib.nullcontext()

    def _defer_rec(self, round_idx, t0, metrics_vec, pending,
                   apply_fn, ckpt, log_fn, timing=None):
        """Deferred metric readback: finalize the PREVIOUS round's record now
        that this round is dispatched, so its device->host transfer overlaps
        this round's compute instead of stalling the pipeline. Rounds that
        evaluate or checkpoint must see the params of their own round, so
        those finalize immediately (a sync point). Returns the new pending
        record (or None)."""
        cfg = self.cfg
        rec = {
            "round": round_idx,
            "dispatch_time": time.perf_counter() - t0,
            "_mvec": metrics_vec,
        }
        if timing:
            rec.update(timing)
        if self._last_qz is not None:
            rec["_qz"] = self._last_qz
            rec["_cohort_ids"] = self._last_cohort_ids
            self._last_qz = self._last_cohort_ids = None
        if pending is not None:
            self._finalize_rec(pending, apply_fn, ckpt, log_fn)
        if (apply_fn is not None and self._should_eval(round_idx)) or (
            ckpt is not None and self._should_checkpoint(round_idx)
        ):
            self._finalize_rec(rec, apply_fn, ckpt, log_fn)
            return None
        return rec

    def _should_eval(self, round_idx: int) -> bool:
        cfg = self.cfg
        return (round_idx % cfg.frequency_of_the_test == 0
                or round_idx == cfg.comm_round - 1)

    def _should_checkpoint(self, round_idx: int) -> bool:
        cfg = self.cfg
        return ((round_idx + 1) % cfg.checkpoint_frequency == 0
                or round_idx == cfg.comm_round - 1)

    def _finalize_rec(self, rec, apply_fn, ckpt, log_fn) -> None:
        """Materialize a round record's deferred metric vector (ONE small
        device->host transfer) and run the post-round bookkeeping.
        ``round_time`` = wall between successive round COMPLETIONS (the
        metric read proves the round's executables retired); with the
        pipelined readback this is the honest per-round throughput number —
        the raw host dispatch time is kept as ``dispatch_time``."""
        # the blocking readback IS the wait on device compute still in flight
        with self._phase("device") as dev:
            mvec = np.asarray(rec.pop("_mvec"))
        now = dev.end
        rec["round_time"] = now - self._last_round_end
        self._last_round_end = now
        rec["train_loss"] = float(mvec[0])
        rec["train_acc"] = float(mvec[1])
        if "_qz" in rec:
            qz = np.asarray(rec.pop("_qz"))
            ids = rec.pop("_cohort_ids")
            quarantined = sorted(
                {int(ids[i]) for i in np.nonzero(qz[0] > 0)[0]}
                | set(rec.pop("_extra_quarantined", ())))
            rec["quarantined"] = quarantined
            if quarantined:
                reg0 = telemetry.get_registry()
                if reg0.enabled:
                    reg0.counter("fedml_quarantined_total").inc(
                        len(quarantined))
                trace_plane.record_instant(
                    "quarantine", round_idx=rec["round"],
                    attrs={"clients": quarantined})
        # the remainder (logging, bookkeeping, deferred eval of earlier
        # rounds' records...) is host_other, so the breakdown sums to
        # round_time
        phases = self._drain_phases()
        phases["host_other"] = max(
            0.0, rec["round_time"] - sum(phases.values()))
        rec["phases"] = phases
        reg = telemetry.get_registry()
        if reg.enabled:
            reg.counter("fedml_rounds_total").inc()
            reg.histogram("fedml_round_seconds").observe(rec["round_time"])
            for name, dt in phases.items():
                reg.histogram(
                    "fedml_round_phase_seconds", phase=name).observe(dt)
            if rec.get("pack_time"):
                # overlapped with the previous round's device compute, so
                # tracked separately — NOT part of the round_time breakdown
                reg.histogram(
                    "fedml_host_pack_seconds").observe(rec["pack_time"])
            # per-round HBM watermark (model-sharding headroom signal);
            # CPU/interpret backends report no memory_stats — skip quietly
            for d in jax.local_devices():
                try:
                    ms = d.memory_stats() or {}
                except Exception:
                    ms = {}
                peak = ms.get("peak_bytes_in_use")
                if peak is not None:
                    reg.gauge("fedml_device_hbm_peak_bytes",
                              device=str(d)).set(float(peak))
        # trace plane: phase record for the Chrome export + flight ring,
        # anomaly/recompile detection annotating rec (= history[i]) in place
        trace_plane.on_round_record(rec)
        self._post_round(rec, rec["round"], apply_fn, ckpt, log_fn)

    def _post_round(self, rec, round_idx, apply_fn, ckpt, log_fn) -> None:
        # eval hooks and checkpoint writes run with the prefetch worker
        # quiescent (forced sync point — the builder is pure, but user
        # test_on_the_server hooks may touch the dataset, and np.random's
        # global state must not be shared mid-build)
        need_sync = (apply_fn is not None and self._should_eval(round_idx)) \
            or (ckpt is not None and self._should_checkpoint(round_idx))
        with self._paused_prefetch() if need_sync else contextlib.nullcontext():
            self._post_round_body(rec, round_idx, apply_fn, ckpt, log_fn)

    def _post_round_body(self, rec, round_idx, apply_fn, ckpt, log_fn) -> None:
        if apply_fn is not None and self._should_eval(round_idx):
            # the model-sharded path's params gather opens "reshard" inside
            with self._phase("eval"):
                handled = False
                if self._server_tester is not None:
                    # reference signature (FedAVGAggregator.py:130): the real
                    # device + the original args, not None placeholders —
                    # ported aggregators read args.* and the device
                    res = self._server_tester.test_on_the_server(
                        self.fed.train_data_local_dict,
                        self.fed.test_data_local_dict,
                        jax.devices()[0], self._hook_args,
                    )
                    if res:  # truthy return replaces the default evaluation
                        handled = True
                        if isinstance(res, dict):
                            rec.update(res)
                if not handled:
                    rec.update(self.evaluate(apply_fn))
                    if self.cfg.local_test_on_all_clients:
                        rec.update(self.local_test_on_all_clients(apply_fn))
        self.history.append(rec)
        # commit→publish: version = rounds folded (resume-stable, monotone —
        # a pending record always finalizes before the next one is created).
        # With deferred readback this record may finalize after later rounds
        # dispatched, so self.params may already be a NEWER commit than this
        # version number; serving callers that need exact round↔version
        # pairing run with frequency_of_the_test=1 (every record finalizes
        # synchronously before the next dispatch).
        self._publish_params(int(round_idx) + 1)
        if ckpt is not None and self._should_checkpoint(round_idx):
            from ..utils.checkpoint import save_simulator_state

            with self._phase("checkpoint"):
                save_simulator_state(ckpt, self, round_idx)
        if log_fn:
            log_fn(f"[round {round_idx}] " + " ".join(
                f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in rec.items() if k not in ("round", "per_client", "phases")
            ))

    def _client_perms(self, client_ids, round_idx: int):
        """Per-client local-epoch shuffles, seeded by (run seed, round,
        client id) — identical whichever order/schedule packs the cohort.
        Drawn by ``sampling.client_permutations``, the vectorized bit-exact
        reimplementation of ``default_rng([seed, round, cid]).permutation``
        (constructing 10k Generators per round cost ~200 ms of host time;
        the vectorized streams cost ~10 ms and self-verify per call)."""
        sizes = [len(self.fed.train_data_local_dict[int(c)])
                 for c in client_ids]
        return client_permutation_list(
            self.cfg.seed, round_idx, np.asarray(client_ids), sizes)

    # --- pure round-input builders (prefetchable host side) -----------------

    def build_round_inputs(self, round_idx: int,
                           exclude=None) -> RoundInputs:
        """The whole host side of one round as a pure function of
        ``(seed, round_idx)``: client sampling, drop mask, per-client
        shuffles, and the schedule's cohort tensors — every RNG stream is
        round-indexed, so the prefetch worker may run this ahead of the
        round loop and the result is bit-identical to inline packing.
        Reads no mutable simulator state (params, client_states, history).

        ``exclude`` (watchdog rollback re-runs only): cohort POSITIONS whose
        clients sit out this build — they are folded into the drop mask
        after sampling, so the cohort itself (and every other client's RNG
        stream) is unchanged vs the original run of the round."""
        cfg = self.cfg
        with self._phase("host_pack", str(round_idx)) as pack:
            client_ids = np.asarray(sample_clients(
                cfg.seed, round_idx,
                cfg.client_num_in_total, cfg.client_num_per_round,
            ))
            # round-indexed RNG streams: resume at round k reproduces an
            # uninterrupted run exactly
            pack_rng = np.random.default_rng([cfg.seed, round_idx])
            # drop mask is drawn FIRST (before any packing) and the
            # per-client shuffle comes from per-client-seeded generators, so
            # all schedules consume identical randomness whatever order they
            # pack clients in
            drop = None
            if cfg.client_dropout_rate > 0.0:
                drop = pack_rng.random(len(client_ids)) < cfg.client_dropout_rate
                if drop.all():
                    drop[0] = False  # a round needs at least one survivor
            if exclude:
                excl = np.zeros(len(client_ids), bool)
                excl[list(exclude)] = True
                drop = excl if drop is None else (drop | excl)
            if self._packed:
                kind = "packed"
                payload = self._build_packed_inputs(client_ids, round_idx, drop)
            elif self._bucketed:
                kind = "bucketed"
                payload = self._build_bucketed_inputs(client_ids, round_idx, drop)
            else:
                kind = "even"
                payload = self._build_even_inputs(client_ids, round_idx, drop)
        return RoundInputs(round_idx, client_ids, drop, kind, payload,
                           pack.wall)

    def _build_even_inputs(self, client_ids, round_idx: int, drop):
        cfg = self.cfg
        perms = self._client_perms(client_ids, round_idx)
        if self._use_device_data:
            packed = self.fed.pack_client_index(
                client_ids, cfg.batch_size, self.num_local_batches,
                perms=perms,
            )
            payload = {"idx": packed.idx}
        else:
            packed = self.fed.pack_clients(
                client_ids, cfg.batch_size, self.num_local_batches,
                perms=perms,
            )
            payload = {"x": packed.x, "y": packed.y}
        mask_np, samples_np = packed.mask, packed.num_samples
        if drop is not None:
            mask_np = mask_np * (~drop)[:, None, None]
            samples_np = samples_np * (~drop)
        pad = self._cohort_pad
        if pad:
            # shard-aware packing: zero-weight, zero-mask rows bring the
            # cohort to a multiple of the mesh axis size; the padding mask
            # rides in as those zeroed weights/masks, and pos keeps counting
            # so padded rows fold distinct (unused) RNG streams
            def _zpad(a):
                return np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])

            payload = {k: _zpad(v) for k, v in payload.items()}
            mask_np = _zpad(mask_np)
            samples_np = _zpad(samples_np)
        payload["mask"] = mask_np
        payload["num_samples"] = samples_np
        payload["pos"] = np.arange(len(client_ids) + pad, dtype=np.uint32)
        return payload

    # --- compiled multi-round dispatch (rounds_per_dispatch > 1) -----------

    def _ensure_idx_registry(self):
        """Dense (rows, sizes, id->row lut) view of the per-client global
        index lists — built once, so a block packer can gather every round's
        index rectangle with bulk numpy ops instead of a 10k-iteration
        per-client list walk."""
        if self._idx_registry is None:
            gi = self.fed._global_index
            keys = np.fromiter(gi.keys(), dtype=np.int64, count=len(gi))
            sizes = np.fromiter((len(gi[int(k)]) for k in keys),
                                dtype=np.int64, count=len(keys))
            max_len = int(sizes.max()) if len(keys) else 0
            reg = np.zeros((len(keys), max(max_len, 1)), dtype=np.int64)
            for row, k in enumerate(keys):
                ix = gi[int(k)]
                reg[row, : len(ix)] = ix
            lut = np.full(int(keys.max()) + 1 if len(keys) else 1, -1,
                          dtype=np.int64)
            lut[keys] = np.arange(len(keys))
            self._idx_registry = (reg, sizes, lut)
        return self._idx_registry

    def build_block_inputs(self, rounds) -> BlockInputs:
        """The host side of one scanned block, pure in ``(seed, rounds)``:
        every round's cohort sample, dropout mask, per-client shuffles, and
        index rectangle, stacked along a leading round axis. Produces
        tensors bit-identical to ``build_round_inputs`` round by round
        (``tests/test_round_scan.py`` pins that equivalence), built with
        the vectorized permutation streams and one registry gather per
        round instead of per-client Python loops."""
        cfg = self.cfg
        with self._phase("host_pack", f"{rounds[0]}+{len(rounds)}") as pack:
            reg, sizes_all, lut = self._ensure_idx_registry()
            rounds = tuple(int(r) for r in rounds)
            L = len(rounds)
            pad = self._cohort_pad
            c_real = int(cfg.client_num_per_round)
            cohort_n = c_real + pad
            nb, bs = self.num_local_batches, cfg.batch_size
            cap = nb * bs
            idx = np.zeros((L, cohort_n, nb, bs), np.int32)
            ns_out = np.zeros((L, cohort_n), np.int32)
            ids = np.empty((L, c_real), np.int64)
            arange_cap = np.arange(cap, dtype=np.int64)
            for k, r in enumerate(rounds):
                cids = np.asarray(sample_clients(
                    cfg.seed, r, cfg.client_num_in_total, c_real))
                ids[k] = cids
                rows = lut[cids]
                csz = sizes_all[rows]
                n_c = np.minimum(csz, cap)
                # same streams as the per-round packer: one permutation per
                # client from default_rng([seed, round, cid]), trimmed to
                # the batch-rectangle capacity
                perm = client_permutations(cfg.seed, r, cids, csz, cap=cap)
                r_idx = np.zeros((c_real, cap), np.int64)
                w = perm.shape[1]
                if w:
                    r_idx[:, :w] = np.take_along_axis(
                        reg[rows][:, : max(w, 1)], perm, axis=1)
                r_idx[arange_cap[None, :] >= n_c[:, None]] = 0
                n_eff = n_c.astype(np.int32)
                if cfg.client_dropout_rate > 0.0:
                    pack_rng = np.random.default_rng([cfg.seed, r])
                    drop = (pack_rng.random(c_real)
                            < cfg.client_dropout_rate)
                    if drop.all():
                        drop[0] = False  # at least one survivor
                    n_eff = n_eff * (~drop)
                idx[k, :c_real] = r_idx.reshape(
                    c_real, nb, bs).astype(np.int32)
                ns_out[k, :c_real] = n_eff
            xs = {"idx": idx, "num_samples": ns_out,
                  "round": np.asarray(rounds, np.uint32)}
            if self._codec_rt is not None:
                gids = ids if not pad else np.concatenate(
                    [ids, np.repeat(ids[:, -1:], pad, axis=1)], axis=1)
                xs["cids_u32"] = gids.astype(np.uint32)
        return BlockInputs(rounds, ids, xs, pack.wall)

    def _build_block(self, block: tuple):
        """Prefetchable builder for one block plan entry: length-1 blocks
        (hook boundaries) reuse the per-round builder + program."""
        if len(block) == 1:
            return self.build_round_inputs(block[0])
        return self.build_block_inputs(block)

    def _plan_blocks(self, rounds, do_eval: bool, do_ckpt: bool):
        """Partition the round range into runs of at most
        ``rounds_per_dispatch`` consecutive rounds, cutting after every
        round that fires a host hook (eval/checkpoint) — hooks run on exact
        round indices with that round's own params, never mid-scan."""
        blocks, cur = [], []
        for r in rounds:
            cur.append(r)
            if ((do_eval and self._should_eval(r))
                    or (do_ckpt and self._should_checkpoint(r))
                    or len(cur) >= self._scan_rounds):
                blocks.append(tuple(cur))
                cur = []
        if cur:
            blocks.append(tuple(cur))
        return blocks

    def _run_scan(self, rounds, base_rng, apply_fn, ckpt, log_fn) -> None:
        """Round loop for ``rounds_per_dispatch > 1``: iterate the block
        plan, dispatching each multi-round block as one scanned program and
        each length-1 block (hook boundary, remainder) on the unchanged
        per-round program. Resume lands on any round index — the plan is
        re-derived from the resumed start round, and every carried bit
        (arena rows, EF residuals) is identical whichever side of a block
        boundary a round falls on."""
        cfg = self.cfg
        blocks = self._plan_blocks(
            rounds, apply_fn is not None, ckpt is not None)
        if cfg.prefetch and blocks:
            from .prefetch import RoundPrefetcher

            self._prefetcher = RoundPrefetcher(
                self._build_block, blocks, depth=cfg.prefetch_depth,
                name="block-prefetch")
        self._last_round_end = time.perf_counter()
        try:
            for block in blocks:
                with self._phase("pack_wait") as pw:
                    if self._prefetcher is not None:
                        inputs = self._prefetcher.get(block)
                    else:
                        inputs = self._build_block(block)
                if len(block) == 1:
                    self._run_one_round(inputs, pw, base_rng,
                                        apply_fn, ckpt, log_fn)
                else:
                    self._dispatch_scan_block(inputs, pw.start, base_rng,
                                              apply_fn, ckpt, log_fn)
        finally:
            self._pregathered_state = self._pregathered_codec = None
            if self._prefetcher is not None:
                self._prefetcher.close()
                self._prefetcher = None

    def _run_one_round(self, inputs: RoundInputs, pw: _Phase, base_rng,
                       apply_fn, ckpt, log_fn) -> None:
        """One round on the per-round program inside the scan loop —
        hook boundaries and capacity fallbacks; ``pw`` is its closed
        ``pack_wait`` phase. Finalized synchronously (these rounds
        evaluate/checkpoint, which are sync points anyway)."""
        r = inputs.round_idx
        step_rng = jax.random.fold_in(base_rng, r)
        with self._phase("dispatch", str(r)):
            metrics_vec = self._dispatch_even(inputs, step_rng)
        rec = {
            "round": r,
            "dispatch_time": time.perf_counter() - pw.start,
            "_mvec": metrics_vec,
            **self._pack_timing(inputs.pack_time, pw.wall),
        }
        if self._last_qz is not None:
            rec["_qz"] = self._last_qz
            rec["_cohort_ids"] = self._last_cohort_ids
            self._last_qz = self._last_cohort_ids = None
        self._finalize_rec(rec, apply_fn, ckpt, log_fn)

    def _dispatch_scan_block(self, inputs: BlockInputs, t0, base_rng,
                             apply_fn, ckpt, log_fn) -> None:
        """Dispatch one multi-round block: block-wide arena residency, one
        stacked upload, one donated scan call, one metric readback — then
        per-round records with amortized phases that still sum exactly to
        each round's ``round_time``."""
        cfg = self.cfg
        block = inputs.rounds
        L = len(block)
        pad = self._cohort_pad
        c_real = int(cfg.client_num_per_round)
        ids = inputs.ids
        gids = ids if not pad else np.concatenate(
            [ids, np.repeat(ids[:, -1:], pad, axis=1)], axis=1)
        xs = dict(inputs.xs)
        slots = cslots = None
        if self._arena is not None or self._codec_arena is not None:
            with self._phase("state_gather"):
                if self._arena is not None:
                    slots = self._arena.ensure_block(gids)
                if self._codec_arena is not None:
                    cslots = self._codec_arena.ensure_block(gids)
            if ((self._arena is not None and slots is None)
                    or (self._codec_arena is not None and cslots is None)):
                # the block's cohort union exceeds the arena capacity: the
                # LRU tier must spill between rounds, so run this block's
                # rounds on the per-round program (bit-identical history)
                if log_fn:
                    log_fn(f"[scan] block @{block[0]}+{L}: cohort union "
                           "exceeds client_state_capacity — running "
                           "per-round")
                for r in block:
                    with self._phase("pack_wait") as pw:
                        inp = self.build_round_inputs(r)
                    self._run_one_round(inp, pw, base_rng, apply_fn,
                                        ckpt, log_fn)
                return
        if slots is not None:
            xs["slots"] = slots.astype(np.int32)
        if cslots is not None:
            xs["codec_slots"] = cslots.astype(np.int32)
        step = self._scan_steps.get(L)
        fresh_program = step is None
        if fresh_program:
            step = self._build_scan_step(L)
            self._scan_steps[L] = step
        # one staged upload per block (a few KB/round of indices)
        with self._phase("scan_pack"):
            if self.mesh is not None:
                blk_sh = shard_along(self.mesh, cfg.cohort_shard_axis, 1)
                rep = replicated(self.mesh)
                xs_dev = {
                    k: jax.device_put(v, rep if v.ndim == 1 else blk_sh)
                    for k, v in xs.items()}
            else:
                xs_dev = {k: jnp.asarray(v) for k, v in xs.items()}
        arena_leaves = (self._arena.take_leaves()
                        if self._arena is not None else [])
        codec_leaves = (self._codec_arena.take_leaves()
                        if self._codec_arena is not None else [])
        with self._phase("dispatch", f"{block[0]}+{L}"):
            (self.params, self.server_state, new_arena, new_codec, ys) = step(
                self.params, self.server_state, arena_leaves,
                codec_leaves, base_rng, xs_dev)
            if self._arena is not None:
                self._arena.set_leaves(new_arena, slots[:, :c_real])
            if self._codec_arena is not None:
                self._codec_arena.set_leaves(new_codec, cslots[:, :c_real])
        if fresh_program:
            # the first block of a given length compiles its own program —
            # a planned event, not the recompile detector's business
            trace_plane.absorb_planned_compiles()
        dispatch_time = (time.perf_counter() - t0) / L
        if self._codec_rt is not None:
            raw, coded = self._codec_wire
            self._codec_record(
                "encode", raw * c_real * L, coded * c_real * L, 0.0)
        mvec_dev = ys[0]
        qz_dev = ys[1] if self._detect else None
        # ONE blocking readback per block; the wait IS the device phase
        # (deliberate sync point, same contract as _finalize_rec) —
        # graftcheck: disable=host-sync
        with self._phase("device") as dev:
            mvec = np.asarray(mvec_dev)  # graftcheck: disable=host-sync
            qz = (np.asarray(qz_dev)  # graftcheck: disable=host-sync
                  if qz_dev is not None else None)
        now = dev.end
        span = now - self._last_round_end
        self._last_round_end = now
        # amortized attribution: each interval the host spent on this block
        # splits evenly over its rounds; the remainder is host_other, so
        # every round's phases sum exactly to its round_time (= span / L)
        per_round = {k: v / L for k, v in self._drain_phases().items()}
        rt = span / L
        per_round["host_other"] = max(0.0, rt - sum(per_round.values()))
        reg = telemetry.get_registry()
        if reg.enabled:
            reg.counter("fedml_scan_blocks_total").inc()
        trace_plane.record_instant(
            "scan_block", round_idx=block[0],
            attrs={"rounds": L, "span_s": span})
        pack_time = inputs.pack_time / L
        pw = per_round.get("pack_wait", 0.0)
        for k, r in enumerate(block):
            rec = {
                "round": r,
                "dispatch_time": dispatch_time,
                "pack_time": pack_time,
                "pack_wait": pw,
                "overlap": (max(0.0, 1.0 - pw / pack_time)
                            if pack_time > 0 else 0.0),
                "scan_rounds": L,
                "train_loss": float(mvec[k, 0]),
                "train_acc": float(mvec[k, 1]),
                "round_time": rt,
                "phases": dict(per_round),
            }
            if qz is not None:
                qzk = qz[k][:, :c_real] if pad else qz[k]
                quarantined = sorted(
                    {int(ids[k][i]) for i in np.nonzero(qzk[0] > 0)[0]})
                rec["quarantined"] = quarantined
                if quarantined:
                    if reg.enabled:
                        reg.counter("fedml_quarantined_total").inc(
                            len(quarantined))
                    trace_plane.record_instant(
                        "quarantine", round_idx=r,
                        attrs={"clients": quarantined})
            if reg.enabled:
                reg.counter("fedml_rounds_total").inc()
                reg.histogram("fedml_round_seconds").observe(rt)
                for name, dt in rec["phases"].items():
                    reg.histogram(
                        "fedml_round_phase_seconds", phase=name).observe(dt)
                if pack_time:
                    reg.histogram(
                        "fedml_host_pack_seconds").observe(pack_time)
            trace_plane.on_round_record(rec)
            self._post_round(rec, r, apply_fn, ckpt, log_fn)

    def _dispatch_even(self, inputs: RoundInputs, step_rng):
        if self.mesh is not None:
            # explicit placement of the round's host tensors under the
            # cohort axis, timed as its own phase: on a 2-D mesh the same
            # stamp also carries the lazy params gather/reshard cost that
            # GSPMD schedules at dispatch, so round phases keep summing
            # exactly to round_time instead of hiding layout traffic in
            # dispatch/host_other
            with self._phase("reshard"):
                c_sh = shard_along(self.mesh, self.cfg.cohort_shard_axis, 0)
                cohort = {k: jax.device_put(np.asarray(v), c_sh)
                          for k, v in inputs.payload.items()}
        else:
            cohort = {k: jnp.asarray(v) for k, v in inputs.payload.items()}
        ids = inputs.client_ids
        pad = self._cohort_pad
        stateful = self._client_state_proto != ()
        # padded rows re-gather the last client's slot (zero weight/mask
        # keeps its extra update rows inert); only real rows scatter back
        gather_ids = ids if not pad else np.concatenate(
            [ids, np.repeat(ids[-1], pad)])
        gkey = gather_ids.tobytes()
        if stateful:
            # a matching pregathered stack (dispatched under the PREVIOUS
            # round's device shadow via put_take) makes this a tree
            # unflatten + the prepare dispatch; prepare must run at consume
            # time because it reads the previous round's server_state OUTPUT
            with self._phase("state_gather"):
                states = self._take_pregathered(
                    "_pregathered_state", inputs.round_idx, gkey)
                if states is not None:
                    if self._prepare_fn is not None:
                        states = self._prepare_fn(self.server_state, states)
                else:
                    states = self._gather_states(gather_ids)
        else:
            states = ()
        step_args = (self.params, self.server_state, cohort, states, step_rng)
        if self._codec_rt is not None:
            # EF residuals ride the same padded-gather pattern as client
            # state; the id vector keys each row's stochastic-rounding stream
            with self._phase("codec"):
                codec_res = ()
                if self._codec_arena is not None:
                    codec_res = self._take_pregathered(
                        "_pregathered_codec", inputs.round_idx, gkey)
                    if codec_res is None:
                        codec_res = self._codec_arena.gather(gather_ids)
                step_args += (codec_res,
                              jnp.asarray(gather_ids.astype(np.uint32)),
                              jnp.uint32(inputs.round_idx))
        if self._use_device_data:
            step_args += (self._x_dev, self._y_dev)
        out = self._round_step(*step_args)
        # peek (non-blocking) at round r+1's prefetched inputs NOW, with the
        # step freshly dispatched: a hit lets the arena scatter+next-gather
        # pair ride the device shadow as one fused put_take dispatch
        nxt = (self._prefetcher.peek(inputs.round_idx + 1)
               if self._prefetcher is not None else None)
        if nxt is not None and nxt.kind != "even":
            nxt = None
        if self._codec_arena is not None:
            *out, new_codec_res = out
        if self._detect:
            (self.params, self.server_state, new_states, metrics_vec,
             qz) = out
            self._last_qz = qz if not pad else qz[:, : len(ids)]
            self._last_cohort_ids = ids
        else:
            self.params, self.server_state, new_states, metrics_vec = out
        if stateful:
            with self._phase("state_scatter") as ph:
                if pad:
                    new_states = jax.tree.map(
                        lambda x: x[: len(ids)], new_states)
                if (nxt is not None and self._arena is not None
                        and self._try_move(self._arena, "_pregathered_state",
                                           nxt, ids, new_states)):
                    # the scatter AND round r+1's gather just dispatched
                    # under the in-flight step — filed as their own phase so
                    # state_gather/state_scatter honestly show only what is
                    # left on the between-rounds critical path (the span
                    # keeps the name it was entered under)
                    ph.name = "state_move"
                else:
                    self._scatter_states(ids, new_states)
        if self._codec_rt is not None:
            with self._phase("codec") as ph:
                if self._codec_arena is not None:
                    if pad:
                        new_codec_res = jax.tree.map(
                            lambda x: x[: len(ids)], new_codec_res)
                    if not (nxt is not None
                            and self._try_move(self._codec_arena,
                                               "_pregathered_codec",
                                               nxt, ids, new_codec_res)):
                        self._codec_arena.scatter(ids, new_codec_res)
            raw, coded = self._codec_wire
            self._codec_record(
                "encode", raw * len(ids), coded * len(ids), ph.wall)
        return metrics_vec

    def _packed_lane_plan(self, client_ids: np.ndarray, drop):
        """Round-independent structure of a packed round: lane assignment
        plus every permutation-independent lane tensor (mask, boundary,
        bweight, pos, sic) and the slot -> (client, batch-row) gather map.
        Cached across rounds keyed by the (cohort, drop) pattern — the
        per-round work left is the RNG shuffles and one bulk row gather.
        Full-participation runs hit the cache every round; sampled cohorts
        hit whenever the (cohort, drop) pattern repeats."""
        key = (client_ids.tobytes(),
               None if drop is None else drop.tobytes())
        plan = self._lane_plan_cache.get(key)
        if plan is not None:
            return plan
        from ..core.scheduler import lane_schedule

        cfg = self.cfg
        bs = cfg.batch_size
        epochs = int(self._packed_ctx[1].epochs)
        # dropped clients are excluded BEFORE lane assignment — their drop
        # mask is known host-side, so training them on zeroed data would
        # only inflate lane loads (review finding). Metric divisors still
        # use the FULL cohort size for parity with the even path, which
        # keeps dropped clients as zero-loss rows.
        cohort_n = len(client_ids)
        positions = np.arange(cohort_n)
        if drop is not None:
            positions = positions[~drop]
        counts = np.asarray([
            min(self._batch_counts[int(client_ids[p])], self.num_local_batches)
            for p in positions
        ], dtype=np.int64)
        lanes, L = lane_schedule(list(counts * epochs), self._axis_size,
                                 max_lanes=len(positions),
                                 force_lanes=cfg.packed_lanes)
        L_pad = -(-L // 4) * 4  # quantize: few compiled (G, L) shapes
        G = len(lanes)
        NB = int(counts.max()) if len(counts) else 1
        P = len(positions)
        # true per-client sample counts, capped at each client's own batch
        # budget (== the per-client packer's num_samples)
        n_samples = np.asarray([
            min(len(self.fed._global_index[int(client_ids[p])]), c * bs)
            for p, c in zip(positions, counts)
        ], dtype=np.int64)
        # slot -> flat row into the (P, NB) cohort index rectangle; row P*NB
        # is a dedicated all-zero pad row, so padded slots stay exactly the
        # zeros the per-client loop produced
        pad_row = P * NB
        srcmap = np.full((G, L_pad), pad_row, np.int64)
        slot_m = np.zeros((G, L_pad), np.int64)  # valid samples per slot row
        boundary = np.zeros((G, L_pad), np.float32)
        bweight = np.zeros((G, L_pad), np.float32)
        pos_arr = np.zeros((G, L_pad), np.uint32)
        sic = np.zeros((G, L_pad), np.int32)
        for g, lane in enumerate(lanes):
            if not lane:
                continue
            li = np.asarray(lane, dtype=np.int64)
            cs = counts[li]
            steps = cs * epochs
            total = int(steps.sum())
            # client index per slot, batch row per slot (epoch-tiled)
            cli = np.repeat(li, steps)
            row_b = np.concatenate([np.tile(np.arange(c), epochs) for c in cs])
            srcmap[g, :total] = cli * NB + row_b
            slot_m[g, :total] = n_samples[cli]
            pos_arr[g, :total] = positions[cli].astype(np.uint32)
            sic[g, :total] = np.concatenate(
                [np.arange(s, dtype=np.int64) for s in steps])
            ends = np.cumsum(steps) - 1
            boundary[g, ends] = 1.0
            bweight[g, ends] = n_samples[li].astype(np.float32)
        # mask depends only on per-client sample counts: slot row (i, b)
        # has min(n_i, c_i*bs) - b*bs valid entries (clipped to [0, bs])
        row_start = np.where(srcmap < pad_row, srcmap % NB, 0) * bs
        mask = ((np.arange(bs, dtype=np.int64)[None, None, :] + row_start[..., None]
                 < slot_m[..., None])).astype(np.float32)
        plan = {
            "G": G, "L_pad": L_pad, "NB": NB, "cohort_n": cohort_n,
            "positions": positions, "srcmap": srcmap, "mask": mask,
            "boundary": boundary, "bweight": bweight, "pos": pos_arr,
            "sic": sic,
        }
        if len(self._lane_plan_cache) >= 32:  # FIFO bound, dropout patterns
            self._lane_plan_cache.pop(next(iter(self._lane_plan_cache)))
        self._lane_plan_cache[key] = plan
        return plan

    def _build_packed_inputs(self, client_ids: np.ndarray, round_idx: int,
                             drop):
        """Host side of the packed schedule, vectorized: ONE cohort-level
        ``pack_client_index`` call (not one per client), the cached lane
        plan for everything permutation-independent, and a single bulk row
        gather (native ``pack_lane_rows`` when available) for the lane idx
        tensor. Bit-identical to ``_build_packed_inputs_loop``."""
        from .. import native

        cfg = self.cfg
        bs = cfg.batch_size
        plan = self._packed_lane_plan(client_ids, drop)
        positions = plan["positions"]
        sel_ids = client_ids[positions]
        if len(positions):
            perms = self._client_perms(sel_ids, round_idx)
            packed = self.fed.pack_client_index(sel_ids, bs, plan["NB"],
                                                perms=perms)
            rows = packed.idx.reshape(len(positions) * plan["NB"], bs)
        else:
            rows = np.zeros((0, bs), np.int32)
        # dedicated zero pad row (plan srcmap points padded slots here)
        rows = np.concatenate([rows, np.zeros((1, bs), np.int32)])
        idx = native.pack_lane_rows(rows, plan["srcmap"])
        return {
            "idx": idx, "mask": plan["mask"], "boundary": plan["boundary"],
            "bweight": plan["bweight"], "pos": plan["pos"], "sic": plan["sic"],
            "shape": (plan["G"], plan["L_pad"]), "cohort_n": plan["cohort_n"],
        }

    def _build_packed_inputs_loop(self, client_ids: np.ndarray,
                                  round_idx: int, drop):
        """Pre-pipeline reference packer: per-client Python loop with
        slice-by-slice lane writes. Kept as the bit-exactness oracle for
        ``_build_packed_inputs`` (``tests/test_prefetch.py``); it bypasses
        the lane-schedule memo cache (same result either way)."""
        from ..core.scheduler import _lane_schedule_cached

        cfg = self.cfg
        bs = cfg.batch_size
        epochs = int(self._packed_ctx[1].epochs)
        cohort_n = len(client_ids)
        positions = np.arange(cohort_n)
        if drop is not None:
            positions = positions[~drop]
        counts = [
            min(self._batch_counts[int(client_ids[p])], self.num_local_batches)
            for p in positions
        ]
        seq_counts = [c * epochs for c in counts]
        lanes, L = _lane_schedule_cached.__wrapped__(
            tuple(int(c) for c in seq_counts), int(self._axis_size),
            len(positions),
            None if cfg.packed_lanes is None else int(cfg.packed_lanes))
        L_pad = -(-L // 4) * 4
        G = len(lanes)
        idx = np.zeros((G, L_pad, bs), np.int32)
        mask = np.zeros((G, L_pad, bs), np.float32)
        boundary = np.zeros((G, L_pad), np.float32)
        bweight = np.zeros((G, L_pad), np.float32)
        pos_arr = np.zeros((G, L_pad), np.uint32)
        sic = np.zeros((G, L_pad), np.int32)
        for g, lane in enumerate(lanes):
            t = 0
            for i in lane:
                p = int(positions[i])  # original cohort position (RNG key)
                cid = int(client_ids[p])
                c = counts[i]
                perm = self._client_perms([cid], round_idx)[0]
                packed = self.fed.pack_client_index([cid], bs, c, perms=[perm])
                for e in range(epochs):
                    idx[g, t:t + c] = packed.idx[0]
                    mask[g, t:t + c] = packed.mask[0]
                    pos_arr[g, t:t + c] = p
                    sic[g, t:t + c] = np.arange(e * c, (e + 1) * c)
                    t += c
                boundary[g, t - 1] = 1.0
                bweight[g, t - 1] = float(packed.num_samples[0])
        return {
            "idx": idx, "mask": mask, "boundary": boundary,
            "bweight": bweight, "pos": pos_arr, "sic": sic,
            "shape": (G, L_pad), "cohort_n": cohort_n,
        }

    def _dispatch_packed(self, inputs: RoundInputs, step_rng):
        p = inputs.payload
        cohort = {
            k: jnp.asarray(p[k])
            for k in ("idx", "mask", "boundary", "bweight", "pos", "sic")
        }
        # introspection for tests/driver dryrun: lane grid of the last round
        # (G is always a multiple of the mesh client axis, so per-device
        # shards are G/axis_size lanes)
        self._last_packed_shape = p["shape"]
        self.params, self.server_state, metrics_vec = self._packed_step(
            self.params, self.server_state, cohort, step_rng,
            jnp.float32(p["cohort_n"]), self._x_dev, self._y_dev,
        )
        return metrics_vec

    def _build_bucketed_inputs(self, client_ids: np.ndarray, round_idx: int,
                               drop):
        """Host side of the bucketed schedule: the exact-DP width classes
        and each bucket's packed payload, all numpy."""
        from ..core.scheduler import bucket_schedule

        cfg = self.cfg
        counts = [
            min(self._batch_counts[int(c)], self.num_local_batches)
            for c in client_ids
        ]
        buckets = bucket_schedule(
            counts, self._axis_size, cfg.max_width_buckets,
            max_width=self.num_local_batches,
        )
        out = []
        for positions, width in buckets:
            ids = client_ids[positions]
            n_real = len(ids)
            # slots = axis-multiple rounded up to a power-of-two multiplier,
            # so the set of compiled (slots, width) shapes stays small as
            # cohorts vary round to round
            per_axis = -(-n_real // self._axis_size)
            per_axis = 1 << (per_axis - 1).bit_length()
            slots = per_axis * self._axis_size
            pad = slots - n_real
            if pad:
                ids = np.concatenate([ids, np.repeat(ids[-1], pad)])
                positions = np.concatenate(
                    [positions, np.repeat(positions[-1], pad)]
                )
            perms = self._client_perms(ids, round_idx)
            if self._use_device_data:
                packed = self.fed.pack_client_index(
                    ids, cfg.batch_size, width, perms=perms
                )
                payload = {"idx": packed.idx}
            else:
                packed = self.fed.pack_clients(
                    ids, cfg.batch_size, width, perms=perms
                )
                payload = {"x": packed.x, "y": packed.y}
            mask_np, samples_np = packed.mask, packed.num_samples
            if pad:
                mask_np = mask_np.copy()
                samples_np = samples_np.copy()
                mask_np[n_real:] = 0
                samples_np[n_real:] = 0
            if drop is not None:
                d = drop[positions[:n_real]]
                mask_np = mask_np.copy()
                samples_np = samples_np.copy()
                mask_np[:n_real] *= (~d)[:, None, None]
                samples_np[:n_real] *= ~d
            payload["mask"] = mask_np
            payload["num_samples"] = samples_np
            payload["pos"] = positions.astype(np.uint32)
            out.append({"ids": ids, "n_real": n_real, "payload": payload})
        return out

    def _dispatch_bucketed(self, inputs: RoundInputs, step_rng):
        """Width-bucketed cohort execution (SimConfig.cohort_schedule doc):
        one partial-aggregation program per width-class, a single finalize.
        Numerically the same weighted mean as the even path (per-client RNG
        and shuffles keyed by cohort position / client id, f32 partial
        sums), modulo fp summation order."""
        sum_wu = None
        total_w = None
        # metric accumulators stay DEVICE scalars (lazy): the caller defers
        # the single readback so it overlaps the next round's compute
        loss_sum = correct_sum = valid_sum = None
        n_clients = 0
        stateful = self._client_state_proto != ()
        for bucket in inputs.payload:
            ids, n_real = bucket["ids"], bucket["n_real"]
            cohort = {k: jnp.asarray(v) for k, v in bucket["payload"].items()}
            if stateful:
                with self._phase("state_gather"):
                    states = self._gather_states(ids)
            else:
                states = ()
            step_args = (self.params, cohort, states, step_rng)
            if self._use_device_data:
                step_args += (self._x_dev, self._y_dev)
            swu, sw, new_states, mets = self._partial_step(*step_args)
            sum_wu = swu if sum_wu is None else jax.tree.map(jnp.add, sum_wu, swu)
            total_w = sw if total_w is None else total_w + sw
            if new_states != ():
                with self._phase("state_scatter"):
                    self._scatter_states(
                        ids[:n_real],
                        jax.tree.map(lambda x: x[:n_real], new_states),
                    )
            ls = mets["train_loss"][:n_real].sum()
            cs = mets["train_correct"][:n_real].sum()
            vs = mets["train_valid"][:n_real].sum()
            if loss_sum is None:
                loss_sum, correct_sum, valid_sum = ls, cs, vs
            else:
                loss_sum, correct_sum, valid_sum = (
                    loss_sum + ls, correct_sum + cs, valid_sum + vs
                )
            n_clients += n_real
        self.params, self.server_state = self._finalize_step(
            self.params, self.server_state, sum_wu, total_w
        )
        return jnp.stack([
            (loss_sum / max(n_clients, 1)).astype(jnp.float32),
            (correct_sum / jnp.maximum(valid_sum, 1.0)).astype(jnp.float32),
        ])

    def _eval_params(self) -> PyTree:
        """Params view for host-driven eval programs. On a model-sharded
        mesh this is the lazy gather to replicated (eval jits are compiled
        over full tensors, and a replicated view keeps their numerics
        bit-identical to the 1-D path); the gather cost lands on the
        ``reshard`` phase so eval timing stays honest."""
        if self._model_axis is None:
            return self.params
        with self._phase("reshard"):
            return jax.device_put(self.params, replicated(self.mesh))

    def evaluate(self, apply_fn) -> Dict[str, float]:
        if self._eval_fn is None:
            self._eval_fn = self._build_eval(apply_fn)
        test = self.fed.test_data_global
        n = len(test.x)
        if n == 0:  # train-only dataset (e.g. LEAF users without test splits)
            return {}
        bs = min(self.cfg.eval_batch_size, n)
        xs, ys, ms = self._pad_and_batch(test.x, test.y, bs)
        l, c, cnt = self._eval_fn(self._eval_params(), xs, ys, ms)
        return {
            "test_loss": float(l) / max(float(cnt), 1.0),
            "test_acc": float(c) / max(float(cnt), 1.0),
        }

    @staticmethod
    def _pad_and_batch(x, y, bs, sid=None, total=None):
        """Pad the tail batch to full size with masked-out rows and reshape
        into (num_batches, bs, ...) device arrays — eval covers every sample
        exactly (a truncated tail would bias parity numbers). Keeps trailing
        label dims (per-token/per-pixel targets). ``sid`` optionally carries
        a per-sample segment id through the same batching. ``total`` pads to
        a FIXED row count (a multiple of bs) instead of the next multiple —
        callers evaluating many differently-sized sets through one jit pad
        them all to the same shape so XLA compiles once."""
        n = len(x)
        if total is not None:
            assert total % bs == 0 and total >= n, (total, bs, n)
            n_pad = total - n
        else:
            n_pad = (-n) % bs
        m = np.ones(n + n_pad, np.float32)
        if n_pad:
            x = np.concatenate([x, np.zeros((n_pad,) + x.shape[1:], x.dtype)])
            y = np.concatenate([y, np.zeros((n_pad,) + y.shape[1:], y.dtype)])
            if sid is not None:
                sid = np.concatenate([sid, np.zeros(n_pad, sid.dtype)])
            m[n:] = 0.0
        out = (jnp.asarray(x).reshape((-1, bs) + x.shape[1:]),
               jnp.asarray(y).reshape((-1, bs) + y.shape[1:]),
               jnp.asarray(m).reshape((-1, bs)))
        if sid is not None:
            out += (jnp.asarray(sid).reshape((-1, bs)),)
        return out

    # --- per-client local-test evaluation ----------------------------------

    def _build_local_eval(self, apply_fn) -> Callable:
        """One compiled segmented pass: scan over mixed-client batches,
        scatter-add each sample's (loss, correct, valid-cells, samples)
        into its owner client's accumulator. Replaces the reference's
        per-client Python eval loop (fedavg_api.py:188-246 runs
        client_num_in_total separate model passes) with ONE program whose
        cost is the sample count — client raggedness costs nothing because
        client identity is data (a per-sample id vector), not shape.
        Valid CELLS (label positions: 1/sample for classification, L or
        H*W for multi-label/per-pixel) normalize loss/acc; SAMPLES is the
        reference's true example count. ``gather`` routes x/y lookups
        through HBM-resident global arrays (index batches) instead of a
        second device copy of the train set."""
        from ..ops.losses import per_sample_metrics

        loss_kind = self.cfg.loss_kind
        C = self.fed.client_num

        def accumulate(params, x, y, m, cid, carry):
            out = apply_fn(params, x, train=False)
            lv, cv, vv = per_sample_metrics(out, y, m, loss_kind)
            L, K, N, S = carry
            return (L.at[cid].add(lv), K.at[cid].add(cv),
                    N.at[cid].add(vv), S.at[cid].add(m))

        z4 = lambda: tuple(jnp.zeros((C,), jnp.float32) for _ in range(4))  # noqa: E731

        def seg_eval(params, xs, ys, ms, cids):
            def body(carry, batch):
                x, y, m, cid = batch
                return accumulate(params, x, y, m, cid, carry), None

            res, _ = jax.lax.scan(body, z4(), (xs, ys, ms, cids))
            return res

        def seg_eval_gather(params, idxs, ms, cids, x_all, y_all):
            def body(carry, batch):
                idx, m, cid = batch
                x = x_all[idx] * m.reshape(
                    m.shape + (1,) * (x_all.ndim - 1)).astype(x_all.dtype)
                y = y_all[idx] * m.reshape(
                    m.shape + (1,) * (y_all.ndim - 1)).astype(y_all.dtype)
                return accumulate(params, x, y, m, cid, carry), None

            res, _ = jax.lax.scan(body, z4(), (idxs, ms, cids))
            return res

        return jax.jit(seg_eval), jax.jit(seg_eval_gather)

    def _local_eval_batches(self, split: str):
        """Batched (xs, ys, ms, sids) tensors for one split ("train" |
        "test") plus a per-client representative map. Clients sharing one
        ArrayPair OBJECT (the default loaders give every client the SAME
        global test set) are deduplicated: the shared array is evaluated
        ONCE under its first client's position and the stats fan out to the
        group afterwards — without this, C clients x the full test set
        would be materialized (O(C * test_set) memory, review finding).
        Cached — built once per simulator. Returns (batched, rep) where
        rep[i] = the client position whose accumulator holds client i's
        stats (-1 = no data); None when the split has no samples."""
        if split in self._local_eval_cache:
            return self._local_eval_cache[split]
        keys = sorted(self.fed.train_data_local_dict.keys())
        rep = np.full(len(keys), -1, np.int64)
        if split == "train" and self._use_device_data:
            # index batches into the ALREADY-device-resident global train
            # arrays — a direct concat would pin a second full HBM copy of
            # the train set for the simulator's lifetime (review finding)
            idx_l, sid_l = [], []
            for i, k in enumerate(keys):
                ix = self.fed._global_index.get(k)
                if ix is None or len(ix) == 0:
                    continue
                rep[i] = i
                idx_l.append(np.asarray(ix, np.int32))
                sid_l.append(np.full(len(ix), i, np.int32))
            if not idx_l:
                self._local_eval_cache[split] = None
                return None
            idx = np.concatenate(idx_l)
            sid = np.concatenate(sid_l)
            bs = min(self.cfg.eval_batch_size, len(idx))
            idx_b, sid_b, m_b = self._pad_and_batch(idx, sid, bs)
            self._local_eval_cache[split] = ("gather", (idx_b, m_b, sid_b),
                                             rep)
            return self._local_eval_cache[split]
        d = (self.fed.train_data_local_dict if split == "train"
             else self.fed.test_data_local_dict)
        first_pos: Dict[int, int] = {}  # id(pair) -> representative position
        xs_l, ys_l, sid_l = [], [], []
        for i, k in enumerate(keys):
            pair = d.get(k)
            if pair is None or len(pair) == 0:
                continue
            if id(pair) in first_pos:
                rep[i] = first_pos[id(pair)]
                continue
            first_pos[id(pair)] = rep[i] = i
            xs_l.append(pair.x)
            ys_l.append(pair.y)
            sid_l.append(np.full(len(pair), i, np.int32))
        if not xs_l:
            self._local_eval_cache[split] = None
            return None
        x, y, sid = (np.concatenate(v) for v in (xs_l, ys_l, sid_l))
        bs = min(self.cfg.eval_batch_size, len(x))
        batched = self._pad_and_batch(x, y, bs, sid=sid)
        self._local_eval_cache[split] = ("direct", batched, rep)
        return self._local_eval_cache[split]

    def local_test_on_all_clients(self, apply_fn) -> Dict[str, Any]:
        """Reference ``_local_test_on_all_clients`` (fedavg_api.py:188-246):
        evaluate the current global params on EVERY client's local train and
        local test split; report the weighted aggregates plus per-client
        vectors under "per_client". Clients without local test data are
        excluded from both aggregates, matching the reference's ``continue``.

        Normalization: loss/acc divide by valid label CELLS. For
        classification (one label per example — everything the reference's
        loop covers) cells == samples, so the numbers equal the reference's
        sum-loss/sum-samples exactly (parity-checked to ~1e-7 in
        scripts/parity_vs_reference.py). For the additional multi-label
        (bce: L cells/sample) and per-pixel (H*W cells/sample) families the
        values are per-cell means — the reference has no equivalent there.
        "per_client[*_samples]" always reports TRUE example counts.
        """
        if self._local_eval_fn is None:
            self._local_eval_fn = self._build_local_eval(apply_fn)
        seg_eval, seg_eval_gather = self._local_eval_fn
        keys = sorted(self.fed.train_data_local_dict.keys())
        include = np.array([
            self.fed.test_data_local_dict.get(k) is not None
            and len(self.fed.test_data_local_dict[k]) > 0
            for k in keys
        ])
        out: Dict[str, Any] = {}
        per_client: Dict[str, List[float]] = {}
        eval_params = self._eval_params()
        for split, agg_prefix in (("train", "local_train"),
                                  ("test", "local_test")):
            cached = self._local_eval_batches(split)
            if cached is None:
                continue
            kind, batched, rep = cached
            if kind == "gather":
                res = seg_eval_gather(eval_params, *batched,
                                      self._x_dev, self._y_dev)
            else:
                res = seg_eval(eval_params, *batched)
            L, K, N, S = (np.asarray(v) for v in res)
            # fan the representative accumulators out to their group (shared
            # ArrayPairs were evaluated once); rep -1 = client has no data
            has = rep >= 0
            r = np.where(has, rep, 0)
            L, K, N, S = (np.where(has, v[r], 0.0) for v in (L, K, N, S))
            # loss/acc normalize over valid label CELLS (== samples for
            # classification; L cells for multi-label, H*W for per-pixel);
            # "samples" is the reference's true example count either way
            n_safe = np.maximum(N, 1.0)
            per_client[f"{split}_loss"] = (L / n_safe).tolist()
            per_client[f"{split}_acc"] = (K / n_safe).tolist()
            per_client[f"{split}_samples"] = S.tolist()
            # reference aggregate: every client contributes its own copy of
            # the stats, so shared test sets count once per client
            inc = include & (N > 0)
            denom = max(float(N[inc].sum()), 1.0)
            out[f"{agg_prefix}_loss"] = float(L[inc].sum()) / denom
            out[f"{agg_prefix}_acc"] = float(K[inc].sum()) / denom
        out["per_client"] = per_client
        return out
