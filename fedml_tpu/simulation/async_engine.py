"""Buffered-async aggregation engine (FedBuff-style, no round barrier).

The synchronous :class:`~fedml_tpu.simulation.fed_sim.FedSimulator` commits
one model version per cohort barrier: the slowest sampled client gates every
round, so under per-client speed skew the barrier — not compute — caps
throughput (Parrot's heterogeneity thesis, arXiv:2303.01778). This engine
removes the barrier: client updates fold into a staleness-weighted buffer as
they (virtually) complete and a new model version commits every
``async_buffer_size = K`` updates.

Virtual-time model (the FedJAX simulated-cost idea, arXiv:2108.02117):
training still executes in *generations* — one un-donated compiled pass
trains the whole sampled cohort against the latest committed params, which
keeps the hot path a single XLA program — but completion is simulated per
client on a seeded :class:`~fedml_tpu.comm.resilience.ClientDelayPlan`:
client ``i`` finishes generation ``g`` at ``clock[i] + delay(i, g)`` where
``clock[i]`` is its own previous completion (clients free-run; nobody waits
for the cohort). Arrival events drain through PR 8's admission edge — every
arrival is offered to the ``CheckinQueue`` and same-virtual-instant batches
are ordered by the deficit-round-robin scheduler — then fold into the commit
buffer. Staleness is measured in *model versions* (commits between an
update's dispatch and its fold) and enters twice: the fold weight scales by
``1/(1+staleness)**async_staleness_alpha`` and the sanitizer's robust-z norms
scale by the same factor (``core.robust`` staleness-aware z-scores), so a
very stale update both counts less and is easier to quarantine.

Goodput accounting: ``committed_updates / virtual_seconds`` where the
virtual clock is the free-running makespan ``max_i clock[i]`` — under 10x
speed skew the synchronous virtual round rate is ``1/max_i delay(i)`` while
the async engine commits every client's work, so goodput scales with the
cohort instead of the straggler.

Bit-exact fallback (the acceptance oracle): ``async_buffer_size == cohort``
delegates each generation to the *actual* synchronous dispatch
(``FedSimulator._dispatch_even`` — same donated jit, same fold order), so
params, history metrics, SCAFFOLD arena state, and codec EF residuals are
bit-identical to the synchronous engine by construction while the event /
commit / goodput accounting stays live.

Eval/checkpoint without round boundaries: both are keyed to generation
boundaries; a boundary that evaluates or checkpoints first *flushes* the
partial buffer (a commit with ``n < K``) so eval always sees a committed
model version and checkpoints always land with an empty buffer — which is
why the checkpoint extras (``_export_extra_state``) are a handful of
scalars (version, virtual clock, per-client clocks, next generation), never
update stacks. Resume replays commit boundaries exactly: the flush happens
at the same flagged boundaries an uninterrupted run flushes at.
"""

from __future__ import annotations

import heapq
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.resilience import ClientDelayPlan
from ..core import telemetry, trace_plane
from ..core.tenancy import CheckinQueue, DeficitRoundRobinScheduler
from .fed_sim import FedSimulator, _cohort_outputs, _gather_from_device

PyTree = Any


def sync_virtual_seconds(plan: Optional[ClientDelayPlan], base_s: float,
                         client_ids, n_rounds: int) -> float:
    """Virtual wall-clock of a *synchronous* run over the same delay plan:
    each round barriers on the slowest sampled client, so the round time is
    the cohort max delay. The async/sync goodput comparison uses this as the
    sync-side denominator (same plan, same seeds — no wall-clock flakiness)."""
    ids = [int(c) for c in client_ids]
    total = 0.0
    for g in range(int(n_rounds)):
        total += max(
            (plan.delay_s(c, g) if plan is not None else base_s) for c in ids)
    return total


class VirtualEventHeap:
    """Min-heap of ``(virtual_time, payload)`` arrival events.

    The async engine's event loop and the cross-device day driver
    (:mod:`fedml_tpu.cross_device.device_day`) share this structure: both
    advance a virtual clock to the earliest outstanding arrival and consume
    every event tied at that instant as one admission batch. Payloads tied
    at the same virtual time pop in push order (a monotonic sequence breaks
    ties), so the drain order is deterministic even for non-comparable
    payloads.
    """

    __slots__ = ("_heap", "_seq")

    def __init__(self):
        self._heap: List = []
        self._seq = 0

    def push(self, vt: float, payload: Any) -> None:
        heapq.heappush(self._heap, (vt, self._seq, payload))
        self._seq += 1

    def peek_vt(self) -> float:
        return self._heap[0][0]

    def pop_batch(self) -> "tuple[float, List[Any]]":
        """Pop every event tied at the earliest virtual time. Returns
        ``(vt, payloads)``; raises IndexError when empty."""
        vt0 = self._heap[0][0]
        batch: List[Any] = []
        while self._heap and self._heap[0][0] == vt0:
            batch.append(heapq.heappop(self._heap)[2])
        return vt0, batch

    def __len__(self) -> int:
        return len(self._heap)

    def __bool__(self) -> bool:
        return bool(self._heap)


class _GenEntry:
    """One generation's device-resident training outputs awaiting folds:
    the stacked update, per-client fold weights, the base model version the
    cohort trained against, and how many arrivals are still outstanding."""

    __slots__ = ("update", "w", "base_version", "metrics_vec", "ids",
                 "remaining")

    def __init__(self, update, w, base_version, metrics_vec, ids, remaining):
        self.update = update
        self.w = w
        self.base_version = base_version
        self.metrics_vec = metrics_vec
        self.ids = ids
        self.remaining = remaining


class AsyncFedSimulator(FedSimulator):
    """FedBuff-style buffered-async server over the FedSimulator chassis.

    Reuses the parent's host plumbing unchanged — ``build_round_inputs`` is
    still pure in (seed, generation) so the prefetch pipeline keeps working,
    and records still flow through ``_defer_rec``/``_finalize_rec`` so the
    phase breakdown (now including ``commit``) sums exactly to wall-clock
    per commit interval. Only the dispatch/commit split and the event clock
    are new.
    """

    def __init__(self, fed_data, algorithm, init_variables, cfg, mesh=None,
                 **kwargs):
        if mesh is not None:
            raise ValueError(
                "async_mode currently runs single-placement (mesh=None): "
                "the per-buffer commit jits are not sharding-annotated yet "
                "— drop the mesh or async_mode")
        if cfg.watchdog_factor > 0:
            raise ValueError(
                "the divergence watchdog's rollback loop needs synchronous "
                "round boundaries; async_mode relies on the staleness-aware "
                "sanitizer instead (sanitize_updates=True) — disable one")
        if cfg.cohort_schedule not in ("auto", "even"):
            raise ValueError(
                f"cohort_schedule='{cfg.cohort_schedule}' is incompatible "
                "with async_mode: the commit buffer gathers rows from the "
                "full stacked cohort (use 'even' or 'auto')")
        # the buffer fold needs the stacked per-client update rectangle,
        # which only the even schedule materializes
        cfg.cohort_schedule = "even"
        super().__init__(fed_data, algorithm, init_variables, cfg, mesh=mesh,
                         **kwargs)
        cohort = int(cfg.client_num_per_round)
        k = cfg.async_buffer_size
        self._buffer_size = cohort if k is None else int(k)
        if not (1 <= self._buffer_size <= cohort):
            raise ValueError(
                f"async_buffer_size={k} must be in [1, cohort="
                f"{cohort}] (larger would deadlock: a generation produces "
                "exactly one update per sampled client)")
        # K == cohort: every commit is exactly one whole-cohort barrier, so
        # each generation delegates to the synchronous dispatch — the
        # bit-exact fallback regime
        self._lockstep = self._buffer_size == cohort
        self._plan = (ClientDelayPlan(
            seed=int(cfg.seed), base_s=float(cfg.async_delay_base_s),
            skew=float(cfg.async_delay_skew),
            jitter=float(cfg.async_delay_jitter))
            if cfg.async_delay_skew > 0 else None)
        self._alpha = float(cfg.async_staleness_alpha)
        # admission edge (PR 8): arrivals are offered to the checkin queue
        # and same-instant ties are ordered by deficit round-robin
        self._checkin = CheckinQueue(maxsize=max(64, 2 * cohort))
        self._drr = DeficitRoundRobinScheduler()
        for c in range(int(cfg.client_num_in_total)):
            self._drr.register(str(c), round_cost=1.0)
        # event/commit state
        self._version = 0            # committed model versions so far
        self._committed = 0          # committed updates so far
        self._vt = 0.0               # virtual clock (free-running makespan)
        self._clock: Dict[int, float] = {}  # per-client completion clocks
        self._events = VirtualEventHeap()  # (arrival_vt, pos) per gen
        self._buffer: List = []      # fold refs: (gen, pos, staleness)
        self._gens: Dict[int, _GenEntry] = {}
        self._shed_updates = 0
        self._pending = None         # deferred commit record
        self._next_gen = 0
        self._resume_gen: Optional[int] = None
        # eval/checkpoint target versions (-1 = no match): set at flagged
        # generation boundaries so the overridden _should_eval /
        # _should_checkpoint reproduce the sync cadence per *generation*
        # while records are keyed by commit version
        self._eval_version = -1
        self._ckpt_version = -1
        if not self._lockstep:
            self._async_step = self._build_async_train_step()
            self._commit_cache: Dict[int, Callable] = {}
        # same fusion condition as the sync round step: agg_kernels + a
        # Krum-family defense folds sanitize+Krum into one kernel pass
        alg = self.alg
        self._fuse_robust = bool(
            cfg.agg_kernels and self._detect
            and getattr(alg, "robust", None) is not None
            and alg.robust.defense_type in type(alg.robust).KRUM_FAMILY
            and not alg.robust.sanitize)

    # --- compiled pieces --------------------------------------------------

    def _build_async_train_step(self) -> Callable:
        """Train-only half of the sync round step: local training + the
        wire-codec roundtrip + the attack transform, returning the stacked
        update instead of aggregating it (the commit jit does that later,
        over buffer rows possibly spanning generations). Params are NOT
        donated — commits own the params lifecycle."""
        alg = self.alg
        transform = self._update_transform
        codec_rt = self._codec_rt
        codec_ef = self._codec_arena is not None

        def train_body(params, cohort, client_states, rng, codec_res=(),
                       cids_u32=None, round_u32=None):
            outs = _cohort_outputs(alg, params, cohort, client_states, rng)
            update = outs.update
            w = outs.weight.astype(jnp.float32)
            if codec_rt is not None:
                update, codec_res = codec_rt(
                    update, codec_res, cids_u32, round_u32)
            if transform is not None:
                update = transform(update, w)
            m = outs.metrics
            metrics_vec = jnp.stack([
                m["train_loss"].mean().astype(jnp.float32),
                (m["train_correct"].sum()
                 / jnp.maximum(m["train_valid"].sum(), 1.0)
                 ).astype(jnp.float32),
            ])
            ret = (update, w, outs.state, metrics_vec)
            if codec_ef:
                ret += (codec_res,)
            return ret

        if self._use_device_data:
            if codec_rt is not None:
                def train_step(params, cohort, client_states, rng, codec_res,
                               cids_u32, round_u32, x_all, y_all):
                    data = _gather_from_device(dict(cohort), x_all, y_all)
                    return train_body(params, data, client_states, rng,
                                      codec_res, cids_u32, round_u32)
            else:
                def train_step(params, cohort, client_states, rng,
                               x_all, y_all):
                    data = _gather_from_device(dict(cohort), x_all, y_all)
                    return train_body(params, data, client_states, rng)
        else:
            train_step = train_body
        return jax.jit(train_step)

    def _commit_step(self, n: int) -> Callable:
        """Donated commit jit for a buffer of ``n`` rows — the sync round
        step's aggregation tail (sanitize / fused Krum / aggregate / server
        update) with staleness-scaled weights and staleness-aware robust-z.
        Compiled once per distinct buffer fill (K, plus the partial flush
        sizes eval boundaries produce)."""
        fn = self._commit_cache.get(n)
        if fn is not None:
            return fn
        alg = self.alg
        detect = self._detect
        fuse = self._fuse_robust
        z_thresh = float(self.cfg.sanitize_z_thresh)
        # buffer-fraction step scaling: the weighted mean over n buffered
        # rows is a full-magnitude step, but a generation produces
        # cohort/K commits — scaling each commit by n/cohort makes one
        # generation's worth of commits apply the same total step as one
        # synchronous round (K == cohort degenerates to 1.0, preserving
        # the bit-exact fallback), instead of an effective server lr
        # inflated by cohort/K
        frac = n / float(self.cfg.client_num_per_round)

        def commit(params, server_state, stacked, w, sw):
            # FedBuff staleness down-weight: 1/(1+s)^alpha rides the fold
            # weight, so stale rows count less in the weighted mean AND in
            # any sample-weighted defense
            wf = w * sw
            qz = None
            if detect and fuse:
                from ..core.robust import fused_sanitize_krum

                ra = alg.robust
                f_byz, m_krum = ra._krum_fm(n)
                agg, wf, quar, z, _sel = fused_sanitize_krum(
                    stacked, wf, z_thresh=z_thresh, n_byz=f_byz, m=m_krum,
                    sample_weighted=ra.defense_type == "krum_fedavg",
                    staleness_scale=sw)
                qz = jnp.stack([quar.astype(jnp.float32),
                                jnp.nan_to_num(z, posinf=1e30)])
            elif detect:
                from ..core.robust import sanitize_stacked

                clean, wf, quar, z = sanitize_stacked(
                    stacked, wf, z_thresh, staleness_scale=sw)
                qz = jnp.stack([quar.astype(jnp.float32),
                                jnp.nan_to_num(z, posinf=1e30)])
                if alg.aggregate is not None:
                    agg = alg.aggregate(clean, wf)
                else:
                    from ..core.algframe import weighted_mean

                    agg = weighted_mean(clean, wf)
            else:
                if alg.aggregate is not None:
                    agg = alg.aggregate(stacked, wf)
                else:
                    from ..core.algframe import weighted_mean

                    agg = weighted_mean(stacked, wf)
            if frac != 1.0:
                agg = jax.tree.map(lambda a: (a * frac).astype(a.dtype), agg)
            new_params, new_server_state = alg.server_update(
                params, agg, server_state)
            ret = (new_params, new_server_state)
            if detect:
                ret += (qz,)
            return ret

        fn = jax.jit(commit, donate_argnums=(0, 1))
        self._commit_cache[n] = fn
        return fn

    # --- eval/checkpoint cadence (generation-keyed) -----------------------

    def _should_eval(self, round_idx: int) -> bool:
        if self._lockstep:
            # versions == generations == sync rounds: the parent's cadence
            # reproduces the synchronous decisions bit for bit
            return super()._should_eval(round_idx)
        return round_idx == self._eval_version

    def _should_checkpoint(self, round_idx: int) -> bool:
        if self._lockstep:
            return super()._should_checkpoint(round_idx)
        return round_idx == self._ckpt_version

    # --- checkpoint extras ------------------------------------------------

    def _export_extra_state(self) -> dict:
        """Scalar-only commit-plane state: checkpoints fire at generation
        boundaries after a flush, so the buffer is empty and no generation
        stacks are alive — only counters and the virtual clocks persist."""
        ids = sorted(self._clock)
        # 0-d ndarrays, not numpy scalars: orbax's StandardSave only accepts
        # array-likes with a shape
        return {
            "next_gen": np.asarray(self._next_gen, np.int64),
            "version": np.asarray(self._version, np.int64),
            "committed": np.asarray(self._committed, np.int64),
            "virtual_time_s": np.asarray(self._vt, np.float64),
            "clock_ids": np.asarray(ids, np.int64),
            "clock_vts": np.asarray([self._clock[i] for i in ids],
                                    np.float64),
        }

    def _import_extra_state(self, extra: dict) -> None:
        self._resume_gen = int(np.asarray(extra["next_gen"]))
        self._version = int(np.asarray(extra["version"]))
        self._committed = int(np.asarray(extra["committed"]))
        self._vt = float(np.asarray(extra["virtual_time_s"]))
        ids = np.asarray(extra["clock_ids"]).reshape(-1)
        vts = np.asarray(extra["clock_vts"]).reshape(-1)
        self._clock = {int(i): float(v) for i, v in zip(ids, vts)}

    def async_stats(self) -> dict:
        """Commit-plane snapshot: model version, committed updates, virtual
        clock, goodput (committed updates per virtual second)."""
        return {
            "version": int(self._version),
            "committed_updates": int(self._committed),
            "shed_updates": int(self._shed_updates),
            "virtual_time_s": float(self._vt),
            "goodput_updates_per_s": (
                self._committed / self._vt if self._vt > 0 else 0.0),
        }

    def _delay(self, client: int, gen: int) -> float:
        if self._plan is not None:
            return self._plan.delay_s(client, gen)
        return float(self.cfg.async_delay_base_s)

    # --- round loop -------------------------------------------------------

    def run(self, apply_fn=None, log_fn=print) -> List[Dict[str, float]]:
        cfg = self.cfg
        base_rng = jax.random.PRNGKey(cfg.seed)
        start_gen, ckpt = 0, None
        if cfg.checkpoint_dir:
            from ..utils.checkpoint import (CheckpointManager,
                                            restore_simulator_state)

            ckpt = CheckpointManager(cfg.checkpoint_dir)
            if cfg.resume and ckpt.latest_step() is not None:
                restored = restore_simulator_state(ckpt, self)
                # engine extras carry the true next generation (records are
                # keyed by commit version, which outruns generations when
                # K < cohort); extras-free checkpoints fall back to the
                # parent's round numbering
                start_gen = (self._resume_gen if self._resume_gen is not None
                             else restored)
                if log_fn:
                    log_fn(f"[resume] from generation {start_gen} (version "
                           f"{self._version}) @ {cfg.checkpoint_dir}")
        rounds = range(start_gen, cfg.comm_round)
        if cfg.prefetch and len(rounds) > 0:
            from .prefetch import RoundPrefetcher

            self._prefetcher = RoundPrefetcher(
                self.build_round_inputs, rounds, depth=cfg.prefetch_depth)
        self._pending = None
        self._last_round_end = time.perf_counter()
        try:
            for gen in rounds:
                if self._round_gate is not None:
                    self._round_gate(gen)
                with self._phase("pack_wait") as pw:
                    self._next_gen = gen + 1
                    if self._prefetcher is not None:
                        inputs = self._prefetcher.get(gen)
                    else:
                        inputs = self.build_round_inputs(gen)
                t0 = pw.start
                step_rng = jax.random.fold_in(base_rng, gen)
                with self._phase("dispatch", str(gen)):
                    if self._lockstep:
                        metrics_vec = self._dispatch_even(inputs, step_rng)
                    else:
                        update, w, metrics_vec = self._dispatch_train(
                            inputs, step_rng)
                timing = self._pack_timing(inputs.pack_time, pw.wall)
                if self._lockstep:
                    self._lockstep_commit(gen, inputs, t0, metrics_vec,
                                          timing, apply_fn, ckpt, log_fn)
                else:
                    ids = inputs.client_ids
                    self._gens[gen] = _GenEntry(
                        update, w, base_version=self._version,
                        metrics_vec=metrics_vec, ids=ids,
                        remaining=len(ids))
                    self._push_arrivals(gen, ids)
                    self._drain_events(gen, apply_fn, ckpt, log_fn)
                    self._gen_boundary(gen, timing, apply_fn, ckpt, log_fn)
        finally:
            self._pregathered_state = self._pregathered_codec = None
            if self._prefetcher is not None:
                self._prefetcher.close()
                self._prefetcher = None
        if not self._lockstep and self._buffer:
            # end-of-run drain: runs without eval/checkpoint never flag the
            # final boundary, but a committed update must never be lost
            self._commit(None, apply_fn, ckpt, log_fn)
        if self._pending is not None:
            self._finalize_rec(self._pending, apply_fn, ckpt, log_fn)
            self._pending = None
        # see FedSimulator.run — graftcheck: disable=host-sync
        jax.block_until_ready(self.params)
        if ckpt is not None:
            ckpt.close()
        telemetry.flush()
        return self.history

    # --- lockstep (bit-exact fallback) regime -----------------------------

    def _lockstep_commit(self, gen, inputs, t0, metrics_vec, timing,
                         apply_fn, ckpt, log_fn) -> None:
        """K == cohort: the synchronous dispatch already folded and
        committed the whole cohort inside its donated round jit — only the
        event/commit accounting runs here, so the model math is the sync
        engine's own, bit for bit."""
        with self._phase("commit"):
            ids = [int(c) for c in inputs.client_ids]
            arrivals = []
            for c in ids:
                a = self._clock.get(c, 0.0) + self._delay(c, gen)
                self._clock[c] = a
                arrivals.append(a)
            # the barriered commit waits for the slowest client, exactly the
            # sync virtual round time
            self._vt = max(self._vt, max(arrivals))
            self._version += 1
            self._committed += len(ids)
            reg = telemetry.get_registry()
            if reg.enabled:
                reg.counter("fedml_commits_total").inc()
                hist = reg.histogram("fedml_update_staleness")
                for _ in ids:
                    hist.observe(0.0)
                reg.gauge("fedml_goodput_updates_per_s").set(
                    self._committed / max(self._vt, 1e-12))
            trace_plane.record_instant(
                "commit", round_idx=gen,
                attrs={"n": len(ids), "version": self._version,
                       "virtual_time_s": round(self._vt, 6)})
        timing.update({
            "version": gen,
            "buffer_fill": len(ids),
            "staleness_mean": 0.0,
            "staleness_max": 0,
            "virtual_time_s": self._vt,
            "goodput_ups": self._committed / max(self._vt, 1e-12),
        })
        # commit→publish rides the inherited record finalize (see _commit):
        # gen+1 == the incremented self._version of this lockstep commit
        self._pending = self._defer_rec(
            gen, t0, metrics_vec, self._pending, apply_fn, ckpt, log_fn,
            timing)

    # --- buffered (general) regime ----------------------------------------

    def _dispatch_train(self, inputs, step_rng):
        """Train-only dispatch: the sync even dispatch minus aggregation
        and the double-buffered put_take (commits interleave with training,
        so there is no single next-gather to fuse the scatter with)."""
        cohort = {k: jnp.asarray(v) for k, v in inputs.payload.items()}
        ids = inputs.client_ids
        stateful = self._client_state_proto != ()
        if stateful:
            with self._phase("state_gather"):
                states = self._gather_states(ids)
        else:
            states = ()
        step_args = (self.params, cohort, states, step_rng)
        if self._codec_rt is not None:
            with self._phase("codec"):
                codec_res = ()
                if self._codec_arena is not None:
                    codec_res = self._codec_arena.gather(ids)
                step_args += (codec_res,
                              jnp.asarray(ids.astype(np.uint32)),
                              jnp.uint32(inputs.round_idx))
        if self._use_device_data:
            step_args += (self._x_dev, self._y_dev)
        out = self._async_step(*step_args)
        if self._codec_arena is not None:
            *out, new_codec_res = out
        update, w, new_states, metrics_vec = out
        if stateful:
            with self._phase("state_scatter"):
                self._scatter_states(ids, new_states)
        if self._codec_rt is not None:
            with self._phase("codec") as ph:
                if self._codec_arena is not None:
                    # EF residuals update at ENCODE time (the client owns
                    # them), not at commit — same as a real uplink
                    self._codec_arena.scatter(ids, new_codec_res)
            raw, coded = self._codec_wire
            self._codec_record(
                "encode", raw * len(ids), coded * len(ids), ph.wall)
        return update, w, metrics_vec

    def _push_arrivals(self, gen: int, ids) -> None:
        for pos, c in enumerate(int(x) for x in ids):
            arrival = self._clock.get(c, 0.0) + self._delay(c, gen)
            self._clock[c] = arrival
            self._events.push(arrival, pos)

    def _drain_events(self, gen: int, apply_fn, ckpt, log_fn) -> None:
        """Consume every arrival of this generation in virtual-time order.
        Same-instant ties (zero-skew plans) form one admission batch: each
        arrival is offered to the checkin queue, then the deficit-round-
        robin scheduler picks the fold order across tenants — the shared
        admission edge with the cross-silo server."""
        entry = self._gens[gen]
        ids = entry.ids
        while self._events:
            vt0, batch = self._events.pop_batch()
            self._vt = max(self._vt, vt0)
            by_tenant: Dict[str, List[int]] = {}
            for pos in batch:
                tenant = str(int(ids[pos]))
                if not self._checkin.offer((gen, pos), tenant=tenant):
                    # shed at the admission edge = a lost (never-committed)
                    # update; counted by the queue's shed metric too
                    self._shed_updates += 1
                    entry.remaining -= 1
                    continue
            while True:
                item = self._checkin.poll()
                if item is None:
                    break
                _, pos = item
                by_tenant.setdefault(str(int(ids[pos])), []).append(pos)
            ready = {t for t, lst in by_tenant.items() if lst}
            while ready:
                tenant = self._drr.next_tenant(ready=ready)
                if tenant is None:
                    break
                lst = by_tenant[tenant]
                pos = lst.pop(0)
                self._drr.charge(tenant, 1.0)
                if not lst:
                    ready.discard(tenant)
                self._fold(gen, pos, apply_fn, ckpt, log_fn)

    def _fold(self, gen: int, pos: int, apply_fn, ckpt, log_fn) -> None:
        entry = self._gens[gen]
        staleness = self._version - entry.base_version
        self._buffer.append((gen, pos, staleness))
        entry.remaining -= 1
        reg = telemetry.get_registry()
        if reg.enabled:
            reg.histogram("fedml_update_staleness").observe(float(staleness))
        if len(self._buffer) >= self._buffer_size:
            self._commit(None, apply_fn, ckpt, log_fn)

    def _commit(self, timing, apply_fn, ckpt, log_fn) -> None:
        """Fold the buffered rows into a new model version: gather the rows
        from their generation stacks device-side, then one donated commit
        jit (sanitize/defense/aggregate/server-update) — the critical path
        never bounces through host."""
        with self._phase("commit") as ph:
            refs = self._buffer
            self._buffer = []
            n = len(refs)
            rows = [jax.tree.map(lambda x, p=pos: x[p], self._gens[g].update)
                    for g, pos, _ in refs]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
            w = jnp.stack([self._gens[g].w[pos] for g, pos, _ in refs])
            stale = np.asarray([s for _, _, s in refs], np.float32)
            sw = jnp.asarray((1.0 + stale) ** (-self._alpha), jnp.float32)
            out = self._commit_step(n)(
                self.params, self.server_state, stacked, w, sw)
            if self._detect:
                self.params, self.server_state, qz = out
                self._last_qz = qz
                self._last_cohort_ids = np.asarray(
                    [int(self._gens[g].ids[pos]) for g, pos, _ in refs])
            else:
                self.params, self.server_state = out
            version = self._version
            self._version += 1
            self._committed += n
            metrics_vec = self._gens[refs[-1][0]].metrics_vec
            # release generation stacks with no outstanding arrivals or refs
            live = {g for g, _, _ in self._buffer}
            for g in [g for g, e in self._gens.items()
                      if e.remaining <= 0 and g not in live]:
                del self._gens[g]
            reg = telemetry.get_registry()
            goodput = self._committed / max(self._vt, 1e-12)
            if reg.enabled:
                reg.counter("fedml_commits_total").inc()
                reg.gauge("fedml_goodput_updates_per_s").set(goodput)
            trace_plane.record_instant(
                "commit", round_idx=version,
                attrs={"n": n, "version": self._version,
                       "staleness_max": int(stale.max()),
                       "virtual_time_s": round(self._vt, 6)})
        rec_timing = dict(timing) if timing else {}
        rec_timing.update({
            "version": version,
            "buffer_fill": n,
            "staleness_mean": float(stale.mean()),
            "staleness_max": int(stale.max()),
            "virtual_time_s": self._vt,
            "goodput_ups": goodput,
        })
        # finalizing this record fires the inherited commit→publish hook
        # (fed_sim._post_round_body) with version round_idx+1 — exactly the
        # post-increment self._version this commit just produced, so the
        # serving plane sees one publish per commit with the right number
        self._pending = self._defer_rec(
            version, ph.start, metrics_vec, self._pending, apply_fn, ckpt,
            log_fn, rec_timing)

    def _gen_boundary(self, gen: int, timing, apply_fn, ckpt,
                      log_fn) -> None:
        """Generation boundary: apply the sync engine's eval/checkpoint
        cadence, flushing the partial buffer first so eval always sees a
        committed model version and checkpoints land with an empty buffer
        (the prefetcher's forced-sync pause then wraps the eval/checkpoint
        via the parent's _post_round, exactly as in the sync engine)."""
        cfg = self.cfg
        last = gen == cfg.comm_round - 1
        want_eval = apply_fn is not None and (
            gen % cfg.frequency_of_the_test == 0 or last)
        want_ckpt = ckpt is not None and (
            (gen + 1) % cfg.checkpoint_frequency == 0 or last)
        if not (want_eval or want_ckpt):
            return
        if self._buffer:
            if want_eval:
                self._eval_version = self._version
            if want_ckpt:
                self._ckpt_version = self._version
            self._commit(timing, apply_fn, ckpt, log_fn)
        elif self._pending is not None:
            if want_eval:
                self._eval_version = int(self._pending["round"])
            if want_ckpt:
                self._ckpt_version = int(self._pending["round"])
            self._finalize_rec(self._pending, apply_fn, ckpt, log_fn)
            self._pending = None
        self._eval_version = self._ckpt_version = -1
