"""A production device day: the 1M-client cross-device driver.

This module composes the pieces the repo already hardened into one
cross-device control plane and runs it over a full simulated day:

- :class:`~fedml_tpu.cross_device.registry.DeviceRegistry` — flat-array
  fleet with seeded availability windows and the device lifecycle
  (eligible → checked-in → training → uploaded | dropped);
- :class:`~fedml_tpu.cross_silo.loadgen.DiurnalCurve` — seeded diurnal
  arrival intensity; each tick's check-in count is a Poisson draw from the
  curve, so load swings through a realistic day/night cycle;
- the async engine's :class:`~fedml_tpu.simulation.async_engine.VirtualEventHeap`
  — arrivals land at seeded virtual times and drain in virtual-time order;
- the bounded :class:`~fedml_tpu.core.tenancy.CheckinQueue` + deficit-
  round-robin admission edge — overload sheds (``queue_full``) and stale
  arrivals are refused (``inadmissible``) instead of growing memory;
- :class:`~fedml_tpu.simulation.client_store.ClientStateArena` — per-device
  optimizer state tiered device → host → disk, so RSS stays bounded at
  1M-registry scale, and reclaimed on permanent departure;
- the tier plane's fan-in: cohorts split into leaf chunks
  (:func:`contiguous_group_split`), folded with :func:`fold_partials`, and
  committed exactly-once through a :class:`CommitLedger`, with
  ``trim_version_log`` retention driving rejoin resync decisions.

Everything is a pure function of the seed: two runs of the same config
produce byte-identical histories (the ``history_digest`` / ``params_digest``
in the result), which is what makes ``chaos-drill --device-churn`` a real
regression gate rather than a flaky demo. The churn drill drops 30% of the
fleet mid-day (with a permanent-departure subset and seeded rejoin waves),
cuts one device class off behind a :class:`NetworkPartition` window, and
asserts the day degrades instead of breaking: accuracy within tolerance of
the churn-free reference, sheds and drops fully accounted, no hangs.

Front doors: ``fedml-tpu chaos-drill --device-churn``,
``scripts/device_day_smoke.py``, ``tests/test_device_day.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..comm.message import Message
from ..comm.resilience import FaultPlan, NetworkPartition
from ..core import telemetry
from ..core.tenancy import CheckinQueue, DeficitRoundRobinScheduler
from ..cross_silo.loadgen import MSG_TYPE_CHECKIN, DiurnalCurve
from ..simulation.async_engine import VirtualEventHeap
from ..simulation.client_store import ClientStateArena
from ..simulation.federation import CommitLedger
from ..simulation.hierarchical import contiguous_group_split, fold_partials
from ..utils.checkpoint import trim_version_log
from .registry import CHECKED_IN, DeviceRegistry

DEVICE_DAY_DEFAULTS = dict(
    device_registry_size=100_000,
    device_day_s=86_400.0,
    device_tick_s=300.0,
    device_classes=4,
    device_cohort=64,
    device_queue_maxsize=4096,
    device_peak_rate=2.0,          # check-ins/s at the diurnal peak
    device_trough_fraction=0.2,
    device_arrival_spread_ticks=1.5,  # announce latency, in ticks
    device_dropout_rate=0.02,      # per-cohort-member mid-round failure
    device_recovery_rate=0.25,     # per-tick natural DROPPED -> ELIGIBLE
    device_max_commits_per_tick=1,
    device_pool_max_factor=4,      # checked-in pool bound, in cohorts
    device_feature_dim=16,
    device_num_labels=8,
    device_local_batch=8,
    device_lr=0.5,
    device_momentum=0.9,
    device_arena_capacity=1024,
    device_host_capacity=8192,
    device_spill_dir="",           # "" = no disk tier
    device_keep_versions=32,
    device_leaves=4,
    device_eval_every_ticks=8,
    device_seed=0,
    # churn drill knobs (all inert at churn_fraction=0)
    churn_fraction=0.0,
    churn_dropout_tick=-1,         # -1 = day midpoint
    churn_rejoin_ticks=3,
    churn_permanent_fraction=0.1,
    churn_partition_classes=0,     # first N device classes get cut off
    churn_partition_ticks=0,       # window length from the dropout tick
)


@dataclasses.dataclass(frozen=True)
class DeviceDayConfig:
    """One simulated day's shape. All randomness keys off ``seed``."""

    registry_size: int = 100_000
    day_s: float = 86_400.0
    tick_s: float = 300.0
    num_classes: int = 4
    cohort: int = 64
    queue_maxsize: int = 4096
    peak_rate: float = 2.0
    trough_fraction: float = 0.2
    # a device decides to check in, but its announce lands up to this many
    # ticks later — arrivals straddle tick boundaries, so a churn wave (or
    # a duplicate announce) can land between decision and admission, which
    # is exactly what the `inadmissible` shed reason exists for
    arrival_spread_ticks: float = 1.5
    dropout_rate: float = 0.02
    recovery_rate: float = 0.25
    max_commits_per_tick: int = 1
    pool_max_factor: int = 4
    feature_dim: int = 16
    num_labels: int = 8
    local_batch: int = 8
    lr: float = 0.5
    momentum: float = 0.9
    arena_capacity: int = 1024
    host_capacity: int = 8192
    spill_dir: Optional[str] = None
    keep_versions: int = 32
    num_leaves: int = 4
    eval_every_ticks: int = 8
    seed: int = 0
    churn_fraction: float = 0.0
    churn_dropout_tick: int = -1
    churn_rejoin_ticks: int = 3
    churn_permanent_fraction: float = 0.1
    churn_partition_classes: int = 0
    churn_partition_ticks: int = 0

    @property
    def n_ticks(self) -> int:
        return max(1, int(round(self.day_s / self.tick_s)))

    def resolved_dropout_tick(self) -> int:
        t = int(self.churn_dropout_tick)
        return t if t >= 0 else self.n_ticks // 2


@dataclasses.dataclass
class DeviceDayResult:
    """One day's full accounting — every arrival ends up in exactly one of
    these buckets, and :attr:`ok` is the closure proof."""

    elapsed_s: float
    ticks: int
    registry_size: int
    arrivals: int                 # events popped off the virtual-time heap
    partition_blackholed: int     # never reached the edge (cut active)
    offered: int                  # reached the admission edge
    accepted: int
    shed_queue_full: int
    shed_inadmissible: int
    not_selected: int             # admitted but released unselected
    in_flight_eod: int            # announces still airborne at midnight
    commits: int
    zero_survivor_commits: int
    cohort_slots: int             # cohort memberships across all commits
    committed_updates: int        # survivors actually folded
    mid_round_drops: int
    dropouts: int                 # registry lifecycle dropouts (all causes)
    rejoins: int
    resync_full: int
    resync_incremental: int
    departures: int
    reclaimed_spill_files: int
    duplicates: int               # CommitLedger double-commits (must be 0)
    final_version: int
    final_acc: float
    admission_edge_s: float       # wall time inside offer/drain only
    max_queue_depth: int
    queue_maxsize: int
    arena_resident: int
    arena_spilled: int
    history_digest: str
    params_digest: str
    history: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list, repr=False)

    @property
    def offered_per_s(self) -> float:
        """Admission-edge throughput: offered check-ins per second of wall
        time spent at the edge itself (offer + DRR drain), not of the whole
        simulation loop."""
        return self.offered / self.admission_edge_s \
            if self.admission_edge_s > 0 else 0.0

    @property
    def ok(self) -> bool:
        """Accounting closes end to end: every arrival was blackholed or
        offered; every offered check-in was accepted or shed (by reason);
        every cohort slot committed or dropped mid-round; the queue bound
        held; and no client update was ever double-committed."""
        return (
            self.arrivals == self.offered + self.partition_blackholed
            and self.offered == (self.accepted + self.shed_queue_full
                                 + self.shed_inadmissible)
            and self.cohort_slots == self.committed_updates
            + self.mid_round_drops
            and self.max_queue_depth <= self.queue_maxsize
            and self.duplicates == 0
        )

    def summary(self) -> str:
        return (
            f"device-day: {'PASS' if self.ok else 'FAIL'} — "
            f"{self.registry_size:,} devices, {self.ticks} ticks in "
            f"{self.elapsed_s:.2f}s | {self.offered:,} offered "
            f"({self.offered_per_s:,.0f}/s at the edge), "
            f"{self.accepted:,} accepted, shed {self.shed_queue_full} full"
            f"/{self.shed_inadmissible} inadmissible, "
            f"{self.partition_blackholed} blackholed | "
            f"{self.commits} commits ({self.committed_updates} updates, "
            f"{self.mid_round_drops} mid-round drops), dup {self.duplicates}"
            f" | churn: {self.dropouts} drops, {self.rejoins} rejoins "
            f"({self.resync_full} full / {self.resync_incremental} incr "
            f"resync), {self.departures} departed, "
            f"{self.reclaimed_spill_files} spill files reclaimed | "
            f"acc {self.final_acc:.3f} @ v{self.final_version}"
        )

    def json_record(self) -> dict:
        rec = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self) if f.name != "history"}
        rec["elapsed_s"] = round(self.elapsed_s, 4)
        rec["admission_edge_s"] = round(self.admission_edge_s, 4)
        rec["final_acc"] = round(self.final_acc, 6)
        rec["offered_per_s"] = round(self.offered_per_s, 1)
        rec["ok"] = self.ok
        return rec


class _FleetModel:
    """Tiny synthetic FL task, fully vectorized and per-device stable.

    A hidden linear truth ``w_true`` labels every batch; device ``i`` sees
    rows of a fixed seeded pool (indexed ``i % pool``) shifted by its
    device-class offset (non-IID by class). A local step is one momentum-
    SGD softmax-cross-entropy gradient on the device's batch, with the
    momentum row living in the :class:`ClientStateArena`. Accuracy is
    agreement with ``w_true`` on a held-out set — it climbs as commits fold,
    which is what gives the churn drill a meaningful accuracy gate.
    """

    _POOL = 4096

    def __init__(self, cfg: DeviceDayConfig):
        self.cfg = cfg
        rng = np.random.default_rng([int(cfg.seed), 0x_7296])
        f, l, b = cfg.feature_dim, cfg.num_labels, cfg.local_batch
        self.w_true = rng.normal(size=(f, l)).astype(np.float32)
        self.pool = rng.normal(size=(self._POOL, b, f)).astype(np.float32)
        self.class_shift = (rng.normal(size=(cfg.num_classes, f))
                            .astype(np.float32) * 0.5)
        self.x_eval = rng.normal(size=(1024, f)).astype(np.float32)
        self.y_eval = np.argmax(self.x_eval @ self.w_true, axis=-1)
        self.params = np.zeros((f, l), dtype=np.float32)

    def _batches(self, ids: np.ndarray):
        x = (self.pool[ids % self._POOL]
             + self.class_shift[ids % self.cfg.num_classes][:, None, :])
        y = np.argmax(x @ self.w_true, axis=-1)
        return x, y

    def local_updates(self, ids: np.ndarray, momenta: np.ndarray):
        """Vectorized local step for ``ids``: returns the stacked update
        proposals ``(n, F, L)`` and the new momentum rows."""
        x, y = self._batches(ids)
        logits = x @ self.params                       # (n, B, L)
        z = logits - logits.max(axis=-1, keepdims=True)
        e = np.exp(z)
        probs = e / e.sum(axis=-1, keepdims=True)
        onehot = np.eye(self.cfg.num_labels,
                        dtype=np.float32)[y]           # (n, B, L)
        grad = np.einsum("nbf,nbl->nfl", x, probs - onehot,
                         dtype=np.float32) / self.cfg.local_batch
        m_new = self.cfg.momentum * momenta + grad
        return (-self.cfg.lr * m_new).astype(np.float32), \
            m_new.astype(np.float32)

    def accuracy(self) -> float:
        pred = np.argmax(self.x_eval @ self.params, axis=-1)
        return float(np.mean(pred == self.y_eval))


def _partition_plan(cfg: DeviceDayConfig) -> Optional[FaultPlan]:
    """PR 14 fault kinds drive the drill's cut: the gateway for device
    class ``c`` is rank ``c + 1``, the root is rank 0, and the first
    ``churn_partition_classes`` classes are cut off from the root for
    ``churn_partition_ticks`` ticks starting at the dropout tick."""
    if cfg.churn_partition_classes <= 0 or cfg.churn_partition_ticks <= 0:
        return None
    t0 = cfg.resolved_dropout_tick()
    cut = NetworkPartition(
        frozenset({0}),
        frozenset(c + 1 for c in range(min(cfg.churn_partition_classes,
                                           cfg.num_classes))),
        rounds=(t0, t0 + int(cfg.churn_partition_ticks)),
        rate=1.0)
    return FaultPlan(seed=int(cfg.seed), partition=cut)


def _cut_classes(plan: Optional[FaultPlan], cfg: DeviceDayConfig,
                 tick: int) -> frozenset:
    """Which device classes are behind the cut at this tick — one
    ``should_partition`` probe per class gateway edge, judged at the
    receiver (the root) exactly like the tier plane does."""
    if plan is None:
        return frozenset()
    cut = set()
    for c in range(cfg.num_classes):
        msg = Message(type=MSG_TYPE_CHECKIN, sender_id=c + 1, receiver_id=0)
        if plan.should_partition(msg, round_hint=tick):
            cut.add(c)
    if cut and telemetry.enabled():
        telemetry.record_fault("device_partition")
    return frozenset(cut)


def run_device_day(cfg: DeviceDayConfig) -> DeviceDayResult:
    """Run one simulated day over the fleet and return its accounting."""
    t_start = time.perf_counter()
    registry = DeviceRegistry(cfg.registry_size, num_classes=cfg.num_classes,
                              seed=cfg.seed, day_s=cfg.day_s)
    curve = DiurnalCurve(peak_rate=cfg.peak_rate,
                         trough_fraction=cfg.trough_fraction,
                         day_s=cfg.day_s, seed=cfg.seed)
    queue = CheckinQueue(maxsize=cfg.queue_maxsize)
    drr = DeficitRoundRobinScheduler()
    for c in range(cfg.num_classes):
        drr.register(str(c), round_cost=1.0)
    heap = VirtualEventHeap()
    model = _FleetModel(cfg)
    proto = np.zeros((cfg.feature_dim, cfg.num_labels), dtype=np.float32)
    arena = ClientStateArena(
        proto, cfg.arena_capacity,
        spill_dir=cfg.spill_dir or None,
        host_capacity=cfg.host_capacity if cfg.spill_dir else None)
    ledger = CommitLedger()
    plan = _partition_plan(cfg)

    version = 0
    version_log: List[List[int]] = []   # [version, n_survivors]
    pool: List[int] = []                # checked-in ids, DRR-drain order
    pending_rejoins: List[np.ndarray] = []
    history: List[Dict[str, Any]] = []

    arrivals = blackholed = offered = accepted = 0
    shed_full = shed_inad = not_selected = 0
    commits = zero_survivor = cohort_slots = committed = mid_drops = 0
    reclaimed = 0
    edge_s = 0.0
    commit_idx = 0
    seed = int(cfg.seed)

    # churn wave schedule (inert unless churn_fraction > 0)
    drop_tick = cfg.resolved_dropout_tick()
    churn_waves: Dict[int, np.ndarray] = {}
    departures_at: Dict[int, np.ndarray] = {}
    if cfg.churn_fraction > 0:
        wave_rng = np.random.default_rng([seed, 0x_C4])
        n_churn = int(cfg.registry_size * cfg.churn_fraction)
        churned = wave_rng.choice(cfg.registry_size, size=n_churn,
                                  replace=False)
        n_perm = int(n_churn * cfg.churn_permanent_fraction)
        departures_at[drop_tick] = churned[:n_perm]
        temp = churned[n_perm:]
        churn_waves[drop_tick] = temp
        rejoin_start = drop_tick + max(1, int(cfg.churn_partition_ticks)) + 1
        rejoin_parts = np.array_split(
            temp, max(1, int(cfg.churn_rejoin_ticks)))
        rejoin_at = {rejoin_start + j: part
                     for j, part in enumerate(rejoin_parts) if part.size}
    else:
        rejoin_at = {}

    for tick in range(cfg.n_ticks):
        t0, t1 = tick * cfg.tick_s, (tick + 1) * cfg.tick_s
        tick_rng = np.random.default_rng([seed, 0x_71C4, tick])
        tick_rec: Dict[str, Any] = {"tick": tick}

        # --- churn waves land at tick start ------------------------------
        if tick in departures_at:
            gone = registry.depart(departures_at[tick])
            reclaimed += arena.discard(gone)
            tick_rec["departed"] = int(gone.size)
        if tick in churn_waves:
            tick_rec["churn_dropped"] = registry.mark_dropped(
                churn_waves[tick], held=True)
            if telemetry.enabled():
                telemetry.record_fault("device_churn_wave")
        if tick in rejoin_at:
            floor = version_log[0][0] if version_log else 0
            tick_rec["rejoin"] = registry.rejoin(
                rejoin_at[tick], log_floor_version=floor)

        cut = _cut_classes(plan, cfg, tick)
        if cut:
            tick_rec["partitioned_classes"] = sorted(cut)

        # --- seeded diurnal arrivals into the virtual-time heap ----------
        n_arr = curve.arrivals(t0, t1, tick_rng)
        cands = registry.eligible_available(t0 + 0.5 * cfg.tick_s)
        n_arr = min(n_arr, int(cands.size))
        if n_arr:
            arr_ids = tick_rng.choice(cands, size=n_arr, replace=False)
            spread = cfg.tick_s * max(1.0, float(cfg.arrival_spread_ticks))
            arr_vts = t0 + np.sort(tick_rng.uniform(0, spread, size=n_arr))
            for dev, vt in zip(arr_ids.tolist(), arr_vts.tolist()):
                heap.push(vt, dev)

        # --- drain arrivals due this tick through the admission edge -----
        due: List[int] = []
        while heap and heap.peek_vt() < t1:
            _, batch = heap.pop_batch()
            due.extend(batch)
        arrivals += len(due)
        tick_rec["arrivals"] = len(due)
        if due:
            ids = np.asarray(due, dtype=np.int64)
            classes = registry.device_class(ids)
            if cut:
                cut_mask = np.isin(classes, list(cut))
                blackholed += int(cut_mask.sum())
                tick_rec["blackholed"] = int(cut_mask.sum())
                ids, classes = ids[~cut_mask], classes[~cut_mask]
            # a device whose first announce is still airborne can announce
            # again (it is still ELIGIBLE when the next tick samples) —
            # only the first copy in a wave is admissible, the rest are
            # duplicate announces and shed as `inadmissible`
            first_mask = np.zeros(ids.size, dtype=bool)
            first_mask[np.unique(ids, return_index=True)[1]] = True
            t_edge = time.perf_counter()
            for c in range(cfg.num_classes):
                cls_mask = classes == c
                sel = ids[cls_mask]
                if not sel.size:
                    continue
                adm = registry.admissible(sel) & first_mask[cls_mask]
                res = queue.offer_many(sel.tolist(), tenant=str(c),
                                       admissible=adm.tolist())
                offered += int(sel.size)
                shed_full += res["shed_queue_full"]
                shed_inad += res["shed_inadmissible"]
            # DRR-fair drain into the checked-in pool
            by_class: Dict[str, List[int]] = {}
            while True:
                item = queue.poll()
                if item is None:
                    break
                by_class.setdefault(
                    str(int(item) % cfg.num_classes), []).append(int(item))
            ready = {c for c, lst in by_class.items() if lst}
            while ready:
                tenant = drr.next_tenant(ready=ready)
                if tenant is None:
                    break
                lst = by_class[tenant]
                grant, by_class[tenant] = lst[:32], lst[32:]
                drr.charge(tenant, float(len(grant)))
                if not by_class[tenant]:
                    ready.discard(tenant)
                registry.mark_checked_in(grant)
                accepted += len(grant)
                pool.extend(grant)
            edge_s += time.perf_counter() - t_edge

        # --- commits: cohorts from the currently-available pool ----------
        tick_commits = 0
        while tick_commits < cfg.max_commits_per_tick:
            # pool members a churn wave evaporated since check-in drop out
            # here (already counted as dropouts by the wave)
            pool = [d for d in pool
                    if registry.state[d] == CHECKED_IN]
            if len(pool) < cfg.cohort:
                break
            cohort_ids = np.asarray(pool[:cfg.cohort], dtype=np.int64)
            pool = pool[cfg.cohort:]
            registry.mark_training(cohort_ids)
            cohort_slots += int(cohort_ids.size)
            commit_idx += 1
            tick_commits += 1
            commits += 1
            crng = np.random.default_rng([seed, 0x_D09, commit_idx])
            drop_mask = crng.random(cohort_ids.size) < cfg.dropout_rate
            if cut:
                # uploads from a cut-off class cannot cross the partition
                drop_mask |= np.isin(registry.device_class(cohort_ids),
                                     list(cut))
            drops = cohort_ids[drop_mask]
            survivors = cohort_ids[~drop_mask]
            if drops.size:
                registry.mark_dropped(drops)
                mid_drops += int(drops.size)
            if survivors.size == 0:
                zero_survivor += 1
                continue  # shrunken to nothing: skip the fold, never hang
            momenta = np.asarray(arena.gather(survivors))
            updates, m_new = model.local_updates(survivors, momenta)
            arena.scatter(survivors, m_new)
            # tier-plane fan-in: leaf chunks fold first, the root folds the
            # leaf partials (identical math to the hierarchical plane)
            parts, _ = contiguous_group_split(survivors, cfg.num_leaves)
            offsets = np.cumsum([0] + [len(p) for p in parts])
            leaf_us, leaf_ws = [], []
            for g, part in enumerate(parts):
                if not len(part):
                    continue
                rows = updates[offsets[g]:offsets[g + 1]]
                w = np.full(len(part), cfg.local_batch, np.float32)
                leaf_us.append(np.asarray(fold_partials(rows, w)))
                leaf_ws.append(float(len(part) * cfg.local_batch))
            delta = np.asarray(fold_partials(
                np.stack(leaf_us), np.asarray(leaf_ws, np.float32)))
            model.params = model.params + delta
            version += 1
            dups = ledger.record(commit_idx, survivors)
            assert not dups, f"double commit: {dups[:4]}"
            version_log.append([version, int(survivors.size)])
            version_log = trim_version_log(version_log, cfg.keep_versions)
            registry.mark_uploaded(survivors, version)
            committed += int(survivors.size)

        # --- end of tick: pool bound, natural recovery, eval -------------
        pool_max = cfg.pool_max_factor * cfg.cohort
        if len(pool) > pool_max:
            excess, pool = pool[pool_max:], pool[:pool_max]
            registry.release(excess)
            not_selected += len(excess)
        recovered = registry.recover(cfg.recovery_rate, tick_rng)
        tick_rec.update(
            offered=offered, accepted=accepted,
            shed_queue_full=shed_full, shed_inadmissible=shed_inad,
            commits=tick_commits, version=version, recovered=recovered,
            pool=len(pool))
        if (tick % max(1, cfg.eval_every_ticks)
                == max(1, cfg.eval_every_ticks) - 1):
            tick_rec["acc"] = round(model.accuracy(), 6)
        history.append(tick_rec)

    # unselected stragglers at end of day are released, not lost
    if pool:
        registry.release(pool)
        not_selected += len(pool)
    in_flight_eod = len(heap)   # announces that would land tomorrow

    final_acc = model.accuracy()
    stats = queue.stats()
    rc = registry.counters
    history_digest = hashlib.sha256(
        json.dumps(history, sort_keys=True).encode()).hexdigest()
    params_digest = hashlib.sha256(model.params.tobytes()).hexdigest()
    return DeviceDayResult(
        elapsed_s=time.perf_counter() - t_start,
        ticks=cfg.n_ticks,
        registry_size=cfg.registry_size,
        arrivals=arrivals,
        partition_blackholed=blackholed,
        offered=offered,
        accepted=accepted,
        shed_queue_full=shed_full,
        shed_inadmissible=shed_inad,
        not_selected=not_selected,
        in_flight_eod=in_flight_eod,
        commits=commits,
        zero_survivor_commits=zero_survivor,
        cohort_slots=cohort_slots,
        committed_updates=committed,
        mid_round_drops=mid_drops,
        dropouts=rc["dropouts"],
        rejoins=rc["rejoins"],
        resync_full=rc["resync_full"],
        resync_incremental=rc["resync_incremental"],
        departures=rc["departures"],
        reclaimed_spill_files=reclaimed,
        duplicates=ledger.duplicates,
        final_version=version,
        final_acc=final_acc,
        admission_edge_s=edge_s,
        max_queue_depth=stats["max_depth"],
        queue_maxsize=stats["maxsize"],
        arena_resident=arena.resident_count,
        arena_spilled=arena.spilled_count,
        history_digest=history_digest,
        params_digest=params_digest,
        history=history,
    )


# --- the churn drill ---------------------------------------------------------

CHURN_DRILL_DEFAULTS = dict(
    registry_size=20_000,
    day_s=7_200.0,
    tick_s=120.0,
    num_classes=4,
    cohort=48,
    queue_maxsize=512,   # tight enough that peak ticks shed (queue_full)
    peak_rate=6.0,
    max_commits_per_tick=2,
    arena_capacity=512,
    host_capacity=2048,
    eval_every_ticks=4,
    churn_fraction=0.3,
    churn_rejoin_ticks=3,
    churn_permanent_fraction=0.1,
    churn_partition_classes=1,
    churn_partition_ticks=4,
)


@dataclasses.dataclass
class DeviceChurnDrillResult:
    """Churn drill verdict: the churned day vs its churn-free reference."""

    reference: DeviceDayResult
    churned: DeviceDayResult
    replay_digest: str
    max_acc_delta: float

    @property
    def acc_delta(self) -> float:
        return abs(self.reference.final_acc - self.churned.final_acc)

    @property
    def replay_identical(self) -> bool:
        return self.replay_digest == self.churned.history_digest

    @property
    def ok(self) -> bool:
        return (self.reference.ok and self.churned.ok
                and self.acc_delta <= self.max_acc_delta
                and self.replay_identical
                and self.churned.dropouts > 0
                and self.churned.rejoins > 0
                and self.churned.departures > 0
                and self.churned.partition_blackholed > 0)

    def summary(self) -> str:
        c = self.churned
        return (
            f"device-churn drill: {'PASS' if self.ok else 'FAIL'} — "
            f"acc {c.final_acc:.3f} vs reference "
            f"{self.reference.final_acc:.3f} (delta {self.acc_delta:.3f} <= "
            f"{self.max_acc_delta}) | {c.dropouts} dropouts, {c.rejoins} "
            f"rejoins, {c.departures} departed "
            f"({c.reclaimed_spill_files} spill files reclaimed), "
            f"{c.partition_blackholed} blackholed | sheds "
            f"{c.shed_queue_full} full / {c.shed_inadmissible} inadmissible"
            f" | replay {'bit-identical' if self.replay_identical else 'DIVERGED'}"
        )

    def json_record(self) -> dict:
        return {
            "acc_reference": round(self.reference.final_acc, 6),
            "acc_churned": round(self.churned.final_acc, 6),
            "acc_delta": round(self.acc_delta, 6),
            "max_acc_delta": self.max_acc_delta,
            "replay_identical": self.replay_identical,
            "reference": self.reference.json_record(),
            "churned": self.churned.json_record(),
            "ok": self.ok,
        }


def run_device_churn_drill(cfg: Optional[DeviceDayConfig] = None,
                           max_acc_delta: float = 0.02,
                           spill_dir: Optional[str] = None
                           ) -> DeviceChurnDrillResult:
    """The robustness headline: run the churn-free reference day, then the
    same day with 30% fleet churn (dropout wave + seeded rejoin waves + a
    permanent-departure subset + one partition window), then replay the
    churned day and require a byte-identical history. Gates: accuracy
    within ``max_acc_delta`` of the reference, full shed/drop accounting,
    zero ledger duplicates, bit-identical replay."""
    if cfg is None:
        cfg = DeviceDayConfig(**CHURN_DRILL_DEFAULTS, spill_dir=spill_dir)

    def _isolated(run_cfg: DeviceDayConfig, name: str) -> DeviceDayConfig:
        # each run spills into its own subdirectory, so reclaim counts and
        # disk contents never leak between the churned run and its replay
        if not run_cfg.spill_dir:
            return run_cfg
        sub = os.path.join(run_cfg.spill_dir, name)
        os.makedirs(sub, exist_ok=True)
        return dataclasses.replace(run_cfg, spill_dir=sub)

    reference = run_device_day(dataclasses.replace(
        cfg, churn_fraction=0.0, churn_partition_classes=0,
        churn_partition_ticks=0, spill_dir=None))
    churned = run_device_day(_isolated(cfg, "churned"))
    replay = run_device_day(_isolated(cfg, "replay"))
    return DeviceChurnDrillResult(
        reference=reference, churned=churned,
        replay_digest=replay.history_digest,
        max_acc_delta=float(max_acc_delta))


# --- config plumbing ---------------------------------------------------------

def config_from_args(args) -> DeviceDayConfig:
    """Map the flat ``device_*`` / ``churn_*`` config keys onto a
    :class:`DeviceDayConfig` (the getattr sites feed the generated config
    reference)."""
    d = DEVICE_DAY_DEFAULTS
    return DeviceDayConfig(
        registry_size=int(getattr(args, "device_registry_size",
                                  d["device_registry_size"])),
        day_s=float(getattr(args, "device_day_s", d["device_day_s"])),
        tick_s=float(getattr(args, "device_tick_s", d["device_tick_s"])),
        num_classes=int(getattr(args, "device_classes",
                                d["device_classes"])),
        cohort=int(getattr(args, "device_cohort", d["device_cohort"])),
        queue_maxsize=int(getattr(args, "device_queue_maxsize",
                                  d["device_queue_maxsize"])),
        peak_rate=float(getattr(args, "device_peak_rate",
                                d["device_peak_rate"])),
        trough_fraction=float(getattr(args, "device_trough_fraction",
                                      d["device_trough_fraction"])),
        arrival_spread_ticks=float(
            getattr(args, "device_arrival_spread_ticks",
                    d["device_arrival_spread_ticks"])),
        dropout_rate=float(getattr(args, "device_dropout_rate",
                                   d["device_dropout_rate"])),
        recovery_rate=float(getattr(args, "device_recovery_rate",
                                    d["device_recovery_rate"])),
        max_commits_per_tick=int(getattr(args, "device_max_commits_per_tick",
                                         d["device_max_commits_per_tick"])),
        pool_max_factor=int(getattr(args, "device_pool_max_factor",
                                    d["device_pool_max_factor"])),
        feature_dim=int(getattr(args, "device_feature_dim",
                                d["device_feature_dim"])),
        num_labels=int(getattr(args, "device_num_labels",
                               d["device_num_labels"])),
        local_batch=int(getattr(args, "device_local_batch",
                                d["device_local_batch"])),
        lr=float(getattr(args, "device_lr", d["device_lr"])),
        momentum=float(getattr(args, "device_momentum",
                               d["device_momentum"])),
        arena_capacity=int(getattr(args, "device_arena_capacity",
                                   d["device_arena_capacity"])),
        host_capacity=int(getattr(args, "device_host_capacity",
                                  d["device_host_capacity"])),
        spill_dir=str(getattr(args, "device_spill_dir",
                              d["device_spill_dir"])) or None,
        keep_versions=int(getattr(args, "device_keep_versions",
                                  d["device_keep_versions"])),
        num_leaves=int(getattr(args, "device_leaves", d["device_leaves"])),
        eval_every_ticks=int(getattr(args, "device_eval_every_ticks",
                                     d["device_eval_every_ticks"])),
        seed=int(getattr(args, "device_seed", d["device_seed"])),
        churn_fraction=float(getattr(args, "churn_fraction",
                                     d["churn_fraction"])),
        churn_dropout_tick=int(getattr(args, "churn_dropout_tick",
                                       d["churn_dropout_tick"])),
        churn_rejoin_ticks=int(getattr(args, "churn_rejoin_ticks",
                                       d["churn_rejoin_ticks"])),
        churn_permanent_fraction=float(
            getattr(args, "churn_permanent_fraction",
                    d["churn_permanent_fraction"])),
        churn_partition_classes=int(
            getattr(args, "churn_partition_classes",
                    d["churn_partition_classes"])),
        churn_partition_ticks=int(getattr(args, "churn_partition_ticks",
                                          d["churn_partition_ticks"])),
    )


def run_device_day_from_args(args) -> DeviceDayResult:
    return run_device_day(config_from_args(args))
