"""Chaos drill: a full cross-silo FL run under a seeded fault plan.

One entry point — :func:`run_chaos_drill` — stands up a complete loopback
deployment (server + N silo clients, real message codec, real round FSM),
switches on the requested ``fault_*`` plan, runs it to completion, and
reports whether every round closed plus what the resilience plane did along
the way (faults injected, sends retried, sends declared dead).

Shared by the ``fedml-tpu chaos-drill`` CLI command and the
``tests/test_chaos.py`` suite — one implementation, two front doors, so the
drill the CI gate runs is exactly the drill an operator can run by hand
against a proposed config change.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Dict, List, Optional

PHASE_DEFAULTS = dict(
    dataset="mnist",
    model="lr",
    debug_small_data=True,
    client_num_in_total=3,
    client_num_per_round=3,
    comm_round=3,
    learning_rate=0.1,
    epochs=1,
    batch_size=8,
    frequency_of_the_test=1,
    random_seed=0,
    # recovery knobs: a drill must terminate even when messages vanish, so
    # rounds close on a short straggler timeout with whatever arrived
    round_timeout=2.0,
    min_clients_per_round=1,
    handshake_timeout=2.0,
    # the default plan: WAN-grade packet loss on every message type
    fault_seed=7,
    fault_drop_rate=0.2,
)


@dataclasses.dataclass
class ChaosDrillResult:
    rounds_completed: int
    rounds_expected: int
    elapsed_s: float
    faults_injected: Dict[str, float]
    send_retries: float
    send_failures: float
    history: List[dict]
    # self-healing plane (PR 4): sanitizer quarantine hits and watchdog
    # rollbacks observed during the drill (0 unless defenses are on)
    quarantined: float = 0.0
    rollbacks: float = 0.0
    # compressed update plane: raw/wire byte deltas keyed by plane
    # (uplink/downlink); empty unless comm_codec was active in the drill
    codec_bytes_raw: Dict[str, float] = dataclasses.field(default_factory=dict)
    codec_bytes_wire: Dict[str, float] = dataclasses.field(default_factory=dict)
    # tenant whose scoped registry the drill accounted against (None = global)
    tenant: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.rounds_completed >= self.rounds_expected

    def codec_ratio(self, plane: str = "uplink") -> float:
        """Raw/wire compression ratio observed on one plane (1.0 when the
        codec was off or produced no traffic there)."""
        raw = self.codec_bytes_raw.get(plane, 0.0)
        wire = self.codec_bytes_wire.get(plane, 0.0)
        return raw / wire if raw > 0 and wire > 0 else 1.0

    def summary(self) -> str:
        faults = ", ".join(f"{k}={int(v)}"
                           for k, v in sorted(self.faults_injected.items()))
        healing = ""
        if self.quarantined or self.rollbacks:
            healing = (f" | quarantined={int(self.quarantined)} "
                       f"rollbacks={int(self.rollbacks)}")
        codec = ""
        if self.codec_bytes_wire:
            codec = (f" | codec uplink {self.codec_ratio('uplink'):.1f}x "
                     f"({int(self.codec_bytes_wire.get('uplink', 0))}B wire)")
        return (
            f"chaos drill: {'PASS' if self.ok else 'FAIL'} — "
            f"{self.rounds_completed}/{self.rounds_expected} rounds in "
            f"{self.elapsed_s:.1f}s | faults injected: {faults or 'none'} | "
            f"sends retried={int(self.send_retries)} "
            f"declared-dead={int(self.send_failures)}" + healing + codec
        )

    def json_record(self) -> dict:
        """The drill outcome as one JSON-able dict — the single reporter
        behind ``fedml-tpu chaos-drill --json`` (callers add their own
        ``metric``/``unit`` framing on top)."""
        rec = {
            "rounds_completed": self.rounds_completed,
            "rounds_expected": self.rounds_expected,
            "elapsed_s": round(self.elapsed_s, 3),
            "faults_injected": {k: int(v)
                                for k, v in sorted(self.faults_injected.items())},
            "send_retries": int(self.send_retries),
            "send_failures": int(self.send_failures),
            "quarantined": int(self.quarantined),
            "rollbacks": int(self.rollbacks),
            "ok": self.ok,
        }
        if self.tenant is not None:
            rec["tenant"] = self.tenant
        if self.codec_bytes_wire:
            rec["codec_bytes_raw"] = {
                k: int(v) for k, v in sorted(self.codec_bytes_raw.items())}
            rec["codec_bytes_wire"] = {
                k: int(v) for k, v in sorted(self.codec_bytes_wire.items())}
            rec["codec_uplink_ratio"] = round(self.codec_ratio("uplink"), 3)
        return rec


STRAGGLER_DEFAULTS = dict(
    dataset="digits",
    model="lr",
    partition_method="homo",
    client_num_in_total=8,
    client_num_per_round=8,
    comm_round=6,
    learning_rate=0.3,
    epochs=1,
    batch_size=32,
    frequency_of_the_test=3,
    random_seed=0,
    # the straggler plan: deterministic heavy-tail speed skew — the slowest
    # client runs async_delay_skew× slower than the fastest, per-round jitter
    # on top, all hash-seeded so every drill replays bit-for-bit
    async_buffer_size=2,
    async_staleness_alpha=0.5,
    async_delay_base_s=1.0,
    async_delay_skew=10.0,
    async_delay_jitter=0.2,
)


@dataclasses.dataclass
class StragglerDrillResult:
    """Sync-vs-async outcome under one seeded straggler plan. Goodput is
    measured on the shared virtual clock (committed updates per virtual
    second), so the comparison is deterministic — a wall-clock drill would
    gate CI on scheduler noise."""

    commits: int
    committed_updates: int
    shed_updates: int
    staleness_max: int
    sync_round_rate: float   # sync rounds per virtual second (barrier pace)
    async_goodput_ups: float  # async committed updates per virtual second
    sync_final_acc: float
    async_final_acc: float
    elapsed_s: float
    min_goodput_ratio: float = 3.0
    max_acc_delta: float = 0.02
    history: List[dict] = dataclasses.field(default_factory=list)

    @property
    def goodput_ratio(self) -> float:
        """Committed-update goodput over the synchronous round rate — the
        acceptance metric: a sync round folds its whole cohort but lands only
        at the barrier pace the slowest client sets, while async keeps
        committing off the fast clients the barrier would have idled."""
        return (self.async_goodput_ups / self.sync_round_rate
                if self.sync_round_rate > 0 else 0.0)

    @property
    def acc_delta(self) -> float:
        return self.sync_final_acc - self.async_final_acc

    @property
    def ok(self) -> bool:
        return (self.goodput_ratio >= self.min_goodput_ratio
                and self.acc_delta <= self.max_acc_delta)

    def summary(self) -> str:
        return (
            f"straggler drill: {'PASS' if self.ok else 'FAIL'} — "
            f"async {self.async_goodput_ups:.2f} upd/vs vs sync "
            f"{self.sync_round_rate:.2f} rounds/vs "
            f"({self.goodput_ratio:.1f}x, gate >={self.min_goodput_ratio:.1f}x)"
            f" | acc async {self.async_final_acc:.4f} vs sync "
            f"{self.sync_final_acc:.4f} (delta {self.acc_delta:+.4f}, gate "
            f"<={self.max_acc_delta:.2f}) | {self.commits} commits, "
            f"{self.committed_updates} updates, max staleness "
            f"{self.staleness_max}, shed {self.shed_updates}"
        )

    def json_record(self) -> dict:
        """Same single-reporter contract as :meth:`ChaosDrillResult.
        json_record` — one JSON-able dict behind ``fedml-tpu chaos-drill
        --straggler --json``."""
        return {
            "commits": self.commits,
            "committed_updates": self.committed_updates,
            "shed_updates": self.shed_updates,
            "staleness_max": self.staleness_max,
            "sync_rounds_per_vs": round(self.sync_round_rate, 4),
            "async_goodput_updates_per_vs": round(self.async_goodput_ups, 4),
            "goodput_ratio": round(self.goodput_ratio, 3),
            "sync_final_acc": round(self.sync_final_acc, 6),
            "async_final_acc": round(self.async_final_acc, 6),
            "acc_delta": round(self.acc_delta, 6),
            "elapsed_s": round(self.elapsed_s, 3),
            "ok": self.ok,
        }


def _final_acc(history: List[dict]) -> float:
    accs = [r["test_acc"] for r in history if "test_acc" in r]
    return float(accs[-1]) if accs else float("nan")


def run_straggler_drill(min_goodput_ratio: float = 3.0,
                        max_acc_delta: float = 0.02,
                        **overrides) -> StragglerDrillResult:
    """Run the sync and buffered-async simulation engines over the SAME
    seeded heavy-tail delay plan and compare goodput + final accuracy.

    The sync side barriers every round on the slowest sampled client
    (:func:`~fedml_tpu.simulation.async_engine.sync_virtual_seconds`), the
    async side commits every ``async_buffer_size`` arrivals — both on the
    identical hash-seeded virtual clock, so the reported ratio is a property
    of the plan, not of the machine running the drill."""
    import time as _time

    import fedml_tpu
    from ..comm.resilience import ClientDelayPlan
    from ..simulation import build_simulator
    from ..simulation.async_engine import sync_virtual_seconds

    cfg = dict(STRAGGLER_DEFAULTS)
    cfg.update(overrides)
    t0 = _time.perf_counter()

    def _run(extra):
        args = fedml_tpu.init(config=dict(cfg, **extra))
        sim, apply_fn = build_simulator(args)
        history = sim.run(apply_fn, log_fn=None)
        return sim, history

    sync_sim, sync_hist = _run({"async_mode": False})
    async_sim, async_hist = _run({"async_mode": True})

    plan = ClientDelayPlan(
        seed=int(cfg["random_seed"]), base_s=float(cfg["async_delay_base_s"]),
        skew=float(cfg["async_delay_skew"]),
        jitter=float(cfg["async_delay_jitter"]))
    n_rounds = int(cfg["comm_round"])
    cohort = int(cfg["client_num_per_round"])
    sync_vs = sync_virtual_seconds(
        plan, float(cfg["async_delay_base_s"]), range(cohort), n_rounds)
    stats = async_sim.async_stats()
    return StragglerDrillResult(
        commits=int(stats["version"]),
        committed_updates=int(stats["committed_updates"]),
        shed_updates=int(stats["shed_updates"]),
        staleness_max=max(
            (int(r.get("staleness_max", 0)) for r in async_hist), default=0),
        sync_round_rate=n_rounds / sync_vs if sync_vs > 0 else 0.0,
        async_goodput_ups=float(stats["goodput_updates_per_s"]),
        sync_final_acc=_final_acc(sync_hist),
        async_final_acc=_final_acc(async_hist),
        elapsed_s=_time.perf_counter() - t0,
        min_goodput_ratio=float(min_goodput_ratio),
        max_acc_delta=float(max_acc_delta),
        history=list(async_hist),
    )


TIER_DEFAULTS = dict(
    dataset="mnist",
    model="lr",
    debug_small_data=True,
    client_num_in_total=6,
    client_num_per_round=4,
    comm_round=3,
    learning_rate=0.1,
    epochs=1,
    batch_size=8,
    frequency_of_the_test=1,
    random_seed=0,
    # the tier plane: 1 root + 2 leaf aggregators over loopback, aggressive
    # lease cadence so a killed leaf is detected within the drill's budget
    hier_num_leaves=2,
    group_comm_round=2,
    lease_ttl_s=0.5,
    lease_heartbeat_s=0.1,
    hier_round_timeout_s=30.0,
    hier_join_timeout_s=20.0,
)


@dataclasses.dataclass
class TierDrillResult:
    """Outcome of one hierarchical-federation drill (leaf crash or
    partition): did the run survive the fault, was every surviving client's
    update committed exactly once, and did the final model stay within the
    accuracy gate of the fault-free reference?"""

    scenario: str                 # "leaf_crash" | "partition"
    rounds_completed: int
    rounds_expected: int
    failovers: int                # lease expiries that triggered reassignment
    rehydrations: int             # chunks recovered from a dead leaf's shard
    committed_updates: int        # client updates folded, across all rounds
    expected_updates: int         # rounds x cohort — what exactly-once means
    duplicate_commits: int        # ledger-caught double-folds (must be 0)
    faults_injected: Dict[str, float]
    fault_free_acc: float
    faulted_acc: float
    elapsed_s: float
    max_acc_delta: float = 0.02
    history: List[dict] = dataclasses.field(default_factory=list)

    @property
    def acc_delta(self) -> float:
        return self.fault_free_acc - self.faulted_acc

    @property
    def ok(self) -> bool:
        return (self.rounds_completed >= self.rounds_expected
                and self.failovers >= 1          # the fault actually fired
                and self.duplicate_commits == 0
                and self.committed_updates == self.expected_updates
                and self.acc_delta <= self.max_acc_delta)

    def summary(self) -> str:
        return (
            f"tier drill [{self.scenario}]: {'PASS' if self.ok else 'FAIL'}"
            f" — {self.rounds_completed}/{self.rounds_expected} rounds in "
            f"{self.elapsed_s:.1f}s | failovers={self.failovers} "
            f"rehydrations={self.rehydrations} | committed "
            f"{self.committed_updates}/{self.expected_updates} updates, "
            f"{self.duplicate_commits} duplicates | acc faulted "
            f"{self.faulted_acc:.4f} vs fault-free {self.fault_free_acc:.4f}"
            f" (delta {self.acc_delta:+.4f}, gate <={self.max_acc_delta:.2f})"
        )

    def json_record(self) -> dict:
        """Same single-reporter contract as :meth:`ChaosDrillResult.
        json_record` — one JSON-able dict behind ``fedml-tpu chaos-drill
        --leaf-crash/--partition --json``."""
        return {
            "scenario": self.scenario,
            "rounds_completed": self.rounds_completed,
            "rounds_expected": self.rounds_expected,
            "failovers": self.failovers,
            "rehydrations": self.rehydrations,
            "committed_updates": self.committed_updates,
            "expected_updates": self.expected_updates,
            "duplicate_commits": self.duplicate_commits,
            "faults_injected": {k: int(v)
                                for k, v in sorted(self.faults_injected.items())},
            "fault_free_acc": round(self.fault_free_acc, 6),
            "faulted_acc": round(self.faulted_acc, 6),
            "acc_delta": round(self.acc_delta, 6),
            "elapsed_s": round(self.elapsed_s, 3),
            "ok": self.ok,
        }


def run_tier_drill(scenario: str = "leaf_crash",
                   max_acc_delta: float = 0.02,
                   **overrides) -> TierDrillResult:
    """Run one hierarchical-federation failure drill over loopback.

    ``leaf_crash`` kills leaf aggregator 1 mid-generation (it computes and
    persists its shard, then dies uploading — the rehydration path's exact
    cut point); ``partition`` cuts root<->leaf-1 for one round window and
    lets the cut heal. Both run a fault-free single-process reference over
    the same seed first, so the accuracy gate — and in practice bit-identical
    params — pins that failover loses no client update and commits none
    twice."""
    import tempfile
    import time as _time

    import fedml_tpu
    from ..core import telemetry
    from ..simulation.federation import (build_tiered_simulator,
                                         run_tiered_federation)

    if scenario not in ("leaf_crash", "partition"):
        raise ValueError(f"unknown tier drill scenario: {scenario!r}")
    cfg = dict(TIER_DEFAULTS)
    cfg.update(overrides)
    rounds = int(cfg["comm_round"])
    cohort = int(cfg["client_num_per_round"])
    t0 = _time.perf_counter()

    # fault-free reference: the single-process driver (same chunks, same
    # leaf program, same fold — minus the wire and minus the fault plan)
    ref_sim, ref_apply = build_tiered_simulator(fedml_tpu.init(config=cfg))
    ref_hist = ref_sim.run(ref_apply, log_fn=None)

    faulted = dict(cfg)
    if scenario == "leaf_crash":
        faulted.setdefault("hier_shard_dir", tempfile.mkdtemp(
            prefix="tier_drill_shards_"))
        faulted.update(fault_leaf_crash_rank=1, fault_leaf_crash_at_round=1)
    else:
        faulted.update(fault_partition_ranks_a=[0],
                       fault_partition_ranks_b=[1],
                       fault_partition_rounds=(1, 2))

    registry = telemetry.get_registry()
    before = registry.snapshot()["counters"] if telemetry.enabled() else {}
    root = run_tiered_federation(fedml_tpu.init(config=faulted))
    after = registry.snapshot()["counters"] if telemetry.enabled() else {}

    def delta(name, label=None):
        a = _label_totals(after, name, label)
        b = _label_totals(before, name, label)
        return {k: v - b.get(k, 0.0) for k, v in a.items()}

    ledger = root.state.ledger
    return TierDrillResult(
        scenario=scenario,
        rounds_completed=len(root.history),
        rounds_expected=rounds,
        failovers=int(root.failovers),
        rehydrations=int(root.rehydrations),
        committed_updates=int(ledger.total_commits),
        expected_updates=rounds * cohort,
        duplicate_commits=int(ledger.duplicates),
        faults_injected=delta("fedml_faults_injected_total", "action"),
        fault_free_acc=_final_acc(ref_hist),
        faulted_acc=_final_acc(root.history),
        elapsed_s=_time.perf_counter() - t0,
        max_acc_delta=float(max_acc_delta),
        history=list(root.history),
    )


def _label_totals(counters: Dict[str, float], name: str,
                  label: Optional[str] = None,
                  where: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """Collect ``name{...}`` counters from a registry snapshot; with
    ``label``, key the result by that label's value; ``where`` keeps only
    series whose labels match every given key=value pair."""
    out: Dict[str, float] = {}
    for key, value in counters.items():
        if not (key == name or key.startswith(name + "{")):
            continue
        inner = key[len(name):].strip("{}")
        labels = dict(kv.split("=", 1) for kv in inner.split(",") if "=" in kv)
        if where and any(labels.get(k) != v for k, v in where.items()):
            continue
        if label is None:
            out["total"] = out.get("total", 0.0) + value
            continue
        k = labels.get(label, "?")
        out[k] = out.get(k, 0.0) + value
    return out


def run_chaos_drill(args=None, n_clients: Optional[int] = None,
                    join_timeout_s: float = 120.0,
                    tenant: Optional[str] = None, registry=None,
                    **overrides) -> ChaosDrillResult:
    """Run one seeded chaos deployment over loopback and report the outcome.

    ``overrides`` lands on top of :data:`PHASE_DEFAULTS` (so e.g.
    ``fault_crash_rank=1`` or ``fault_drop_rate=0.4`` tweak the plan);
    passing a pre-built ``args`` skips the defaults entirely.

    ``tenant``/``registry`` scope the drill's accounting to one tenant: every
    server/client thread runs inside :func:`telemetry.tenant_scope`, so the
    resilience counters land tenant-labeled, and the before/after deltas are
    filtered to that tenant's series. Passing a
    :class:`~fedml_tpu.core.telemetry.TenantRegistry` (from
    :func:`telemetry.scoped_registry`) implies its tenant.
    """
    import fedml_tpu
    from ..comm import LoopbackHub
    from ..core import telemetry
    from .horizontal_api import FedML_Horizontal

    if args is None:
        cfg = dict(PHASE_DEFAULTS)
        cfg.update(overrides)
        args = fedml_tpu.init(config=cfg)
    # PHASE_DEFAULTS is the single source for drill defaults — a pre-built
    # args missing a key falls back to the same values the cfg path uses
    n = int(n_clients if n_clients is not None
            else getattr(args, "client_num_in_total",
                         PHASE_DEFAULTS["client_num_in_total"]))
    rounds = int(getattr(args, "comm_round", PHASE_DEFAULTS["comm_round"]))

    if registry is None:
        registry = telemetry.get_registry()
    if tenant is None:
        tenant = getattr(registry, "tenant", None)
    before = registry.snapshot()["counters"] if telemetry.enabled() else {}

    def scoped(fn):
        # contextvars do not inherit into threads: each drill thread must
        # enter the tenant scope inside its own body
        def runner():
            with telemetry.tenant_scope(tenant):
                fn()
        return runner

    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, n, backend="LOOPBACK", hub=hub)
    clients = [FedML_Horizontal(args, rank, n, backend="LOOPBACK", hub=hub)
               for rank in range(1, n + 1)]
    threads = [threading.Thread(target=scoped(c.run), daemon=True,
                                name=f"chaos-c{i+1}")
               for i, c in enumerate(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    with telemetry.tenant_scope(tenant):
        server.start()  # caller-thread sends must carry the label too
    server_thread = threading.Thread(target=scoped(server.run), daemon=True,
                                     name="chaos-server")
    server_thread.start()
    server_thread.join(timeout=join_timeout_s)
    hung = server_thread.is_alive()
    if hung:
        logging.error("chaos drill: server did not finish within %.0fs — "
                      "forcing shutdown", join_timeout_s)
        server.finish()
    for c in clients:
        c.com_manager.stop_receive_message()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t0

    after = registry.snapshot()["counters"] if telemetry.enabled() else {}
    twhere = {"tenant": tenant} if tenant is not None else {}

    def delta(name, label=None, where=None):
        w = dict(where or {}, **twhere) or None
        a = _label_totals(after, name, label, w)
        b = _label_totals(before, name, label, w)
        return {k: v - b.get(k, 0.0) for k, v in a.items()}

    # codec accounting from the ENCODE side only: the drill hosts server and
    # clients in one process, so summing encode+decode would double-count
    # every frame. encode's in=raw / out=wire on both planes.
    enc = {"direction": "encode"}
    return ChaosDrillResult(
        rounds_completed=len(server.history) if not hung else
        min(len(server.history), rounds - 1),  # a hung run never passes
        rounds_expected=rounds,
        elapsed_s=elapsed,
        faults_injected=delta("fedml_faults_injected_total", "action"),
        send_retries=sum(delta("fedml_send_retries_total").values()),
        send_failures=sum(delta("fedml_send_failures_total").values()),
        history=list(server.history),
        quarantined=sum(delta("fedml_quarantined_total").values()),
        rollbacks=sum(delta("fedml_rollbacks_total").values()),
        codec_bytes_raw=delta("fedml_codec_bytes_in", "plane", enc),
        codec_bytes_wire=delta("fedml_codec_bytes_out", "plane", enc),
        tenant=tenant,
    )


# --- poisoned-rollout drill (serving plane) ----------------------------------

ROLLOUT_DEFAULTS = dict(
    dataset="mnist",
    model="lr",
    debug_small_data=True,
    client_num_in_total=6,
    client_num_per_round=4,
    comm_round=6,
    learning_rate=0.1,
    epochs=1,
    batch_size=8,
    # every round commits AND evaluates synchronously, so publish order is
    # deterministic and each version number pairs with its exact round
    frequency_of_the_test=1,
    random_seed=0,
    prefetch=False,
    # serving plane: canary on, inline verdicts (no worker thread — the
    # drill wants the promote/rollback decision before publish returns)
    serve_enabled=True,
    canary_batches=4,
    canary_batch_size=64,
    canary_regression_threshold=0.02,
    canary_seed=0,
    # the poison: the publish artifact of this version is corrupted the way
    # a compromised rollout pipeline would corrupt it — training itself is
    # untouched, so fault-free and faulted runs train identically
    rollout_poison_version=5,
    rollout_poison_kind="sign_flip",
    rollout_poison_scale=10.0,
)


@dataclasses.dataclass
class RolloutDrillResult:
    """Outcome of one poisoned-rollout drill: did the canary block the
    poisoned promotion, did serving roll back to last-good, did served
    accuracy hold, and is the poisoned version pinned unre-promotable?"""

    poison_version: int
    poison_kind: str
    publishes: int
    promoted: int                 # hot-swaps in the faulted run
    rollbacks: int                # store rollbacks (>= 1: the fault fired)
    rollbacks_counter: float      # fedml_rollbacks_served_total delta
    poison_status: str            # publish() return for the poisoned version
    poison_verdict: str           # version-log verdict for that version
    repub_status: str             # re-publishing the CLEAN params afterwards
    served_acc_gap: float         # max over versions: ref served acc - faulted
    fault_free_acc: float         # final served accuracy, fault-free run
    faulted_acc: float            # final served accuracy, faulted run
    trajectory: List[dict]        # per publish: version/status/served acc
    elapsed_s: float
    max_acc_delta: float = 0.02

    @property
    def ok(self) -> bool:
        return (self.rollbacks >= 1
                and self.rollbacks_counter >= 1
                and self.poison_status == "rolled_back"
                and self.poison_verdict == "rolled_back"
                and self.repub_status == "pinned"
                and self.served_acc_gap <= self.max_acc_delta)

    def summary(self) -> str:
        return (
            f"rollout drill [{self.poison_kind} @ v{self.poison_version}]: "
            f"{'PASS' if self.ok else 'FAIL'} — {self.publishes} publishes, "
            f"{self.promoted} promoted, {self.rollbacks} rolled back in "
            f"{self.elapsed_s:.1f}s | poison {self.poison_status}/"
            f"{self.poison_verdict}, re-publish {self.repub_status} | "
            f"served acc gap {self.served_acc_gap:+.4f} "
            f"(gate <= {self.max_acc_delta:.2f}; final faulted "
            f"{self.faulted_acc:.4f} vs fault-free {self.fault_free_acc:.4f})"
        )

    def json_record(self) -> dict:
        return {
            "scenario": "rollout",
            "poison_version": self.poison_version,
            "poison_kind": self.poison_kind,
            "publishes": self.publishes,
            "promoted": self.promoted,
            "rollbacks": self.rollbacks,
            "rollbacks_counter": int(self.rollbacks_counter),
            "poison_status": self.poison_status,
            "poison_verdict": self.poison_verdict,
            "repub_status": self.repub_status,
            "served_acc_gap": round(self.served_acc_gap, 6),
            "fault_free_acc": round(self.fault_free_acc, 6),
            "faulted_acc": round(self.faulted_acc, 6),
            "trajectory": self.trajectory,
            "elapsed_s": round(self.elapsed_s, 3),
            "ok": self.ok,
        }


def run_rollout_drill(max_acc_delta: float = 0.02,
                      **overrides) -> RolloutDrillResult:
    """Poisoned-rollout drill: train a real simulator twice over the same
    seed, publishing every committed version through the canary-gated
    serving plane. The faulted run corrupts ONE version's published
    artifact (``rollout_poison_kind``, a byzantine kind from
    comm/resilience.py — training itself is untouched, modeling a
    compromised rollout pipeline, not a poisoned cohort). The canary must
    refuse the promotion, serving must keep answering from last-good within
    the accuracy gate, and the poisoned version must stay pinned: a later
    re-publish — even of CLEAN params under that version number — is
    refused, because a version number that shipped poison can never be
    trusted to mean one thing again."""
    import numpy as np

    import fedml_tpu
    from ..comm.resilience import corrupt_update_tree
    from ..core import telemetry
    from ..serving import (CanaryEvaluator, InferenceServer, ServeConfig,
                           held_out_batches)
    from ..simulation import build_simulator

    cfg = dict(ROLLOUT_DEFAULTS)
    cfg.update(overrides)
    poison_v = int(cfg["rollout_poison_version"])
    kind = str(cfg["rollout_poison_kind"])
    t0 = time.perf_counter()

    def _run(poison: bool):
        args = fedml_tpu.init(config=cfg)
        sim, apply_fn = build_simulator(args)
        scfg = ServeConfig.from_args(args)

        def predict(params, x):
            return np.asarray(apply_fn(params, np.asarray(x), train=False))

        test = sim.fed.test_data_global
        batches = held_out_batches(test.x, test.y, scfg.canary)
        evaluator = CanaryEvaluator(predict, batches, scfg.canary)
        server = InferenceServer(predict, scfg, eval_batches=batches)
        traj: List[dict] = []
        clean: Dict[int, object] = {}

        def publish(version, params):
            clean[int(version)] = params
            if poison and int(version) == poison_v:
                params = corrupt_update_tree(
                    params, kind, scale=float(cfg["rollout_poison_scale"]),
                    seed=int(cfg["random_seed"]))
            status = server.publish(version, params)
            act = server.store.active()
            served_acc = evaluator.score(act[1])[0] if act else 0.0
            traj.append({"version": int(version), "status": status,
                         "served_acc": round(served_acc, 6)})
            return status

        sim.attach_publisher(publish)
        sim.run(apply_fn, log_fn=None)
        return server, traj, clean

    # fault-free reference: same seed, same publishes, no poison
    _, ref_traj, _ = _run(poison=False)

    registry = telemetry.get_registry()
    before = registry.snapshot()["counters"] if telemetry.enabled() else {}
    server, traj, clean = _run(poison=True)
    after = registry.snapshot()["counters"] if telemetry.enabled() else {}

    def delta(name):
        a = _label_totals(after, name)
        b = _label_totals(before, name)
        return sum(a.values()) - sum(b.values())

    poison_recs = [r for r in traj if r["version"] == poison_v]
    poison_status = poison_recs[0]["status"] if poison_recs else "missing"
    verdicts = server.store.versions()
    # the pin: re-publishing the poisoned version number with the CLEAN
    # params must still be refused
    repub_status = server.publish(poison_v, clean[poison_v])
    gap = max((ref["served_acc"] - fau["served_acc"]
               for ref, fau in zip(ref_traj, traj)), default=float("nan"))
    store = server.store.stats()
    return RolloutDrillResult(
        poison_version=poison_v,
        poison_kind=kind,
        publishes=len(traj),
        promoted=store["swaps"],
        rollbacks=store["rollbacks"],
        rollbacks_counter=delta("fedml_rollbacks_served_total"),
        poison_status=poison_status,
        poison_verdict=str(verdicts.get(poison_v, "missing")),
        repub_status=repub_status,
        served_acc_gap=float(gap),
        fault_free_acc=ref_traj[-1]["served_acc"] if ref_traj else 0.0,
        faulted_acc=traj[-1]["served_acc"] if traj else 0.0,
        trajectory=traj,
        elapsed_s=time.perf_counter() - t0,
        max_acc_delta=float(max_acc_delta),
    )
