"""Check-in load generator: overload drills for the tenancy control plane.

Production FL serving is dominated not by training rounds but by device
*check-in* traffic — millions of phones announcing themselves, most of which
must be turned away politely. This harness replays tens of thousands of
simulated device check-ins per second through the real comm plane
(``comm.Message`` + msgpack codec, so every check-in pays honest
serialization cost) against a bounded
:class:`~fedml_tpu.core.tenancy.CheckinQueue`:

- N producer threads mint per-device check-in messages (round-robin across
  tenants), run each through a seeded
  :class:`~fedml_tpu.comm.resilience.FaultPlan` for realistic churn (a
  dropped check-in is a device that went away mid-announce — deterministic
  under the seed, so drills replay), and ``offer`` the serialized frame;
- one consumer drains the queue at its natural rate, deserializing each
  frame back through the codec — when producers outrun it, the bounded
  queue sheds and the per-tenant ``fedml_checkins_shed_total`` counters and
  depth gauge make the overload visible;
- the report carries the throughput/shed frontier: offered rate, processed
  rate, shed fraction, and the queue's high-water mark (which can never
  exceed ``queue_maxsize`` — that bound is the "zero unbounded memory
  growth" guarantee).

Front doors: ``fedml-tpu loadgen`` (CLI; ``--json`` for one JSON line) and
``tests/test_tenancy.py`` (``-m loadgen``).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..comm.message import Message
from ..comm.resilience import FaultPlan, FaultRule
from ..core import telemetry
from ..core.tenancy import CheckinQueue
from .chaos import _label_totals

MSG_TYPE_CHECKIN = "device_checkin"
TENANT_KEY = "tenant"

LOADGEN_DEFAULTS = dict(
    loadgen_duration_s=1.0,
    loadgen_target_rate=0.0,  # 0 = unthrottled (find the natural ceiling)
    loadgen_producers=2,
    loadgen_queue_maxsize=512,
    loadgen_tenants=2,
    loadgen_churn=0.1,
    loadgen_seed=0,
    loadgen_payload_bytes=64,
    # fixed simulated device population per producer: devices re-check-in
    # modulo this, which also bounds the fault plan's per-edge sequence
    # table (no per-message memory growth on long drills)
    loadgen_population=50_000,
)


# --- diurnal arrival curve ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DiurnalCurve:
    """Seeded diurnal arrival-rate curve: check-ins/s as a function of
    simulated time-of-day.

    Real cross-device fleets check in on a day/night cycle — devices charge
    and idle on wifi in the local evening (the FL eligibility window), so
    offered load swings several-fold between the overnight peak and the
    midday trough. The curve is a raised cosine between
    ``peak_rate * trough_fraction`` and ``peak_rate``, peaking at
    ``peak_hour``, plus a few small seeded harmonics so two seeds give two
    distinct (but individually reproducible) days. Everything is a pure
    function of ``(seed, t)``: the cross-device day driver replays
    bit-identically from it, and drills can dial overload by raising
    ``peak_rate`` past the admission edge's drain rate.
    """

    peak_rate: float
    trough_fraction: float = 0.2
    day_s: float = 86_400.0
    peak_hour: float = 20.0
    jitter: float = 0.05
    seed: int = 0

    def _harmonics(self):
        # three seeded overtones (amplitude, frequency multiple, phase) —
        # drawn once per curve, so rate(t) stays pure in (seed, t)
        rng = np.random.default_rng([int(self.seed), 0x_D1A2])
        amps = rng.uniform(0.2, 1.0, size=3) * float(self.jitter)
        freqs = rng.integers(2, 7, size=3)
        phases = rng.uniform(0.0, 2 * np.pi, size=3)
        return amps, freqs, phases

    def rate(self, t_s) -> np.ndarray:
        """Arrival rate (check-ins/s) at simulated time ``t_s``; accepts a
        scalar or an array and is vectorized over it."""
        t = np.asarray(t_s, dtype=np.float64)
        phase = 2 * np.pi * (t / self.day_s - self.peak_hour / 24.0)
        base = 0.5 * (1.0 + np.cos(phase))          # 1 at peak, 0 at trough
        shape = self.trough_fraction + (1.0 - self.trough_fraction) * base
        amps, freqs, phases = self._harmonics()
        wobble = sum(a * np.sin(2 * np.pi * f * t / self.day_s + p)
                     for a, f, p in zip(amps, freqs, phases))
        return np.maximum(0.0, float(self.peak_rate) * (shape + wobble))

    def expected_arrivals(self, t0_s: float, t1_s: float) -> float:
        """Expected check-ins in ``[t0_s, t1_s)`` (trapezoid over the
        endpoints — exact enough for tick-scale windows)."""
        r0, r1 = self.rate([t0_s, t1_s])
        return 0.5 * float(r0 + r1) * max(0.0, float(t1_s) - float(t0_s))

    def arrivals(self, t0_s: float, t1_s: float, rng) -> int:
        """Seeded Poisson draw of the arrival count for one tick window.
        The caller owns the generator (e.g. ``default_rng([seed, tick])``)
        so replays are bit-identical."""
        lam = self.expected_arrivals(t0_s, t1_s)
        return int(rng.poisson(lam)) if lam > 0 else 0


@dataclasses.dataclass
class LoadGenReport:
    elapsed_s: float
    offered: int
    accepted: int
    shed: int
    processed: int
    churned: int
    max_queue_depth: int
    queue_maxsize: int
    per_tenant_shed: Dict[str, float]
    per_tenant_accepted: Dict[str, float]

    @property
    def offered_rate(self) -> float:
        return self.offered / self.elapsed_s if self.elapsed_s > 0 else 0.0

    @property
    def processed_rate(self) -> float:
        return self.processed / self.elapsed_s if self.elapsed_s > 0 else 0.0

    @property
    def shed_fraction(self) -> float:
        return self.shed / self.offered if self.offered else 0.0

    @property
    def ok(self) -> bool:
        """Accounting closes and the queue bound held: every offered
        check-in was either accepted or shed, every processed frame was
        accepted first, and the depth high-water mark never passed the
        configured bound."""
        return (self.offered == self.accepted + self.shed
                and self.processed <= self.accepted
                and self.max_queue_depth <= self.queue_maxsize)

    def summary(self) -> str:
        return (
            f"loadgen: {'PASS' if self.ok else 'FAIL'} — "
            f"{self.offered_rate:,.0f} check-ins/s offered "
            f"({self.processed_rate:,.0f}/s processed) over "
            f"{self.elapsed_s:.2f}s | shed {self.shed} "
            f"({self.shed_fraction:.1%}), churned {self.churned} | "
            f"queue depth max {self.max_queue_depth}/{self.queue_maxsize}"
        )

    def json_record(self) -> dict:
        """The throughput/shed frontier as one JSON-able dict (what
        ``fedml-tpu loadgen --json`` prints)."""
        return {
            "elapsed_s": round(self.elapsed_s, 4),
            "offered": self.offered,
            "offered_per_sec": round(self.offered_rate, 1),
            "processed": self.processed,
            "processed_per_sec": round(self.processed_rate, 1),
            "shed": self.shed,
            "shed_fraction": round(self.shed_fraction, 4),
            "churned": self.churned,
            "max_queue_depth": self.max_queue_depth,
            "queue_maxsize": self.queue_maxsize,
            "queue_depth_bounded": self.max_queue_depth <= self.queue_maxsize,
            "per_tenant_shed": {k: int(v)
                                for k, v in sorted(self.per_tenant_shed.items())},
            "per_tenant_accepted": {
                k: int(v) for k, v in sorted(self.per_tenant_accepted.items())},
            "ok": self.ok,
        }


def _checkin_frame(device_id: int, tenant: str, payload: bytes) -> Message:
    msg = Message(type=MSG_TYPE_CHECKIN, sender_id=device_id, receiver_id=0)
    msg.add_params(TENANT_KEY, tenant)
    msg.add_params("capabilities", payload)
    return msg


def run_loadgen(duration_s: float = 1.0, target_rate: float = 0.0,
                producers: int = 2, queue_maxsize: int = 512,
                tenants: int = 2, churn: float = 0.1, seed: int = 0,
                payload_bytes: int = 64,
                population: int = 50_000) -> LoadGenReport:
    """Drive the bounded check-in queue as hard as requested and report the
    throughput/shed frontier. ``target_rate`` throttles the *aggregate*
    offered rate (0 = each producer runs flat out)."""
    tenant_names = [f"tenant{i}" for i in range(max(1, int(tenants)))]
    queue = CheckinQueue(maxsize=int(queue_maxsize))
    plan = FaultPlan(seed=int(seed),
                     rules=(FaultRule(action="drop", rate=float(churn)),)
                     if churn > 0 else ())
    payload = bytes(int(payload_bytes))
    stop = threading.Event()
    churned = [0] * int(producers)
    processed = [0]
    per_rate = (float(target_rate) / max(1, int(producers))
                if target_rate and target_rate > 0 else 0.0)

    registry = telemetry.get_registry()
    before = (registry.snapshot()["counters"]
              if telemetry.enabled() else {})

    def produce(worker: int) -> None:
        t0 = time.perf_counter()
        i = 0
        n_tenants = len(tenant_names)
        pop = max(1, int(population))
        while not stop.is_set():
            device_id = worker * 10_000_000 + (i % pop)
            tenant = tenant_names[device_id % n_tenants]
            msg = _checkin_frame(device_id, tenant, payload)
            if plan.active and plan.decide(msg).drop:
                # seeded churn: this device dropped off mid-announce
                churned[worker] += 1
            else:
                data = msg.to_bytes()
                queue.offer(data, tenant=tenant)
            i += 1
            if per_rate > 0 and i % 64 == 0:
                # pace toward the per-producer rate (sleep holds no lock)
                ahead = i / per_rate - (time.perf_counter() - t0)
                if ahead > 0.001:
                    time.sleep(min(ahead, 0.05))

    def consume() -> None:
        while True:
            data = queue.poll()
            if data is None:
                if stop.is_set():
                    return
                time.sleep(0.0005)
                continue
            msg = Message.from_bytes(data)  # real codec on the drain side too
            telemetry.record_receive("loadgen", len(data))
            processed[0] += 1
            assert msg.get_type() == MSG_TYPE_CHECKIN

    threads = [threading.Thread(target=produce, args=(w,), daemon=True,
                                name=f"loadgen-p{w}")
               for w in range(max(1, int(producers)))]
    consumer = threading.Thread(target=consume, daemon=True,
                                name="loadgen-consumer")
    t0 = time.perf_counter()
    consumer.start()
    for t in threads:
        t.start()
    # bounded wall-clock: the drill runs for duration_s, then drains
    time.sleep(max(0.01, float(duration_s)))
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t0
    consumer.join(timeout=10.0)

    after = (registry.snapshot()["counters"]
             if telemetry.enabled() else {})

    def delta(name: str) -> Dict[str, float]:
        a = _label_totals(after, name, label="tenant")
        b = _label_totals(before, name, label="tenant")
        return {k: v - b.get(k, 0.0) for k, v in a.items()}

    stats = queue.stats()
    return LoadGenReport(
        elapsed_s=elapsed,
        offered=stats["offered"],
        accepted=stats["accepted"],
        shed=stats["shed"],
        processed=processed[0],
        churned=sum(churned),
        max_queue_depth=stats["max_depth"],
        queue_maxsize=stats["maxsize"],
        per_tenant_shed=delta("fedml_checkins_shed_total"),
        per_tenant_accepted=delta("fedml_checkins_accepted_total"),
    )


# --- mixed train/serve traffic ----------------------------------------------

MIXED_DEFAULTS = dict(
    mixed_duration_s=1.0,
    mixed_target_rate=0.0,  # aggregate INFERENCE offer rate; 0 = flat out
    mixed_infer_producers=2,
    mixed_checkin_producers=1,
    mixed_queue_maxsize=8192,
    mixed_feature_dim=16,
    mixed_classes=10,
    mixed_commit_interval_s=0.05,
    mixed_min_swaps=5,
    mixed_seed=0,
)


@dataclasses.dataclass
class MixedLoadReport:
    """The mixed-traffic frontier: inference and training check-ins through
    ONE bounded admission queue, versions hot-swapping underneath."""

    elapsed_s: float
    submitted: int       # inference requests offered
    admitted: int        # inference requests accepted at the edge
    served: int          # inference requests answered (post-drain)
    canary_served: int   # of served, routed to an undecided candidate
    train_offered: int   # check-in frames offered (post-churn)
    train_processed: int  # check-in frames deserialized by the handler
    publishes: int
    swaps: int           # promoted versions = hot pointer swaps
    rollbacks: int
    min_swaps: int
    max_queue_depth: int
    queue_maxsize: int
    served_by_version: Dict[str, int]

    @property
    def shed(self) -> int:
        """Refused at the admission edge — bounded-queue overload working
        as designed, NOT a dropped request."""
        return self.submitted - self.admitted

    @property
    def dropped(self) -> int:
        """Admitted but never answered. The zero-drop hot-swap guarantee
        is exactly ``dropped == 0``."""
        return self.admitted - self.served

    @property
    def served_rate(self) -> float:
        return self.served / self.elapsed_s if self.elapsed_s > 0 else 0.0

    @property
    def ok(self) -> bool:
        return (self.dropped == 0
                and self.served == self.admitted
                and self.train_processed <= self.train_offered
                and self.max_queue_depth <= self.queue_maxsize
                and self.swaps >= self.min_swaps)

    def summary(self) -> str:
        return (
            f"mixed-loadgen: {'PASS' if self.ok else 'FAIL'} — "
            f"{self.served_rate:,.0f} req/s served over {self.elapsed_s:.2f}s "
            f"({self.canary_served} canary) | dropped {self.dropped}, "
            f"shed {self.shed} | {self.swaps} hot-swaps "
            f"(>= {self.min_swaps} required), {self.rollbacks} rollbacks | "
            f"train {self.train_processed}/{self.train_offered} frames | "
            f"queue depth max {self.max_queue_depth}/{self.queue_maxsize}"
        )

    def json_record(self) -> dict:
        return {
            "elapsed_s": round(self.elapsed_s, 4),
            "submitted": self.submitted,
            "admitted": self.admitted,
            "served": self.served,
            "served_per_sec": round(self.served_rate, 1),
            "canary_served": self.canary_served,
            "dropped": self.dropped,
            "shed": self.shed,
            "train_offered": self.train_offered,
            "train_processed": self.train_processed,
            "publishes": self.publishes,
            "swaps": self.swaps,
            "rollbacks": self.rollbacks,
            "min_swaps": self.min_swaps,
            "max_queue_depth": self.max_queue_depth,
            "queue_maxsize": self.queue_maxsize,
            "queue_depth_bounded": self.max_queue_depth <= self.queue_maxsize,
            "served_by_version": {
                str(k): int(v)
                for k, v in sorted(self.served_by_version.items())},
            "ok": self.ok,
        }


def run_mixed_loadgen(duration_s: float = 1.0, target_rate: float = 0.0,
                      infer_producers: int = 2, checkin_producers: int = 1,
                      queue_maxsize: int = 8192, feature_dim: int = 16,
                      classes: int = 10, commit_interval_s: float = 0.05,
                      min_swaps: int = 5, seed: int = 0,
                      payload_bytes: int = 64, population: int = 50_000,
                      server=None, committer=None) -> MixedLoadReport:
    """Mixed-traffic drill: inference requests AND training check-in frames
    share one bounded :class:`CheckinQueue`, drained deficit-round-robin by
    the serving worker, while a committer publishes new model versions
    underneath — the proof that hot-swaps drop nothing under load.

    Default harness is self-contained: a seeded numpy linear model serves,
    and a committer thread publishes perturbed weights every
    ``commit_interval_s`` (worker-mode canary gates each one). Callers may
    inject their own ``server`` (e.g. wired to a live simulator via
    ``serving.build_inference_server``) and/or ``committer(server, stop)``.
    """
    from ..core.tenancy import DeficitRoundRobinScheduler
    from ..serving import (CanaryConfig, InferenceServer, ServeConfig,
                           held_out_batches)

    rng = np.random.default_rng(int(seed))
    train_processed = [0]

    def handler(item) -> None:
        msg = Message.from_bytes(item)  # real codec on the drain side
        assert msg.get_type() == MSG_TYPE_CHECKIN
        train_processed[0] += 1

    if server is None:
        w0 = (rng.normal(size=(int(feature_dim), int(classes)))
              .astype(np.float32) * 0.5)
        x_pool = rng.normal(
            size=(4096, int(feature_dim))).astype(np.float32)
        y_pool = np.argmax(x_pool @ w0, axis=-1)

        def predict(params, x):
            return x @ params

        cfg = ServeConfig(
            enabled=True, queue_maxsize=int(queue_maxsize),
            canary=CanaryConfig(seed=int(seed)))
        drr = DeficitRoundRobinScheduler()
        drr.register("train", round_cost=1.0)
        decided = threading.Event()
        server = InferenceServer(
            predict, cfg,
            eval_batches=held_out_batches(x_pool, y_pool, cfg.canary),
            drr=drr, handler=handler,
            on_verdict=lambda _v, _s: decided.set())
        server.publish(1, w0)

        if committer is None:
            def committer(srv, stop_evt) -> None:
                version = 2
                while not stop_evt.is_set():
                    # small seeded drift: stays within the canary threshold,
                    # so every version promotes (a hot swap per commit)
                    delta = (np.random.default_rng(version)
                             .normal(size=w0.shape).astype(np.float32)
                             * 1e-4)
                    t_pub = time.perf_counter()
                    decided.clear()
                    status = srv.publish(version, w0 + delta)
                    if status == "candidate":
                        # trainer-paced rollout: block on the verdict (the
                        # canary window advances one held-out batch per
                        # pump), so a loaded host slows the commit cadence
                        # instead of superseding every candidate before
                        # its window closes
                        while (not stop_evt.is_set()
                               and not decided.wait(0.25)):
                            pass
                    version += 1
                    waited = time.perf_counter() - t_pub
                    stop_evt.wait(
                        max(float(commit_interval_s) - waited, 1e-3))
    else:
        server._handler = handler
        x_pool = rng.normal(
            size=(4096, int(feature_dim))).astype(np.float32)

    stop = threading.Event()
    per_rate = (float(target_rate) / max(1, int(infer_producers))
                if target_rate and target_rate > 0 else 0.0)

    def produce_infer(worker: int) -> None:
        t0 = time.perf_counter()
        i = 0
        n_pool = len(x_pool)
        while not stop.is_set():
            server.submit(x_pool[(worker + i) % n_pool],
                          request_id=(worker, i))
            i += 1
            if per_rate > 0 and i % 64 == 0:
                ahead = i / per_rate - (time.perf_counter() - t0)
                if ahead > 0.001:
                    time.sleep(min(ahead, 0.05))

    payload = bytes(int(payload_bytes))
    train_offered = [0] * max(1, int(checkin_producers))

    def produce_checkin(worker: int) -> None:
        i = 0
        pop = max(1, int(population))
        while not stop.is_set():
            device_id = worker * 10_000_000 + (i % pop)
            msg = _checkin_frame(device_id, "train", payload)
            server.queue.offer(msg.to_bytes(), tenant="train")
            train_offered[worker] += 1
            i += 1
            # check-ins are the background tenant: pace them well below the
            # inference stream so DRR fairness, not starvation, is on trial
            if i % 256 == 0:
                time.sleep(0.001)

    threads = [threading.Thread(target=produce_infer, args=(w,),
                                daemon=True, name=f"mixed-infer{w}")
               for w in range(max(1, int(infer_producers)))]
    threads += [threading.Thread(target=produce_checkin, args=(w,),
                                 daemon=True, name=f"mixed-checkin{w}")
                for w in range(max(1, int(checkin_producers)))]
    commit_thread = None
    if committer is not None:
        commit_thread = threading.Thread(
            target=committer, args=(server, stop), daemon=True,
            name="mixed-committer")

    t0 = time.perf_counter()
    server.start()
    for t in threads:
        t.start()
    if commit_thread is not None:
        commit_thread.start()
    time.sleep(max(0.01, float(duration_s)))
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    if commit_thread is not None:
        commit_thread.join(timeout=10.0)
    # stop drains the queue and lands any in-flight canary verdict, so the
    # zero-drop accounting below is exact, not racy
    server.stop(drain=True)
    elapsed = time.perf_counter() - t0

    st = server.stats()
    store = st["store"]
    log = server.store.export_state()["log"]
    return MixedLoadReport(
        elapsed_s=elapsed,
        submitted=st["submitted"],
        admitted=st["admitted"],
        served=st["served"],
        canary_served=st["canary_served"],
        train_offered=sum(train_offered),
        train_processed=train_processed[0],
        publishes=sum(1 for _, ev in log if ev == "publish"),
        swaps=store["swaps"],  # promote() pointer swaps; v1 doesn't count
        rollbacks=store["rollbacks"],
        min_swaps=int(min_swaps),
        max_queue_depth=st["queue"]["max_depth"],
        queue_maxsize=st["queue"]["maxsize"],
        served_by_version=st["served_by_version"],
    )


def run_mixed_loadgen_from_args(args) -> MixedLoadReport:
    """Map the flat ``mixed_*`` config keys onto :func:`run_mixed_loadgen`."""
    d = MIXED_DEFAULTS
    return run_mixed_loadgen(
        duration_s=float(getattr(args, "mixed_duration_s",
                                 d["mixed_duration_s"])),
        target_rate=float(getattr(args, "mixed_target_rate",
                                  d["mixed_target_rate"])),
        infer_producers=int(getattr(args, "mixed_infer_producers",
                                    d["mixed_infer_producers"])),
        checkin_producers=int(getattr(args, "mixed_checkin_producers",
                                      d["mixed_checkin_producers"])),
        queue_maxsize=int(getattr(args, "mixed_queue_maxsize",
                                  d["mixed_queue_maxsize"])),
        feature_dim=int(getattr(args, "mixed_feature_dim",
                                d["mixed_feature_dim"])),
        classes=int(getattr(args, "mixed_classes", d["mixed_classes"])),
        commit_interval_s=float(getattr(args, "mixed_commit_interval_s",
                                        d["mixed_commit_interval_s"])),
        min_swaps=int(getattr(args, "mixed_min_swaps",
                              d["mixed_min_swaps"])),
        seed=int(getattr(args, "mixed_seed", d["mixed_seed"])),
    )


def run_loadgen_from_args(args) -> LoadGenReport:
    """Map the flat ``loadgen_*`` config keys onto :func:`run_loadgen`."""
    d = LOADGEN_DEFAULTS
    return run_loadgen(
        duration_s=float(getattr(args, "loadgen_duration_s",
                                 d["loadgen_duration_s"])),
        target_rate=float(getattr(args, "loadgen_target_rate",
                                  d["loadgen_target_rate"])),
        producers=int(getattr(args, "loadgen_producers",
                              d["loadgen_producers"])),
        queue_maxsize=int(getattr(args, "loadgen_queue_maxsize",
                                  d["loadgen_queue_maxsize"])),
        tenants=int(getattr(args, "loadgen_tenants",
                            d["loadgen_tenants"])),
        churn=float(getattr(args, "loadgen_churn", d["loadgen_churn"])),
        seed=int(getattr(args, "loadgen_seed", d["loadgen_seed"])),
        payload_bytes=int(getattr(args, "loadgen_payload_bytes",
                                  d["loadgen_payload_bytes"])),
        population=int(getattr(args, "loadgen_population",
                               d["loadgen_population"])),
    )
