"""Multi-tenant simulation driver: N federated jobs, one device mesh.

Runs several heterogeneous :class:`FedSimulator` jobs concurrently over the
same mesh under the :mod:`fedml_tpu.core.tenancy` control plane:

- each job is admitted against a :class:`~fedml_tpu.core.tenancy.JobRegistry`
  byte budget (typed verdict: admit / queue / reject) before it touches the
  device; queued jobs start automatically when a running job releases
  capacity;
- admitted jobs run in their own worker thread, but their *round steps* are
  interleaved one at a time by a
  :class:`~fedml_tpu.core.tenancy.DeficitRoundRobinScheduler` through the
  simulator's ``_round_gate`` hook — the mesh executes exactly one tenant's
  round at any moment, so per-tenant numerics are bit-identical to a solo
  run (every RNG stream is (seed, round)-indexed and no state is shared);
- each worker enters :func:`telemetry.tenant_scope`, so every metric a job
  emits (round phases, comm counters, faults) is tenant-labeled, and the
  time a job spends waiting for its turn is attributed as its own
  ``tenant_wait`` phase — the per-round phase breakdown still sums exactly
  to that job's ``round_time``;
- checkpoints are namespaced per tenant under ``checkpoint_root`` so one
  tenant's recovery state can never collide with another's.

Jobs are forced to ``prefetch=False``: round-exact phase attribution and a
round-granular gate both require the synchronous round loop.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..core import telemetry, trace_plane
from ..core.tenancy import (
    AdmissionVerdict,
    DeficitRoundRobinScheduler,
    JobRegistry,
    ResourceEnvelope,
)

# states a tenant worker moves through; the scheduler only ever grants a
# tenant sitting at its round gate ("ready")
_NEW, _READY, _GRANTED, _RUNNING, _DONE = (
    "new", "ready", "granted", "running", "done")


@dataclasses.dataclass
class TenantJob:
    """One federated job: a tenant name plus its ``fedml_tpu.init`` config.
    ``priority`` weights the fair scheduler (2.0 = twice the service)."""

    tenant: str
    config: Dict[str, Any]
    priority: float = 1.0


@dataclasses.dataclass
class TenantRunResult:
    tenant: str
    verdict: AdmissionVerdict
    history: List[dict] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    elapsed_s: float = 0.0
    rounds_expected: int = 0

    @property
    def ok(self) -> bool:
        return (self.error is None and self.verdict.admitted
                and len(self.history) >= self.rounds_expected)

    def summary(self) -> str:
        if not self.verdict.admitted:
            return self.verdict.summary()
        if self.error is not None:
            return f"tenant[{self.tenant}]: FAIL — {self.error}"
        last = self.history[-1] if self.history else {}
        loss = last.get("train_loss")
        return (f"tenant[{self.tenant}]: {'PASS' if self.ok else 'FAIL'} — "
                f"{len(self.history)}/{self.rounds_expected} rounds in "
                f"{self.elapsed_s:.1f}s"
                + (f", final train_loss={loss:.4f}"
                   if isinstance(loss, float) else ""))


class MultiTenantSimDriver:
    """Admit, schedule, and run a set of :class:`TenantJob` s to completion.

    ``capacity_bytes`` is the admission budget (the mesh's usable device
    memory at tier-1 scale); jobs whose envelope never fits are rejected,
    jobs that fit-but-not-now queue and start on a release. ``run()``
    returns ``{tenant: TenantRunResult}`` for every submitted job, verdicts
    included for the rejected ones.
    """

    def __init__(self, jobs: List[TenantJob], capacity_bytes: int = 2 << 30,
                 max_concurrent: int = 8, max_queue: int = 16,
                 quantum: float = 1.0, demote_factor: float = 0.5,
                 over_budget_factor: float = 2.0,
                 checkpoint_root: Optional[str] = None, log_fn=None):
        names = [j.tenant for j in jobs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tenant names in {names}")
        self.jobs = list(jobs)
        self.registry = JobRegistry(capacity_bytes,
                                    max_concurrent=max_concurrent,
                                    max_queue=max_queue)
        self.scheduler = DeficitRoundRobinScheduler(
            quantum=quantum, demote_factor=demote_factor,
            over_budget_factor=over_budget_factor)
        self.checkpoint_root = checkpoint_root
        self._log = log_fn
        self._cond = threading.Condition()
        self._state: Dict[str, str] = {}
        self._sims: Dict[str, tuple] = {}  # tenant -> (sim, apply_fn, env)
        self._threads: Dict[str, threading.Thread] = {}
        self._results: Dict[str, TenantRunResult] = {}
        # global seconds-per-declared-cost-unit estimate: converts measured
        # wall into the scheduler's cost units, so the over-budget detector
        # compares a tenant against the fleet-normal rate
        self._rate_num = 0.0
        self._rate_den = 0.0

    @classmethod
    def from_args(cls, args, jobs: List[TenantJob],
                  **kw) -> "MultiTenantSimDriver":
        """Build from the flat ``admission_*`` / ``tenant_*`` config keys."""
        return cls(
            jobs,
            capacity_bytes=int(getattr(args, "admission_capacity_bytes",
                                       2 << 30)),
            max_concurrent=int(getattr(args, "admission_max_jobs", 8)),
            max_queue=int(getattr(args, "admission_max_queue", 16)),
            quantum=float(getattr(args, "tenant_quantum", 1.0)),
            demote_factor=float(getattr(args, "tenant_demote_factor", 0.5)),
            over_budget_factor=float(
                getattr(args, "tenant_over_budget_factor", 2.0)),
            checkpoint_root=getattr(args, "tenant_checkpoint_root", None),
            **kw,
        )

    # ------------------------------------------------------------- build

    def _build(self, job: TenantJob):
        """Materialize one job: args -> simulator -> resource envelope."""
        import jax
        import numpy as np

        import fedml_tpu
        from . import build_simulator

        cfg = dict(job.config)
        # synchronous rounds: exact per-round phase sums + round-granular
        # gating both need the prefetch pipeline off
        cfg["prefetch"] = False
        if self.checkpoint_root is not None and "checkpoint_dir" not in cfg:
            cfg["checkpoint_dir"] = os.path.join(
                self.checkpoint_root, job.tenant)
        args = fedml_tpu.init(config=cfg)
        sim, apply_fn = build_simulator(args)
        model_bytes = int(sum(
            np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
                sim.params)))
        per_round = int(sim.cfg.client_num_per_round)
        env = ResourceEnvelope.from_workloads(
            job.tenant,
            workloads=[float(sim.num_local_batches)] * per_round,
            model_bytes=model_bytes,
            rounds=int(sim.cfg.comm_round),
            priority=float(job.priority),
        )
        return sim, apply_fn, env

    # ------------------------------------------------------------- worker

    def _worker(self, tenant: str) -> None:
        sim, apply_fn, _env = self._sims[tenant]
        result = self._results[tenant]
        t_run = time.perf_counter()

        def gate(round_idx: int) -> None:
            # the scheduler wait is its own phase, so the round's breakdown
            # still sums exactly to round_time
            with sim._phase("tenant_wait"), self._cond:
                self._state[tenant] = _READY
                self._cond.notify_all()
                while self._state[tenant] != _GRANTED:
                    self._cond.wait()
                self._state[tenant] = _RUNNING

        sim._round_gate = gate
        # contextvars do not inherit into threads: the tenant scope must be
        # entered HERE, inside the worker body
        with telemetry.tenant_scope(tenant):
            try:
                result.history = sim.run(apply_fn, log_fn=None)
            except Exception as exc:  # surfaced in the result, not swallowed
                result.error = repr(exc)
            finally:
                result.elapsed_s = time.perf_counter() - t_run
                with self._cond:
                    self._state[tenant] = _DONE
                    self._cond.notify_all()

    def _start(self, tenant: str) -> None:
        t = threading.Thread(target=self._worker, args=(tenant,),
                             daemon=True, name=f"tenant-{tenant}")
        self._threads[tenant] = t
        with self._cond:
            self._state[tenant] = _NEW
        t.start()

    # ------------------------------------------------------------- run

    def run(self) -> Dict[str, TenantRunResult]:
        # build + admit every job up front (building measures the envelope;
        # a rejected job's simulator is dropped before it ever runs a round)
        for job in self.jobs:
            sim, apply_fn, env = self._build(job)
            verdict = self.registry.admit(env)
            trace_plane.record_instant(
                "admission", attrs={"tenant": job.tenant,
                                    "decision": verdict.decision})
            # written before Thread.start(); start() is the happens-before
            # edge the worker reads through — graftcheck: disable=thread-hazard
            self._results[job.tenant] = TenantRunResult(
                tenant=job.tenant, verdict=verdict,
                rounds_expected=int(sim.cfg.comm_round))
            if self._log:
                self._log(verdict.summary())
            if verdict.rejected:
                continue
            # written before Thread.start(); start() is the happens-before
            # edge the worker reads through — graftcheck: disable=thread-hazard
            self._sims[job.tenant] = (sim, apply_fn, env)
            if verdict.admitted:
                self.scheduler.register(job.tenant, env.round_cost,
                                        priority=env.priority)
                self._start(job.tenant)

        # grant loop: one tenant's round step on the mesh at a time
        while True:
            with self._cond:
                while True:
                    ready = [t for t, s in self._state.items() if s == _READY]
                    live = [t for t, s in self._state.items()
                            if s not in (_DONE,)]
                    if ready or not live:
                        break
                    self._cond.wait()
                # snapshot under the cond — workers mutate _state under it;
                # the joins in _finish stay outside the critical section
                done = [t for t, s in self._state.items() if s == _DONE
                        and t in self._threads]
            for t in done:
                self._finish(t)
            if not ready:
                with self._cond:
                    still_live = any(self._state.get(t) != _DONE
                                     for t in self._threads)
                if not still_live:
                    break
                continue
            tenant = self.scheduler.next_tenant(ready)
            if tenant is None:
                continue
            t0 = time.perf_counter()
            with self._cond:
                if self._state.get(tenant) != _READY:
                    continue
                self._state[tenant] = _GRANTED
                self._cond.notify_all()
                while self._state[tenant] in (_GRANTED, _RUNNING):
                    self._cond.wait()
            measured_s = time.perf_counter() - t0
            env = self._sims[tenant][2]
            self._rate_num += measured_s
            self._rate_den += env.round_cost
            rate = self._rate_num / self._rate_den if self._rate_den else 0.0
            self.scheduler.charge(
                tenant, measured_s / rate if rate > 0 else env.round_cost)

        for t in list(self._threads):
            self._finish(t)
        return dict(self._results)

    def _finish(self, tenant: str) -> None:
        """Join a finished worker once, release its capacity, and start any
        queued jobs the release admitted."""
        thread = self._threads.pop(tenant, None)
        if thread is None:
            return
        thread.join()
        self.scheduler.unregister(tenant)
        for verdict in self.registry.release(tenant):
            promoted = verdict.tenant
            trace_plane.record_instant(
                "admission", attrs={"tenant": promoted,
                                    "decision": verdict.decision,
                                    "promoted_after": tenant})
            self._results[promoted].verdict = verdict
            if self._log:
                self._log(verdict.summary())
            _sim, _apply, env = self._sims[promoted]
            self.scheduler.register(promoted, env.round_cost,
                                    priority=env.priority)
            self._start(promoted)
