"""Tier-1: the graftcheck static-analysis suite (fedml_tpu/analysis/).

Three layers:

1. the package itself must be clean — zero non-baselined findings from
   every checker (the committed baseline in
   scripts/graftcheck_baseline.json grandfathers the known, deliberate
   exceptions, and deleting any of its lines must turn the run red);
2. every checker must actually FIRE on its bad fixture and stay silent
   on its clean twin (tests/fixtures/graftcheck/);
3. the shared machinery — suppression comments, baseline round-trip,
   CLI entry point — must keep its contract.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from fedml_tpu.analysis import core as gc
from fedml_tpu.analysis.collective_deadlock import CollectiveDeadlockChecker
from fedml_tpu.analysis.config_drift import ConfigDriftChecker
from fedml_tpu.analysis.determinism import DeterminismChecker
from fedml_tpu.analysis.donation import DonationSafetyChecker
from fedml_tpu.analysis.host_sync import HostSyncChecker
from fedml_tpu.analysis.jit_purity import JitPurityChecker
from fedml_tpu.analysis.lock_order import LockOrderChecker
from fedml_tpu.analysis.no_print import NoPrintChecker
from fedml_tpu.analysis.resource_leak import ResourceLeakChecker
from fedml_tpu.analysis.retrace_hazard import RetraceHazardChecker
from fedml_tpu.analysis.sharding_consistency import ShardingConsistencyChecker
from fedml_tpu.analysis.thread_hazard import ThreadHazardChecker
from fedml_tpu.analysis.wire_protocol import WireProtocolChecker

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "fixtures", "graftcheck")


def _run_on_fixture(checker_cls, filename, relpath=None):
    """One checker over one fixture file; ``relpath`` lets a fixture
    masquerade as an in-scope module for scope-restricted checkers."""
    path = os.path.join(FIXTURES, filename)
    ctx = gc.Context(repo_root=FIXTURES, package_dir=path)
    mod = gc.load_module(path, FIXTURES)
    if relpath is not None:
        mod.relpath = relpath
    checker = checker_cls(ctx)
    findings = []
    if checker.interested(mod.relpath):
        findings.extend(checker.visit_module(mod))
    findings.extend(checker.finalize())
    return findings


# ------------------------------------------------------- package is clean

def test_package_has_no_new_findings():
    rc = gc.main([])
    assert rc == 0, "graftcheck found non-baselined violations in fedml_tpu/"


def test_analyze_runs_fast_enough():
    # the <60s CPU budget for the full ten-checker run; generous margin so
    # CI noise never flakes this
    import time

    t0 = time.perf_counter()
    gc.main([])
    assert time.perf_counter() - t0 < 60.0


def test_deleting_a_baseline_line_fails_the_run(tmp_path):
    baseline_path = gc.default_baseline_path(REPO_ROOT)
    baseline = gc.load_baseline(baseline_path)
    assert baseline, "committed baseline should grandfather known findings"
    pruned = tmp_path / "baseline.json"
    pruned.write_text(json.dumps(baseline[1:]))
    rc = gc.main(["--baseline", str(pruned)])
    assert rc == 1, "a de-baselined known finding must turn the run red"


# ------------------------------------------------------------- jit-purity

def test_jit_purity_fires_on_bad_fixture():
    findings = _run_on_fixture(JitPurityChecker, "jit_purity_bad.py")
    msgs = "\n".join(f.message for f in findings)
    assert "time.time" in msgs          # direct impure call in jit body
    assert "print" in msgs              # host I/O in jit body
    assert "random.random" in msgs      # unkeyed python RNG in jit body
    assert "np.random" in msgs          # reached through the call graph
    assert "time.monotonic" in msgs     # inside a lax.scan body
    assert all(f.checker == "jit-purity" for f in findings)


def test_jit_purity_silent_on_clean_fixture():
    assert _run_on_fixture(JitPurityChecker, "jit_purity_clean.py") == []


def test_jit_purity_fires_inside_pallas_kernel():
    # a pallas_call kernel body (handed over via functools.partial) is a
    # traced root exactly like a jit body
    findings = _run_on_fixture(JitPurityChecker, "agg_pallas_bad.py")
    keys = {f.key for f in findings}
    assert "_agg_kernel:print" in keys
    assert "_agg_kernel:time.time" in keys
    assert "_agg_kernel:np.random.rand" in keys
    assert "_agg_kernel:.item" in keys
    assert all(f.checker == "jit-purity" for f in findings)


def test_jit_purity_silent_on_clean_pallas_fixture():
    assert _run_on_fixture(JitPurityChecker, "agg_pallas_clean.py") == []


# ----------------------------------------------------------- determinism

def test_determinism_fires_on_bad_fixture():
    findings = _run_on_fixture(DeterminismChecker, "determinism_bad.py")
    keys = {f.key for f in findings}
    assert "make_rng:unseeded:default_rng" in keys
    assert "make_py_rng:unseeded:Random" in keys
    assert "time_seeded:time-seed:default_rng" in keys
    assert "reseed_global:global-seed" in keys
    assert "cohort_order:set-order" in keys
    assert "spec_leaf_order:set-order" in keys
    assert "quantize_without_seed:stochastic-unseeded:stochastic_quantize" in keys
    assert "quantize_none_seed:stochastic-unseeded:stochastic_quantize" in keys
    assert "key_time_seed:time-seed:stochastic_key" in keys
    assert "roundtrip_without_seed:stochastic-unseeded:build_stacked_roundtrip" \
        in keys


def test_determinism_silent_on_clean_fixture():
    assert _run_on_fixture(DeterminismChecker, "determinism_clean.py") == []


# ------------------------------------------------------------ lock-order

_IN_SCOPE = "fedml_tpu/comm/_graftcheck_fixture.py"


def test_lock_order_fires_on_bad_fixture():
    findings = _run_on_fixture(
        LockOrderChecker, "lock_order_bad.py", relpath=_IN_SCOPE)
    msgs = "\n".join(f.message for f in findings)
    assert "re-acquired" in msgs                       # self-deadlock
    assert "lock acquisition cycle" in msgs            # AB/BA cycle
    assert ".sendall()" in msgs                        # blocking under lock
    assert "time.sleep" in msgs


def test_lock_order_silent_on_clean_fixture():
    findings = _run_on_fixture(
        LockOrderChecker, "lock_order_clean.py", relpath=_IN_SCOPE)
    assert findings == []


def test_lock_order_ignores_out_of_scope_files():
    # same bad source, but outside comm/cross_silo/telemetry scope
    findings = _run_on_fixture(LockOrderChecker, "lock_order_bad.py")
    assert findings == []


# ---------------------------------------------------------- config-drift

def test_config_drift_fires_on_fixture_repo():
    repo = os.path.join(FIXTURES, "config_drift_repo")
    findings = gc.run_checkers(
        [ConfigDriftChecker], os.path.join(repo, "pkg"), repo)
    keys = {f.key for f in findings}
    assert "conflicting-default:retry_count" in keys   # 0 vs 3
    assert "doc-only:ghost_key" in keys                # documented, unread
    assert "undocumented:batch_size" in keys           # read, undocumented
    # None probes and fallback-chain inner defaults never conflict
    assert "conflicting-default:learning_rate" not in keys
    assert "conflicting-default:retry_window" not in keys
    # phase-name drift: emitted-but-undocumented fires, documented is clean
    assert "phase-undocumented:mystery_phase" in keys
    assert "phase-undocumented:warp" not in keys
    # a name the body sets on the handle is a phase too; one set on anything
    # else, or not a constant, is not
    assert "phase-undocumented:renamed_mystery" in keys
    assert not {"phase-undocumented:drift", "phase-undocumented:not_a_phase",
                "phase-undocumented:dynamic"} & keys


# -------------------------------------------------------------- no-print

def test_no_print_fires_on_bad_fixture():
    findings = _run_on_fixture(NoPrintChecker, "no_print_bad.py")
    assert len(findings) == 1
    assert findings[0].checker == "no-print"


def test_no_print_silent_on_clean_fixture():
    # logging calls and print-as-value (log_fn=print) stay legal
    assert _run_on_fixture(NoPrintChecker, "no_print_clean.py") == []


def test_no_print_respects_allowlist():
    checker = NoPrintChecker(gc.Context(repo_root=REPO_ROOT,
                                        package_dir=REPO_ROOT))
    assert not checker.interested("fedml_tpu/cli/main.py")
    assert checker.interested("fedml_tpu/core/telemetry.py")


# ------------------------------------------------------- donation-safety

def test_donation_safety_fires_on_bad_fixture():
    findings = _run_on_fixture(DonationSafetyChecker, "donation_bad.py")
    keys = {f.key for f in findings}
    # direct self._step = jax.jit(..., donate_argnums=...) binding
    assert "Trainer.step_and_log:use-after-donate:self.params:self._step" in keys
    # builder hop: self._round = self._build_round_step()
    assert "Trainer.advance:use-after-donate:state:self._round" in keys
    # @partial(jax.jit, donate_argnums=...) decorated def, called by name
    assert "drive:use-after-donate:weights:apply_update" in keys
    # inline jax.jit(f, donate_argnums=...)(x) call
    assert "inline:use-after-donate:x:jax.jit" in keys
    assert all(f.checker == "donation-safety" for f in findings)


def test_donation_safety_silent_on_clean_fixture():
    assert _run_on_fixture(DonationSafetyChecker, "donation_clean.py") == []


# -------------------------------------------------- sharding-consistency

def test_sharding_consistency_fires_on_bad_fixture():
    findings = _run_on_fixture(
        ShardingConsistencyChecker, "sharding_consistency_bad.py")
    keys = {f.key for f in findings}
    assert "unknown-axis:clients" in keys          # typo of "client"
    assert "unknown-axis:modle" in keys            # typo inside a tuple spec
    assert "tree-literal-spec" in keys             # hand-rolled spec pytree
    by_key = {f.key: f for f in findings}
    assert by_key["unknown-axis:clients"].severity == "error"
    assert by_key["tree-literal-spec"].severity == "warning"


def test_sharding_consistency_silent_on_clean_fixture():
    # ad-hoc Mesh axis names and the canonical vocabulary are both legal
    assert _run_on_fixture(
        ShardingConsistencyChecker, "sharding_consistency_clean.py") == []


# -------------------------------------------------------------- host-sync

_FED_SIM = "fedml_tpu/simulation/fed_sim.py"


def test_host_sync_fires_on_bad_fixture():
    findings = _run_on_fixture(
        HostSyncChecker, "host_sync_bad.py", relpath=_FED_SIM)
    keys = {f.key for f in findings}
    assert "FedSimulator.run:block_until_ready" in keys
    assert "FedSimulator.run:float()" in keys            # scalar readback
    assert "FedSimulator._round:np.asarray:metrics" in keys
    assert "FedSimulator._round:item:metrics" in keys
    assert "FedSimulator._round:device_get" in keys


def test_host_sync_silent_on_clean_fixture():
    # cold planes (eval/build_*), placement-wrapped asarray, and host
    # containers never fire
    assert _run_on_fixture(
        HostSyncChecker, "host_sync_clean.py", relpath=_FED_SIM) == []


def test_host_sync_ignores_out_of_scope_files():
    findings = _run_on_fixture(HostSyncChecker, "host_sync_bad.py")
    assert findings == []


_PALLAS_MOD = "fedml_tpu/ops/pallas/agg_fixture.py"


def test_host_sync_covers_pallas_op_modules():
    # every top-level def in an ops/pallas module is a hot entry point
    findings = _run_on_fixture(
        HostSyncChecker, "agg_pallas_bad.py", relpath=_PALLAS_MOD)
    keys = {f.key for f in findings}
    assert "fused_agg:block_until_ready" in keys
    assert "fused_agg:np.asarray:out" in keys
    assert "_agg_kernel:item:expr" in keys


def test_host_sync_silent_on_clean_pallas_fixture():
    assert _run_on_fixture(
        HostSyncChecker, "agg_pallas_clean.py", relpath=_PALLAS_MOD) == []


def test_host_sync_pallas_fixture_out_of_scope_by_default():
    assert _run_on_fixture(HostSyncChecker, "agg_pallas_bad.py") == []


def test_host_sync_roots_scanned_round_body():
    # callbacks handed to lax.scan/fori_loop/while_loop are hot even when
    # defined inside a cold _build_* factory (the compiled multi-round
    # dispatch builds its round body exactly that way)
    findings = _run_on_fixture(
        HostSyncChecker, "host_sync_scan_bad.py", relpath=_FED_SIM)
    keys = {f.key for f in findings}
    assert "FedSimulator._build_scan_step.scan_round:np.asarray:out" in keys
    assert "FedSimulator._build_scan_step.scan_round:block_until_ready" in keys
    # call edges OUT of the scanned body are followed
    assert "FedSimulator._round_math:item:loss" in keys
    # fori/while callbacks root the same way
    assert "_build_loops.body_fun:device_get" in keys
    assert "_build_loops.cond_fun:float()" in keys


def test_host_sync_scan_body_why_names_the_hof():
    findings = _run_on_fixture(
        HostSyncChecker, "host_sync_scan_bad.py", relpath=_FED_SIM)
    body = [f for f in findings
            if f.key.startswith("FedSimulator._build_scan_step.scan_round")]
    assert body and all("compiled-region callback" in f.message for f in body)


def test_host_sync_silent_on_clean_scan_fixture():
    # a device-resident scanned body plus host staging in its cold factory
    assert _run_on_fixture(
        HostSyncChecker, "host_sync_scan_clean.py", relpath=_FED_SIM) == []


# ----------------------------------------------------- collective-deadlock

def test_collective_deadlock_fires_on_bad_fixture():
    findings = _run_on_fixture(
        CollectiveDeadlockChecker, "collective_deadlock_bad.py")
    keys = {f.key for f in findings}
    assert "sync_stats:guarded:jax.lax.psum" in keys          # process_index
    assert "rank_guarded:guarded:lax.all_gather" in keys      # *rank* name
    assert "ternary:guarded:lax.pmean" in keys                # IfExp guard
    assert "TenantWorker.maybe_broadcast:guarded:broadcast_one_to_all" in keys


def test_collective_deadlock_silent_on_clean_fixture():
    # uniform guards (config flags, process_count), divergent branches
    # without collectives, and nested defs all stay legal
    assert _run_on_fixture(
        CollectiveDeadlockChecker, "collective_deadlock_clean.py") == []


# ----------------------------------------------------------- thread-hazard

def test_thread_hazard_fires_on_bad_fixture():
    findings = _run_on_fixture(
        ThreadHazardChecker, "thread_hazard_bad.py", relpath=_IN_SCOPE)
    keys = {f.key for f in findings}
    assert "hazard:Wire.status" in keys        # unlocked on both sides
    assert "hazard:Wire._pending" in keys      # reader locks, writer doesn't
    assert "hazard:Pump.result" in keys        # executor.submit thread root


def test_thread_hazard_silent_on_clean_fixture():
    # common lock, entry-lock propagation (self-call and nested plain-name
    # call), const flag flips, and Queue attrs are all safe idioms
    findings = _run_on_fixture(
        ThreadHazardChecker, "thread_hazard_clean.py", relpath=_IN_SCOPE)
    assert findings == []


def test_thread_hazard_ignores_out_of_scope_files():
    findings = _run_on_fixture(ThreadHazardChecker, "thread_hazard_bad.py")
    assert findings == []


# ------------------------------------------- async engine (thread + replay)

_ASYNC_ENGINE = "fedml_tpu/simulation/async_engine.py"


def test_async_engine_scope_fires_on_bad_fixture():
    # the buffered-async module is in thread-hazard scope: an ingest
    # thread folding into the commit buffer without the committer's lock
    # must fire, and so must an unseeded delay-plan RNG (determinism —
    # a replayed straggler schedule would diverge)
    hazards = _run_on_fixture(
        ThreadHazardChecker, "async_engine_bad.py", relpath=_ASYNC_ENGINE)
    keys = {f.key for f in hazards}
    assert "hazard:BadAsyncServer._buffer" in keys
    assert "hazard:BadAsyncServer._version" in keys
    det = _run_on_fixture(
        DeterminismChecker, "async_engine_bad.py", relpath=_ASYNC_ENGINE)
    assert any("default_rng" in f.message for f in det)


def test_async_engine_scope_silent_on_clean_fixture():
    # lock-protected fold/commit + seed-derived RNG stream: both checkers
    # stay quiet, so the real module's discipline is the enforced shape
    assert _run_on_fixture(
        ThreadHazardChecker, "async_engine_clean.py",
        relpath=_ASYNC_ENGINE) == []
    assert _run_on_fixture(
        DeterminismChecker, "async_engine_clean.py",
        relpath=_ASYNC_ENGINE) == []


# ------------------------------------- tiered federation (thread + locks)

_FEDERATION = "fedml_tpu/simulation/federation.py"
_HIERARCHICAL = "fedml_tpu/simulation/hierarchical.py"


def test_federation_scope_fires_on_bad_fixture():
    # the tiered-federation modules are in both checkers' scope: a
    # heartbeat thread reading the round counter the receive handlers
    # write unguarded must fire thread-hazard, and opposite lease/ledger
    # lock nesting on the dispatch vs failover paths must fire lock-order
    hazards = _run_on_fixture(
        ThreadHazardChecker, "federation_bad.py", relpath=_FEDERATION)
    assert "hazard:BadLeafWorker._round" in {f.key for f in hazards}
    locks = _run_on_fixture(
        LockOrderChecker, "federation_bad.py", relpath=_FEDERATION)
    msgs = "\n".join(f.message for f in locks)
    assert "lock acquisition cycle" in msgs
    assert "time.sleep" in msgs


def test_federation_scope_silent_on_clean_fixture():
    # lock-guarded round accessors + a single lease-before-ledger order
    # (sleep outside the critical section): both checkers stay quiet, so
    # the real modules' discipline is the enforced shape
    for relpath in (_FEDERATION, _HIERARCHICAL):
        assert _run_on_fixture(
            ThreadHazardChecker, "federation_clean.py", relpath=relpath) == []
        assert _run_on_fixture(
            LockOrderChecker, "federation_clean.py", relpath=relpath) == []


def test_federation_fixture_out_of_scope_by_default():
    assert _run_on_fixture(ThreadHazardChecker, "federation_bad.py") == []
    assert _run_on_fixture(LockOrderChecker, "federation_bad.py") == []


# --------------------------------------- serving plane (thread + locks)

_SERVING = "fedml_tpu/serving/_graftcheck_fixture.py"


def test_serving_scope_fires_on_bad_fixture():
    # the serving package is in both checkers' scope: a serve-loop
    # thread swapping the active pointer / served-counts the main
    # thread reads unguarded must fire thread-hazard, and a promote
    # that publishes under the store locks (plus AB/BA nesting with
    # the stats path) must fire lock-order
    hazards = _run_on_fixture(
        ThreadHazardChecker, "serving_bad.py", relpath=_SERVING)
    keys = {f.key for f in hazards}
    assert "hazard:BadServer.active" in keys
    assert "hazard:BadServer._served" in keys
    locks = _run_on_fixture(
        LockOrderChecker, "serving_bad.py", relpath=_SERVING)
    msgs = "\n".join(f.message for f in locks)
    assert ".publish()" in msgs
    assert "lock acquisition cycle" in msgs
    assert "time.sleep" in msgs


def test_serving_scope_silent_on_clean_fixture():
    # one short lock around the RCU swap, telemetry after release, the
    # serve thread taking the same lock as readers, Event run flag:
    # both checkers stay quiet, so the real package's discipline is
    # the enforced shape
    assert _run_on_fixture(
        ThreadHazardChecker, "serving_clean.py", relpath=_SERVING) == []
    assert _run_on_fixture(
        LockOrderChecker, "serving_clean.py", relpath=_SERVING) == []


def test_serving_fixture_out_of_scope_by_default():
    assert _run_on_fixture(ThreadHazardChecker, "serving_bad.py") == []
    assert _run_on_fixture(LockOrderChecker, "serving_bad.py") == []


# ------------------------------------ cross-device plane (thread + locks)

_CROSS_DEVICE = "fedml_tpu/cross_device/_graftcheck_fixture.py"


def test_cross_device_scope_fires_on_bad_fixture():
    # the cross-device package is in both checkers' scope: a check-in
    # gateway doing a blocking send under the admission+fleet locks
    # (with the eviction path nesting them in the opposite order) must
    # fire lock-order, and a heartbeat thread stamping last_checkin
    # without the readers' lock must fire thread-hazard
    locks = _run_on_fixture(
        LockOrderChecker, "device_registry_bad.py", relpath=_CROSS_DEVICE)
    msgs = "\n".join(f.message for f in locks)
    assert ".sendall()" in msgs
    assert "time.sleep" in msgs
    assert "lock acquisition cycle" in msgs
    hazards = _run_on_fixture(
        ThreadHazardChecker, "device_registry_bad.py", relpath=_CROSS_DEVICE)
    assert "hazard:Gateway.last_checkin" in {f.key for f in hazards}


def test_cross_device_scope_silent_on_clean_fixture():
    # one nesting order, send/sleep after release, heartbeat writes and
    # staleness reads sharing the fleet lock: both checkers stay quiet,
    # so the real package's discipline is the enforced shape
    assert _run_on_fixture(
        LockOrderChecker, "device_registry_clean.py",
        relpath=_CROSS_DEVICE) == []
    assert _run_on_fixture(
        ThreadHazardChecker, "device_registry_clean.py",
        relpath=_CROSS_DEVICE) == []


def test_cross_device_fixture_out_of_scope_by_default():
    assert _run_on_fixture(
        ThreadHazardChecker, "device_registry_bad.py") == []
    assert _run_on_fixture(LockOrderChecker, "device_registry_bad.py") == []


# ----------------------------------------------------------- suppression

def _no_print_over(tmp_path, source):
    path = tmp_path / "mod.py"
    path.write_text(source)
    return gc.run_checkers([NoPrintChecker], str(path), str(tmp_path))


def test_inline_suppression_drops_finding(tmp_path):
    src = 'print("x")  # graftcheck: disable=no-print\n'
    assert _no_print_over(tmp_path, src) == []


def test_standalone_comment_suppresses_next_line(tmp_path):
    src = ('# tooling speaks over stdout; graftcheck: disable=no-print\n'
           'print("x")\n')
    assert _no_print_over(tmp_path, src) == []


def test_disable_all_suppresses_every_checker(tmp_path):
    src = 'print("x")  # graftcheck: disable=all\n'
    assert _no_print_over(tmp_path, src) == []


def test_unsuppressed_line_still_fires(tmp_path):
    src = ('print("a")  # graftcheck: disable=no-print\n'
           'print("b")\n')
    findings = _no_print_over(tmp_path, src)
    assert [f.line for f in findings] == [2]


def test_suppression_for_other_checker_does_not_apply(tmp_path):
    src = 'print("x")  # graftcheck: disable=determinism\n'
    findings = _no_print_over(tmp_path, src)
    assert len(findings) == 1


def test_suppression_applies_to_new_checker_ids(tmp_path):
    src = ("import jax\n"
           "def f(x):\n"
           "    if jax.process_index() == 0:\n"
           "        # single-host warmup subtree, never multi-process\n"
           "        return jax.lax.psum(x, 'data')"
           "  # graftcheck: disable=collective-deadlock\n"
           "    return x\n")
    path = tmp_path / "mod.py"
    path.write_text(src)
    findings = gc.run_checkers(
        [CollectiveDeadlockChecker], str(path), str(tmp_path))
    assert findings == []


def test_standalone_suppression_on_new_checker_ids(tmp_path):
    src = ("import jax\n"
           "step = jax.jit(lambda p, b: p, donate_argnums=(0,))\n"
           "def f(p, b):\n"
           "    out = step(p, b)\n"
           "    # donation is a no-op on the CPU-only debug path here\n"
           "    # graftcheck: disable=donation-safety\n"
           "    return out, p\n")
    path = tmp_path / "mod.py"
    path.write_text(src)
    findings = gc.run_checkers(
        [DonationSafetyChecker], str(path), str(tmp_path))
    assert findings == []
    # and without the directive the same source fires
    path.write_text(src.replace("    # graftcheck: disable=donation-safety\n",
                                ""))
    findings = gc.run_checkers(
        [DonationSafetyChecker], str(path), str(tmp_path))
    assert [f.key for f in findings] == ["f:use-after-donate:p:step"]


# -------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    findings = _run_on_fixture(DeterminismChecker, "determinism_bad.py")
    assert findings
    path = tmp_path / "baseline.json"
    gc.write_baseline(findings, str(path))
    baseline = gc.load_baseline(str(path))
    new, old, stale = gc.apply_baseline(findings, baseline)
    assert new == [] and stale == []
    assert {f.fingerprint for f in old} == set(baseline)

    # dropping one entry resurfaces exactly that finding as new
    new, _old, _stale = gc.apply_baseline(findings, baseline[1:])
    assert [f.fingerprint for f in new] == [baseline[0]]

    # a fingerprint matching nothing is reported stale, never fatal
    _new, _old, stale = gc.apply_baseline(findings, baseline + ["bogus:x:y"])
    assert stale == ["bogus:x:y"]


def test_baseline_file_is_one_fingerprint_per_line(tmp_path):
    findings = _run_on_fixture(NoPrintChecker, "no_print_bad.py")
    path = tmp_path / "baseline.json"
    gc.write_baseline(findings, str(path))
    lines = path.read_text().splitlines()
    # [ ... one quoted fingerprint per interior line ... ]
    assert lines[0] == "[" and lines[-1] == "]"
    assert len(lines) == 2 + len({f.fingerprint for f in findings})


def test_fingerprints_are_line_number_free():
    findings = _run_on_fixture(NoPrintChecker, "no_print_bad.py")
    for f in findings:
        assert str(f.line) not in f.fingerprint.split(":")[-1] or f.line > 99


# --------------------------------------------------------------- frontend

def test_cli_analyze_exits_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "fedml_tpu.cli", "analyze"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftcheck:" in proc.stdout


def test_json_output_shape(tmp_path, capsys):
    bad = os.path.join(FIXTURES, "no_print_bad.py")
    rc = gc.main(["--json", "--no-baseline", "--checker", "no-print",
                  "--root", bad])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["checkers"] == ["no-print"]
    assert len(out["new"]) == 1
    finding = out["new"][0]
    assert set(finding) == {"checker", "path", "line", "severity",
                            "message", "fingerprint"}


def test_sarif_output_shape(capsys):
    bad = os.path.join(FIXTURES, "no_print_bad.py")
    rc = gc.main(["--format", "sarif", "--no-baseline",
                  "--checker", "no-print", "--root", bad])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graftcheck"
    # one rule per registered checker, findings or not
    assert {r["id"] for r in driver["rules"]} == set(gc.checker_registry())
    result = run["results"][0]
    assert result["ruleId"] == "no-print"
    assert result["level"] in ("error", "warning")
    assert result["message"]["text"]
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("no_print_bad.py")
    assert loc["region"]["startLine"] >= 1
    # baseline identity rides along for CI dedup across pushes
    assert result["partialFingerprints"]["graftcheck/v1"].startswith("no-print:")


def test_sarif_clean_run_exits_zero(capsys):
    clean = os.path.join(FIXTURES, "no_print_clean.py")
    rc = gc.main(["--format", "sarif", "--no-baseline",
                  "--checker", "no-print", "--root", clean])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["runs"][0]["results"] == []


def test_changed_files_returns_existing_py_paths():
    changed = gc.changed_files(REPO_ROOT, "HEAD")
    assert isinstance(changed, list)
    for path in changed:
        assert path.endswith(".py") and os.path.exists(path)


def test_changed_only_run_completes(capsys):
    # whatever the working tree looks like, the dev loop must terminate
    # cleanly: either "nothing changed" or a normal (possibly red) run
    rc = gc.main(["--changed-only", "HEAD", "--no-baseline",
                  "--checker", "no-print"])
    out = capsys.readouterr().out
    assert rc in (0, 1)
    assert "graftcheck:" in out


def test_changed_only_skips_whole_package_checkers(capsys):
    # config-drift over a partial scan would report every unchanged key as
    # doc-only drift — it must be excluded from the dev loop
    rc = gc.main(["--changed-only", "HEAD", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc in (0, 1)
    if "no .py files changed" not in out:
        assert "skipping whole-package checker(s)" in out
        assert "config-drift" not in out.split("[checkers:")[-1]


def test_checker_registry_is_complete():
    assert sorted(gc.checker_registry()) == [
        "collective-deadlock", "config-drift", "determinism",
        "donation-safety", "host-sync", "jit-purity", "lock-order",
        "no-print", "resource-leak", "retrace-hazard",
        "sharding-consistency", "thread-hazard", "wire-protocol"]


# -------------------------------------------------------- retrace-hazard

def test_retrace_hazard_fires_on_bad_fixture():
    findings = _run_on_fixture(RetraceHazardChecker, "retrace_hazard_bad.py")
    keys = {f.key for f in findings}
    assert "jit_in_loop:jit-in-loop:step" in keys
    assert "per_call_jit:per-call-jit:step" in keys
    assert "loop_varying_static:static-loop-varying:compiled:1" in keys
    assert "unhashable_static:unhashable-static:compiled:1" in keys
    assert "shape_flow:shape-flow:plain" in keys
    # a retrace inside a lax.scan block body recompiles the whole fused
    # dispatch — the PR 15 scope gets its own key
    assert "scan_block.body:scan-body-jit:step" in keys
    # bound-but-never-invoked wrapper is a warning, not an error
    discarded = [f for f in findings
                 if f.key == "discarded_jit:per-call-jit:step"]
    assert discarded and discarded[0].severity == "warning"


def test_retrace_hazard_silent_on_clean_fixture():
    assert _run_on_fixture(RetraceHazardChecker,
                           "retrace_hazard_clean.py") == []


# --------------------------------------------------------- wire-protocol

def _run_on_fixture_set(checker_cls, filenames):
    """Whole-package checker over several fixture files sharing one
    project graph (the shape run_checkers provides)."""
    from fedml_tpu.analysis.project import build_graph

    mods = [gc.load_module(os.path.join(FIXTURES, fn), FIXTURES)
            for fn in filenames]
    ctx = gc.Context(repo_root=FIXTURES, package_dir=FIXTURES)
    ctx.graph = build_graph(mods)
    checker = checker_cls(ctx)
    findings = []
    for mod in mods:
        if checker.interested(mod.relpath):
            findings.extend(checker.visit_module(mod))
    findings.extend(checker.finalize())
    return findings


def test_wire_protocol_fires_on_bad_fixture():
    findings = _run_on_fixture(WireProtocolChecker, "wire_protocol_bad.py")
    keys = {f.key for f in findings}
    assert "unhandled-send:MSG_TYPE_ORPHANED" in keys
    assert "unstamped-key:MSG_TYPE_UPLOAD:'model_version'" in keys
    assert any(k.startswith("raw-literal:") and "'num_samples'" in k
               for k in keys)


def test_wire_protocol_silent_on_clean_fixture():
    assert _run_on_fixture(WireProtocolChecker,
                           "wire_protocol_clean.py") == []


def test_wire_protocol_flags_duplicated_constant_across_modules():
    # both fixtures define MSG_TYPE_SHARED = "shared_event"; the checker
    # flags every copy except the sorted-first canonical one
    findings = _run_on_fixture_set(
        WireProtocolChecker,
        ["wire_protocol_bad.py", "wire_protocol_clean.py"])
    dups = [f for f in findings if f.key == "dup-constant:MSG_TYPE_SHARED"]
    assert len(dups) == 1
    assert dups[0].path.endswith("wire_protocol_clean.py")
    assert dups[0].severity == "warning"


# --------------------------------------------------------- resource-leak

def test_resource_leak_fires_on_bad_fixture():
    findings = _run_on_fixture(ResourceLeakChecker, "resource_leak_bad.py")
    keys = {f.key for f in findings}
    assert "thread_never_joined:thread-no-join:t" in keys
    assert "inline_thread:thread-no-join:<inline>" in keys
    assert "unclosed_file:unclosed:file:f" in keys
    assert "inline_open:unclosed:file:<inline>" in keys
    assert "unclosed_socket:unclosed:socket:s" in keys
    assert "unclosed_channel:unclosed:grpc-channel:ch" in keys
    assert "spill-no-reclaim" in keys


def test_resource_leak_silent_on_clean_fixture():
    assert _run_on_fixture(ResourceLeakChecker,
                           "resource_leak_clean.py") == []


# ------------------------------------------------------ incremental cache

def test_cache_cold_and_warm_runs_are_byte_identical(tmp_path, capsys):
    cache = str(tmp_path / "cache.json")
    rc_cold = gc.main(["--json", "--cache", cache])
    cold = capsys.readouterr().out
    assert os.path.exists(cache)
    rc_warm = gc.main(["--json", "--cache", cache])
    warm = capsys.readouterr().out
    assert rc_cold == rc_warm
    assert cold == warm, "warm cache run must reproduce the cold run exactly"


def test_cache_warm_run_is_fast(tmp_path, capsys):
    import time

    cache = str(tmp_path / "cache.json")
    gc.main(["--json", "--cache", cache])  # cold: populate
    capsys.readouterr()
    t0 = time.perf_counter()
    gc.main(["--json", "--cache", cache])
    assert time.perf_counter() - t0 < 10.0, "warm path must skip parsing"
    capsys.readouterr()


def test_cache_invalidates_on_file_change(tmp_path, capsys):
    # a package copy with one bad file: fixing the file must flip the
    # cached verdict (content-hash invalidation, not mtime)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    bad = pkg / "mod.py"
    bad.write_text("def f():\n    print('hi')\n")
    from fedml_tpu.analysis.cache import run_checkers_cached

    cache = str(tmp_path / "cache.json")
    registry = gc.checker_registry()
    classes = [registry["no-print"]]
    first = run_checkers_cached(classes, str(pkg), str(tmp_path), cache)
    assert len(first) == 1 and first[0].checker == "no-print"
    bad.write_text("def f():\n    return 'hi'\n")
    second = run_checkers_cached(classes, str(pkg), str(tmp_path), cache)
    assert second == []


def test_stats_report_timing_and_hit_rate(tmp_path, capsys):
    cache = str(tmp_path / "cache.json")
    gc.main(["--stats", "--json", "--cache", cache])
    capsys.readouterr()
    gc.main(["--stats", "--json", "--cache", cache])
    err = capsys.readouterr().err
    assert "graftcheck stats:" in err
    assert "cache hit rate 100.0%" in err
    assert "jit-purity" in err


# ----------------------------------------------- changed-only improvements

def _git(repo, *args):
    subprocess.run(["git", *args], cwd=repo, check=True,
                   capture_output=True, text=True)


def test_changed_files_follows_renames(tmp_path):
    repo = str(tmp_path)
    _git(repo, "init", "-q")
    _git(repo, "config", "user.email", "t@t")
    _git(repo, "config", "user.name", "t")
    (tmp_path / "old_name.py").write_text("X = 1\n")
    _git(repo, "add", "old_name.py")
    _git(repo, "commit", "-qm", "seed")
    _git(repo, "mv", "old_name.py", "new_name.py")
    changed = gc.changed_files(repo, "HEAD")
    # the rename must surface the NEW path, not the dead old one
    assert any(p.endswith("new_name.py") for p in changed)
    assert not any(p.endswith("old_name.py") for p in changed)


def test_changed_files_skips_deletions(tmp_path):
    repo = str(tmp_path)
    _git(repo, "init", "-q")
    _git(repo, "config", "user.email", "t@t")
    _git(repo, "config", "user.name", "t")
    (tmp_path / "doomed.py").write_text("X = 1\n")
    _git(repo, "add", "doomed.py")
    _git(repo, "commit", "-qm", "seed")
    _git(repo, "rm", "-q", "doomed.py")
    assert gc.changed_files(repo, "HEAD") == []


def test_expand_with_dependents_pulls_in_importers(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "base.py").write_text("def helper():\n    return 1\n")
    (pkg / "user.py").write_text(
        "from pkg.base import helper\n\ndef g():\n    return helper()\n")
    (pkg / "loner.py").write_text("def h():\n    return 2\n")
    expanded = gc.expand_with_dependents(
        [str(pkg / "base.py")], str(pkg), str(tmp_path))
    names = {os.path.basename(p) for p in expanded}
    # editing base invalidates its importer's findings, not the loner's
    assert names == {"base.py", "user.py"}
