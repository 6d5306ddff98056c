"""``fedml_tpu`` command-line interface.

Parity: reference ``python/fedml/cli/cli.py:24`` (click group with
``version``, ``status``, ``logs``, ``build``, ``login``, ``logout``) plus a
``run`` command the reference spreads across example main.py files. The
MLOps-platform network calls are replaced by a local state directory
(``~/.fedml_tpu``): ``login`` records the account binding, ``status``/
``logs`` read local runner state — the agent daemon surface without the
hosted backend (which is gated in this zero-egress build).

Usage: ``python -m fedml_tpu.cli <command>``.
"""

from __future__ import annotations

import json
import os
import time
import zipfile

import click

STATE_DIR = os.path.expanduser(os.environ.get("FEDML_TPU_HOME", "~/.fedml_tpu"))


def _state_path(name: str) -> str:
    os.makedirs(STATE_DIR, exist_ok=True)
    return os.path.join(STATE_DIR, name)


@click.group()
def cli():
    """fedml_tpu: TPU-native federated learning."""


@cli.command("version", help="Display fedml_tpu version.")
def version():
    import fedml_tpu

    click.echo("fedml_tpu version: " + fedml_tpu.__version__)


@cli.command("login", help="Bind this device to an account id (local record).")
@click.argument("account_id")
@click.option("--role", default="client", type=click.Choice(["client", "server"]))
def login(account_id, role):
    with open(_state_path("session.json"), "w") as f:
        json.dump({"account_id": account_id, "role": role, "time": time.time()}, f)
    click.echo(f"bound account {account_id} as {role} (state: {STATE_DIR})")


@cli.command("logout", help="Clear the account binding.")
def logout():
    p = _state_path("session.json")
    if os.path.exists(p):
        os.remove(p)
    click.echo("logged out")


@cli.command("status", help="Display training status.")
def status():
    def _read(name):
        try:
            with open(os.path.join(STATE_DIR, name)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    edge_recs = [
        r for r in (
            _read(f) for f in sorted(os.listdir(STATE_DIR))
            if f.startswith("status_edge") and f.endswith(".json")
        ) if r
    ] if os.path.isdir(STATE_DIR) else []
    local = _read("status.json")
    # status.json without an edge_id came from the `run` command; with one
    # it duplicates a per-edge file (agents write both). Show each source
    # once so stale agent state never masks a live local run or vice versa.
    show_local = local is not None and "edge_id" not in local
    if not edge_recs and not show_local:
        click.echo("Client training status: IDLE")
        return
    if show_local:
        click.echo("Client training status: "
                   + local.get("status", "IDLE").upper())
    for r in edge_recs:
        click.echo(f"Edge {r.get('edge_id', '?')} training status: "
                   + r.get("status", "IDLE").upper())


@cli.command("logs", help="Display recent run logs.")
@click.option("--client", "-c", is_flag=True, help="Client logs.")
@click.option("--server", "-s", is_flag=True, help="Server logs.")
@click.option("--lines", "-n", default=30)
def logs(client, server, lines):
    log_dir = _state_path("logs")
    if not os.path.isdir(log_dir) or not os.listdir(log_dir):
        click.echo("no logs yet")
        return
    newest = max(
        (os.path.join(log_dir, f) for f in os.listdir(log_dir)), key=os.path.getmtime
    )
    with open(newest) as f:
        for line in f.readlines()[-lines:]:
            click.echo(line.rstrip())


@cli.command("build", help="Package entry script + config for distribution.")
@click.option("--type", "-t", "pkg_type", type=click.Choice(["client", "server"]), required=True)
@click.option("--source_folder", "-sf", required=True)
@click.option("--entry_point", "-ep", required=True)
@click.option("--config_folder", "-cf", required=True)
@click.option("--dest_folder", "-df", required=True)
def build(pkg_type, source_folder, entry_point, config_folder, dest_folder):
    """Reference ``fedml build`` (cli.py:351 ``build_mlops_package:434``):
    zips entry + source + config into a deployable package.

    ``--source_folder default`` packages the stock skeleton entries
    (cli/build_package — reference ``cli/build-package/mlops-core``); a
    real directory named ``default`` takes precedence over the sentinel."""
    if source_folder == "default" and not os.path.isdir(source_folder):
        from . import build_package as _bp

        source_folder = _bp.SKELETON_DIR
        entry_point = (_bp.SERVER_ENTRY if pkg_type == "server"
                       else _bp.CLIENT_ENTRY)
        click.echo(f"using stock skeleton source (entry {entry_point})")
    os.makedirs(dest_folder, exist_ok=True)
    out = os.path.join(dest_folder, f"fedml_tpu-{pkg_type}-package.zip")

    def _walk_clean(top):
        # no bytecode, sorted traversal: entry ORDER is deterministic
        # across hosts (readdir order varies). Full byte-reproducibility
        # would also need fixed zip mtimes + dropping built_at.
        for root, dirs, files in os.walk(top):
            dirs[:] = sorted(d for d in dirs if d != "__pycache__")
            for name in sorted(files):
                if not name.endswith((".pyc", ".pyo")):
                    yield os.path.join(root, name)

    with zipfile.ZipFile(out, "w", zipfile.ZIP_DEFLATED) as z:
        for full in _walk_clean(source_folder):
            z.write(full, os.path.join("source", os.path.relpath(full, source_folder)))
        for full in _walk_clean(config_folder):
            z.write(full, os.path.join("config", os.path.relpath(full, config_folder)))
        z.writestr(
            "package.json",
            json.dumps({"type": pkg_type, "entry_point": entry_point,
                        "built_at": time.time()}),
        )
    click.echo(f"package built: {out}")


@cli.command("agent", help="Run the edge agent daemon (serves MLOps jobs).")
@click.option("--edge_id", "-e", default=0, type=int)
@click.option("--broker_dir", "-b", default=None,
              help="FileSystemBroker root shared with the server runner.")
@click.option("--store_dir", "-s", default=None,
              help="FileSystemBlobStore root for package distribution.")
def agent(edge_id, broker_dir, store_dir):
    """Reference ``fedml login`` spawns this daemon (cli.py:152); here it is
    an explicit foreground command (daemonize with your supervisor)."""
    from ..comm.pubsub import FileSystemBroker
    from ..comm.store import FileSystemBlobStore
    from .runner import FedMLEdgeRunner

    broker = FileSystemBroker(root=broker_dir)
    store = FileSystemBlobStore(root=store_dir)
    runner = FedMLEdgeRunner(edge_id, broker, store=store, home_dir=STATE_DIR)
    runner.start()
    click.echo(f"edge agent {edge_id} serving jobs (broker: {broker.root})")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        runner.stop()
        broker.close()


@cli.command("dispatch", help="Fan a built package out to edge agents and wait.")
@click.option("--package", "-p", required=True, type=click.Path(exists=True))
@click.option("--edge_id", "-e", "edge_ids", multiple=True, type=int, required=True)
@click.option("--run_id", "-r", default="run0")
@click.option("--broker_dir", "-b", default=None)
@click.option("--store_dir", "-s", default=None)
@click.option("--timeout", "-t", default=600.0)
def dispatch(package, edge_ids, run_id, broker_dir, store_dir, timeout):
    """Reference ``server_runner.py:426 send_training_request_to_edges``:
    the server-side MLOps flow the agent daemons serve. Exits 0 when every
    edge reports FINISHED."""
    from ..comm.pubsub import FileSystemBroker
    from ..comm.store import FileSystemBlobStore
    from .runner import FedMLServerRunner

    broker = FileSystemBroker(root=broker_dir)
    store = FileSystemBlobStore(root=store_dir)
    server = FedMLServerRunner(broker, store=store)
    server.send_training_request_to_edges(run_id, list(edge_ids), package)
    statuses = server.wait_for_edges(
        list(edge_ids), timeout=timeout, run_id=run_id)
    click.echo(json.dumps({"run_id": run_id, "statuses": statuses}))
    broker.close()
    if not all(statuses.get(e) == "FINISHED" for e in edge_ids):
        raise SystemExit(1)


@cli.command("analyze",
             help="Run the graftcheck static-analysis suite over fedml_tpu/ "
                  "(jit-purity, determinism, lock-order, config-drift, "
                  "no-print, donation-safety, sharding-consistency, "
                  "host-sync, collective-deadlock, thread-hazard, "
                  "retrace-hazard, wire-protocol, resource-leak). Flags are "
                  "forwarded to the checker driver: --checker ID "
                  "(repeatable), --json, --format {text,json,sarif}, "
                  "--changed-only [REF], --baseline PATH, --no-baseline, "
                  "--write-baseline, --root DIR, --stats, --cache PATH, "
                  "--no-cache. Exits 1 on non-baselined "
                  "findings. See docs/static_analysis.md.",
             context_settings={"ignore_unknown_options": True})
@click.argument("graftcheck_args", nargs=-1, type=click.UNPROCESSED)
def analyze(graftcheck_args):
    from ..analysis import main as graftcheck_main

    raise SystemExit(graftcheck_main(list(graftcheck_args)))


@cli.command("run", help="Run a simulation from a YAML config.")
@click.option("--cf", "config_file", required=True, type=click.Path(exists=True))
@click.option("--backend", default=None, help="sp | TPU (overrides YAML)")
@click.option("--flight-record", is_flag=True,
              help="Arm the flight recorder for this run (equivalent to "
                   "flight_recorder: true in the YAML): crashes, rollbacks "
                   "and SIGTERM dump a black-box bundle under flight_dir.")
def run(config_file, backend, flight_record):
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments

    args_list = ["--cf", config_file]
    args = load_arguments(args_list=args_list)
    if backend:
        args.backend = backend
    if flight_record:
        args.flight_recorder = True
    fedml_tpu.init(args=args)
    with open(_state_path("status.json"), "w") as f:
        json.dump({"status": "RUNNING", "time": time.time()}, f)
    try:
        history = fedml_tpu.run_simulation(args=args)
        final = history[-1] if history else {}
        with open(_state_path("status.json"), "w") as f:
            json.dump({"status": "FINISHED", "final": final, "time": time.time()}, f)
        click.echo(json.dumps(final))
    except Exception:
        with open(_state_path("status.json"), "w") as f:
            json.dump({"status": "FAILED", "time": time.time()}, f)
        raise


@cli.command("chaos-drill",
             help="Run a seeded fault-injection drill over loopback.")
@click.option("--seed", default=7, type=int, help="Fault plan seed.")
@click.option("--rounds", default=3, type=int)
@click.option("--clients", default=3, type=int)
@click.option("--drop-rate", default=0.2, type=float,
              help="Per-message drop probability.")
@click.option("--duplicate-rate", default=0.0, type=float)
@click.option("--fail-send-rate", default=0.0, type=float,
              help="Per-attempt transient send-failure probability.")
@click.option("--crash-rank", default=None, type=int,
              help="Rank to crash (black-hole) mid-run.")
@click.option("--crash-at-round", default=1, type=int)
@click.option("--byzantine-kind", default=None,
              type=click.Choice(["scale", "sign_flip", "gauss", "nan"]),
              help="Corrupt client uploads with this byzantine fault kind.")
@click.option("--byzantine-rate", default=0.3, type=float,
              help="Per-upload corruption probability (byzantine scenario).")
@click.option("--byzantine-scale", default=10.0, type=float,
              help="Boost factor for --byzantine-kind=scale.")
@click.option("--defend/--no-defend", default=True,
              help="Byzantine scenario: run with sanitizer + multi-Krum "
                   "(default) or undefended (shows the damage).")
@click.option("--codec", default=None, metavar="SPEC",
              help="Run the drill with the compressed update plane on "
                   "(comm_codec spec, e.g. 'delta|topk:0.01|q8' or 'q8') — "
                   "proves faults on compressed frames are absorbed.")
@click.option("--timeout", default=120.0, type=float,
              help="Hang bound: the drill fails if the run outlives this.")
@click.option("--tenant", default=None,
              help="Scope the drill's telemetry accounting to this tenant "
                   "(counters land tenant-labeled; deltas filter to them).")
@click.option("--flight-record", is_flag=True,
              help="Arm the flight recorder + span shipping for the drill; "
                   "crashes and rollbacks dump a black-box bundle, and one "
                   "manual bundle is written when the drill ends.")
@click.option("--flight-dir", default="flight_records", type=click.Path(),
              help="Directory flight bundles land in (with --flight-record).")
@click.option("--json", "as_json", is_flag=True,
              help="Emit the drill outcome as one JSON line (the same "
                   "reporter every drill shares) instead of the summary.")
@click.option("--straggler", is_flag=True,
              help="Run the straggler drill instead: sync vs buffered-async "
                   "engines under one seeded heavy-tail delay plan; gates "
                   "async goodput >= --min-goodput-ratio x the sync round "
                   "rate at final accuracy within --max-acc-delta.")
@click.option("--leaf-crash", "tier_scenario", flag_value="leaf_crash",
              default=None,
              help="Run the hierarchical-federation drill instead: kill a "
                   "leaf aggregator mid-generation and gate that failover "
                   "commits every surviving client's update exactly once "
                   "within --max-acc-delta of the fault-free run.")
@click.option("--partition", "tier_scenario", flag_value="partition",
              help="Hierarchical drill variant: cut root<->leaf for one "
                   "round window, verify the cut heals and the same "
                   "exactly-once + accuracy gates hold.")
@click.option("--device-churn", "device_churn", is_flag=True,
              help="Run the cross-device fleet drill instead: a simulated "
                   "device day with 30% fleet churn (dropout + rejoin waves, "
                   "permanent departures, one partition window), gated on "
                   "accuracy within --max-acc-delta of the churn-free "
                   "reference, closed shed/drop accounting, and a "
                   "bit-identical replay.")
@click.option("--spill-dir", default=None, type=click.Path(),
              help="Device-churn drill: directory for the client-state "
                   "arena's disk tier (departures reclaim their spill "
                   "files there). Default: a temp dir.")
@click.option("--rollout", is_flag=True,
              help="Run the poisoned-rollout drill instead: corrupt one "
                   "published model version (--byzantine sign_flip/nan/"
                   "scale/gauss) and gate that the serving canary blocks "
                   "the promotion, rolls back to last-good within "
                   "--max-acc-delta of served accuracy, and pins the "
                   "version against re-promotion.")
@click.option("--skew", default=10.0, type=float,
              help="Straggler drill: slowest/fastest client speed ratio.")
@click.option("--buffer-size", default=2, type=int,
              help="Straggler drill: async commit buffer size K.")
@click.option("--min-goodput-ratio", default=3.0, type=float,
              help="Straggler drill: async-goodput / sync-round-rate gate.")
@click.option("--max-acc-delta", default=0.02, type=float,
              help="Straggler drill: max allowed sync-minus-async accuracy.")
def chaos_drill(seed, rounds, clients, drop_rate, duplicate_rate,
                fail_send_rate, crash_rank, crash_at_round, byzantine_kind,
                byzantine_rate, byzantine_scale, defend, codec, timeout,
                tenant, flight_record, flight_dir, as_json, straggler,
                tier_scenario, device_churn, spill_dir, rollout, skew,
                buffer_size, min_goodput_ratio, max_acc_delta):
    """Stand up a full cross-silo deployment (server + clients, real codec,
    real round FSM) under the given fault plan and verify every round still
    closes. Exits 1 if the run hangs or loses rounds — the same check
    ``tests/test_chaos.py`` gates CI with, runnable against any config."""
    from ..cross_silo.chaos import run_chaos_drill

    if tier_scenario is not None:
        from ..cross_silo.chaos import run_tier_drill

        result = run_tier_drill(
            scenario=tier_scenario, max_acc_delta=max_acc_delta,
            random_seed=seed, comm_round=rounds)
        click.echo(json.dumps(result.json_record()) if as_json
                   else result.summary())
        if not result.ok:
            raise SystemExit(1)
        return

    if device_churn:
        import tempfile

        from ..cross_device.device_day import run_device_churn_drill

        result = run_device_churn_drill(
            max_acc_delta=max_acc_delta,
            spill_dir=spill_dir or tempfile.mkdtemp(prefix="device_day_"))
        click.echo(json.dumps(result.json_record()) if as_json
                   else result.summary())
        if not result.ok:
            raise SystemExit(1)
        return

    if rollout:
        from ..cross_silo.chaos import run_rollout_drill

        kw = dict(random_seed=seed, max_acc_delta=max_acc_delta)
        if byzantine_kind is not None:
            kw.update(rollout_poison_kind=byzantine_kind,
                      rollout_poison_scale=byzantine_scale)
        result = run_rollout_drill(**kw)
        click.echo(json.dumps(result.json_record()) if as_json
                   else result.summary())
        if not result.ok:
            raise SystemExit(1)
        return

    if straggler:
        from ..cross_silo.chaos import run_straggler_drill

        result = run_straggler_drill(
            min_goodput_ratio=min_goodput_ratio, max_acc_delta=max_acc_delta,
            random_seed=seed, async_delay_skew=skew,
            async_buffer_size=buffer_size)
        click.echo(json.dumps(result.json_record()) if as_json
                   else result.summary())
        if not result.ok:
            raise SystemExit(1)
        return

    kw = dict(
        fault_seed=seed, comm_round=rounds, client_num_in_total=clients,
        client_num_per_round=clients, fault_drop_rate=drop_rate,
        fault_duplicate_rate=duplicate_rate,
        fault_fail_send_rate=fail_send_rate,
    )
    if crash_rank is not None:
        kw.update(fault_crash_rank=crash_rank,
                  fault_crash_at_round=crash_at_round)
    if byzantine_kind is not None:
        kw.update(fault_byzantine_kind=byzantine_kind,
                  fault_byzantine_rate=byzantine_rate,
                  fault_byzantine_scale=byzantine_scale,
                  local_test_on_all_clients=True)
        if defend:
            kw.update(defense_type="multi_krum", sanitize_updates=True,
                      watchdog_factor=2.0)
    if codec is not None:
        # validate the spec before standing up a whole deployment
        from ..comm.codec import parse_codec_spec

        parse_codec_spec(codec)
        kw.update(comm_codec=codec)
    from ..core import telemetry
    if (codec is not None or tenant is not None or flight_record) \
            and not telemetry.enabled():
        # the codec verdict and tenant scoping read counter deltas
        telemetry.configure(enabled=True)
    if flight_record:
        # through the drill's config (not configure() here): the drill's
        # fedml_tpu.init re-reads the trace-plane family from its args and
        # would reset a pre-set flight_dir back to the default
        kw.update(flight_recorder=True, flight_dir=flight_dir,
                  trace_ship_spans=True)
    result = run_chaos_drill(join_timeout_s=timeout, tenant=tenant, **kw)
    if flight_record:
        from ..core import trace_plane

        bundle = trace_plane.flight_dump("manual", force=True)
        if bundle:
            click.echo(f"flight bundle: {bundle}")
    click.echo(json.dumps(result.json_record()) if as_json
               else result.summary())
    if not result.ok:
        raise SystemExit(1)
    if codec is not None and not result.codec_bytes_wire:
        click.echo("codec drill: FAIL — comm_codec was set but no "
                   "fedml_codec_* traffic was recorded")
        raise SystemExit(1)


@cli.command("serve",
             help="Run N federated jobs multi-tenant over one device mesh.")
@click.option("--job", "-j", "job_specs", multiple=True, required=True,
              metavar="NAME=CONFIG.yaml[:PRIORITY]",
              help="One tenant job: a name, its YAML config, and an optional "
                   "scheduler priority weight (repeat for each tenant).")
@click.option("--capacity-bytes", default=2 << 30, type=int,
              help="Admission budget: total device bytes jobs may reserve.")
@click.option("--max-jobs", default=8, type=int,
              help="Max concurrently admitted jobs.")
@click.option("--max-queue", default=16, type=int,
              help="Admission queue bound (beyond it: reject).")
@click.option("--quantum", default=1.0, type=float,
              help="Deficit-round-robin quantum per scheduling cycle.")
@click.option("--checkpoint-root", default=None, type=click.Path(),
              help="Per-tenant checkpoint namespaces live under this root.")
@click.option("--json", "as_json", is_flag=True,
              help="Emit one JSON line per tenant instead of summaries.")
def serve(job_specs, capacity_bytes, max_jobs, max_queue, quantum,
          checkpoint_root, as_json):
    """Admit each job against the byte budget (admit / queue / reject, typed
    verdicts), then interleave the admitted jobs' round steps fairly over one
    mesh — per-tenant telemetry, checkpoints, and numerics stay isolated
    (each job's history is bit-identical to running it solo). Exits 1 if any
    job is rejected or fails."""
    from ..arguments import SECTION_FAMILIES, load_yaml_config
    from ..core import telemetry
    from ..simulation import MultiTenantSimDriver, TenantJob

    if not telemetry.enabled():
        telemetry.configure(enabled=True)

    def flat(cfg):
        # same section-flattening rule as Arguments.set_attr_from_config
        out = {}
        for section, content in cfg.items():
            if isinstance(content, dict) and (
                    section in SECTION_FAMILIES or section.endswith("_args")):
                out.update(content)
            else:
                out[section] = content
        return out

    jobs = []
    for spec in job_specs:
        name, eq, rest = spec.partition("=")
        if not eq or not name:
            raise click.BadParameter(
                f"--job wants NAME=CONFIG.yaml[:PRIORITY], got '{spec}'")
        path, colon, prio = rest.rpartition(":")
        try:
            priority = float(prio) if colon else 1.0
        except ValueError:
            path, priority = rest, 1.0  # the ':' belonged to the path
        if not colon:
            path = rest
        if not os.path.exists(path):
            raise click.BadParameter(f"--job {name}: no such config '{path}'")
        jobs.append(TenantJob(name, flat(load_yaml_config(path)),
                              priority=priority))

    driver = MultiTenantSimDriver(
        jobs, capacity_bytes=capacity_bytes, max_concurrent=max_jobs,
        max_queue=max_queue, quantum=quantum,
        checkpoint_root=checkpoint_root, log_fn=click.echo)
    results = driver.run()
    ok = True
    for name in sorted(results):
        r = results[name]
        ok = ok and r.ok
        if as_json:
            last = r.history[-1] if r.history else {}
            click.echo(json.dumps({
                "tenant": r.tenant, "decision": r.verdict.decision,
                "ok": r.ok, "rounds": len(r.history),
                "rounds_expected": r.rounds_expected,
                "elapsed_s": round(r.elapsed_s, 3), "error": r.error,
                "final_train_loss": last.get("train_loss"),
            }))
        else:
            click.echo(r.summary())
    if not ok:
        raise SystemExit(1)


@cli.command("loadgen",
             help="Replay device check-in overload against the bounded "
                  "check-in queue and report the throughput/shed frontier.")
@click.option("--duration", default=1.0, type=float,
              help="Drill length in seconds.")
@click.option("--rate", default=0.0, type=float,
              help="Target aggregate check-ins/sec (0 = producers run flat "
                   "out to find the natural ceiling).")
@click.option("--producers", default=2, type=int)
@click.option("--queue-maxsize", default=512, type=int,
              help="Check-in queue bound; overflow is shed, never buffered.")
@click.option("--tenants", default=2, type=int,
              help="Tenant count check-ins round-robin across.")
@click.option("--churn", default=0.1, type=float,
              help="Seeded fraction of devices that vanish mid-announce.")
@click.option("--seed", default=0, type=int)
@click.option("--json", "as_json", is_flag=True,
              help="Emit the frontier as one JSON line.")
def loadgen(duration, rate, producers, queue_maxsize, tenants, churn, seed,
            as_json):
    """Every check-in rides the real message codec; shedding shows up in the
    per-tenant ``fedml_checkins_shed_total`` counters and the queue's depth
    high-water mark can never pass the bound. Exits 1 if the accounting
    doesn't close (offered != accepted + shed) or the bound broke."""
    from ..core import telemetry
    from ..cross_silo.loadgen import run_loadgen

    if not telemetry.enabled():
        telemetry.configure(enabled=True)
    report = run_loadgen(duration_s=duration, target_rate=rate,
                         producers=producers, queue_maxsize=queue_maxsize,
                         tenants=tenants, churn=churn, seed=seed)
    click.echo(json.dumps(report.json_record()) if as_json
               else report.summary())
    if not report.ok:
        raise SystemExit(1)


@cli.group("telemetry", help="Inspect telemetry artifacts.")
def telemetry_group():
    pass


@telemetry_group.command(
    "summary", help="Summarize a telemetry JSONL file (spans + registry).")
@click.argument("jsonl_path", type=click.Path(exists=True))
@click.option("--tenant", default=None,
              help="Restrict to one tenant's spans and series (multi-run "
                   "JSONL files interleave every tenant's records).")
def telemetry_summary(jsonl_path, tenant):
    from ..core import telemetry as _telemetry

    spans = {}
    instants = {}
    snapshot = None
    skipped = 0
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            kind = rec.get("kind")
            if kind == "span":
                if tenant is not None and rec.get("tenant") != tenant:
                    continue
                s = spans.setdefault(
                    rec.get("name", "?"), {"durations": [], "traces": set()})
                s["durations"].append(float(rec.get("duration", 0.0)))
                if rec.get("trace_id"):
                    s["traces"].add(rec["trace_id"])
            elif kind == "instant":
                # point events (commit / quarantine / rollback / shed …) —
                # the same records the Perfetto export renders as ph:"i"
                if tenant is not None and rec.get("tenant") != tenant:
                    continue
                i = instants.setdefault(
                    rec.get("name", "?"), {"count": 0, "rounds": set()})
                i["count"] += 1
                if rec.get("round") is not None:
                    i["rounds"].add(int(rec["round"]))
            elif kind == "registry_snapshot":
                snapshot = rec.get("registry")  # keep the LAST one
    if snapshot is not None and tenant is not None:
        # the same filtering TenantRegistry.snapshot applies in-process
        snapshot = _telemetry.filter_snapshot(snapshot, tenant)
    if spans:
        click.echo("spans:")
        click.echo(f"  {'name':<28}{'count':>7}{'total_s':>10}"
                   f"{'mean_s':>10}{'p95_s':>10}{'traces':>8}")
        for name in sorted(spans):
            ds = sorted(spans[name]["durations"])
            total = sum(ds)
            p95 = ds[min(len(ds) - 1, int(0.95 * (len(ds) - 1)))]
            click.echo(f"  {name:<28}{len(ds):>7}{total:>10.4f}"
                       f"{total / len(ds):>10.5f}{p95:>10.5f}"
                       f"{len(spans[name]['traces']):>8}")
    if instants:
        click.echo("instants:")
        click.echo(f"  {'name':<28}{'count':>7}{'rounds':>8}")
        for name in sorted(instants):
            i = instants[name]
            click.echo(f"  {name:<28}{i['count']:>7}{len(i['rounds']):>8}")
    if snapshot:
        counters = snapshot.get("counters", {})
        dropped = sum(v for k, v in counters.items()
                      if k.startswith("fedml_spans_dropped_total"))
        if dropped:
            click.echo(f"spans dropped (ring evictions): {dropped:g} — "
                       "raise telemetry_span_buffer to keep them")
        if counters:
            click.echo("counters:")
            for key in sorted(counters):
                click.echo(f"  {key} = {counters[key]:g}")
        shed_rows = [(k.split("reason=", 1)[-1].rstrip("}"), v)
                     for k, v in counters.items()
                     if k.startswith("fedml_shed_total{")
                     and "reason=" in k]
        if shed_rows:
            by_reason: dict = {}
            for reason, v in shed_rows:
                reason = reason.split(",", 1)[0]
                by_reason[reason] = by_reason.get(reason, 0.0) + v
            total = sum(by_reason.values()) or 1.0
            click.echo("shed breakdown (by reason):")
            for reason, v in sorted(by_reason.items(), key=lambda kv: -kv[1]):
                click.echo(f"  {reason:<16}{v:>12g}{v / total:>9.1%}")
        hists = snapshot.get("histograms", {})
        phase_rows = []
        if hists:
            click.echo("histograms:")
            for key in sorted(hists):
                h = hists[key]
                n = h.get("count", 0)
                mean = h["sum"] / n if n else 0.0
                click.echo(f"  {key}: count={n:g} mean={mean:.6g}")
                if key.startswith("fedml_round_phase_seconds{"):
                    phase = key.split("phase=", 1)[-1].rstrip("}")
                    phase_rows.append((phase, h["sum"]))
        if phase_rows:
            total = sum(v for _, v in phase_rows) or 1.0
            click.echo("round phase breakdown (share of attributed wall):")
            for phase, v in sorted(phase_rows, key=lambda kv: -kv[1]):
                click.echo(f"  {phase:<12}{v:>12.4f}s{v / total:>9.1%}")
    if not spans and not instants and not snapshot:
        click.echo("no span or registry_snapshot records found")
    if skipped:
        click.echo(f"({skipped} unparseable lines skipped)")


@telemetry_group.command(
    "trace",
    help="Render a telemetry JSONL file or flight-recorder bundle as Chrome "
         "trace-event JSON (open in Perfetto / chrome://tracing): one "
         "process per tenant, one track per rank, phase slices, comm spans, "
         "and instant events, skew-corrected from the handshake exchange.")
@click.argument("source", type=click.Path(exists=True))
@click.option("--out", "out_path", required=True, type=click.Path(),
              help="Output trace file, e.g. round.trace.json.")
@click.option("--tenant", default=None,
              help="Keep only this tenant's records.")
@click.option("--round", "round_idx", default=None, type=int,
              help="Keep only this round's spans/phases/instants.")
def telemetry_trace(source, out_path, tenant, round_idx):
    from ..core import trace_plane

    records = trace_plane.load_records(source)
    doc = trace_plane.export_chrome_trace(
        records, out_path=out_path, tenant=tenant, round_idx=round_idx)
    events = doc["traceEvents"]
    slices = [e for e in events if e.get("ph") == "X"]
    if not slices:
        click.echo(f"no matching trace events in {source} "
                   f"(tenant={tenant!r}, round={round_idx!r}) — wrote an "
                   "empty trace")
    pids = {e["pid"] for e in slices}
    tids = {(e["pid"], e["tid"]) for e in slices}
    instants = sum(1 for e in events if e.get("ph") == "i")
    click.echo(f"wrote {out_path}: {len(slices)} slices, {instants} "
               f"instants across {len(pids)} process(es) / {len(tids)} "
               "track(s)")


def main():
    cli()


if __name__ == "__main__":
    main()
