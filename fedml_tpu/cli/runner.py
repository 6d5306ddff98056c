"""MLOps agent daemons: edge (client) and server runners.

Parity: reference ``cli/edge_deployment/client_runner.py:38``
(``FedMLClientRunner``: package download ``retrieve_and_unzip_package:129``,
config rewrite ``update_local_fedml_config:147``, train-process fork
``callback_start_train:426``, stop ``callback_stop_train:445``, status FSM
``callback_runner_id_status:619``) and ``cli/server_deployment/
server_runner.py:42`` (``FedMLServerRunner``: fans the training request to
edges ``send_training_request_to_edges:426``).

Redesign: the daemons ride the same pluggable control plane as the MQTT_S3
backend — a ``PubSubBroker`` for job dispatch (filesystem broker needs no
hosted MQTT) and a ``BlobStore`` for package distribution (filesystem store
replaces S3). The job lifecycle is identical: a start message names a built
package; the edge daemon fetches + unzips it, rewrites its YAML config with
the run's dynamic args, forks the training process, and reports the
IDLE/RUNNING/FAILED/FINISHED FSM through MLOpsMetrics and a status file the
CLI ``status`` command reads.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import subprocess
import sys
import threading
import time
import zipfile
from typing import Any, Dict, Optional

import yaml

from ..comm.message import pack_payload, unpack_payload
from ..comm.pubsub import PubSubBroker
from ..comm.store import BlobStore
from ..core.mlops import MetricsSink, MLOpsMetrics

JOB_TOPIC_FMT = "mlops_job_{edge_id}"
STATUS_TOPIC = "mlops_status"

MSG_START_TRAIN = "start_train"
MSG_STOP_TRAIN = "stop_train"


class FedMLEdgeRunner:
    """Edge agent daemon (reference ``FedMLClientRunner:38``)."""

    def __init__(
        self,
        edge_id: int,
        broker: PubSubBroker,
        store: Optional[BlobStore] = None,
        home_dir: Optional[str] = None,
        sink: Optional[MetricsSink] = None,
    ):
        self.edge_id = int(edge_id)
        self.broker = broker
        self.store = store
        self.home = home_dir or os.path.expanduser(
            os.environ.get("FEDML_TPU_HOME", "~/.fedml_tpu")
        )
        os.makedirs(self.home, exist_ok=True)
        self.metrics = MLOpsMetrics(sink=sink)
        self.metrics.edge_id = self.edge_id
        self._proc: Optional[subprocess.Popen] = None
        self._current_run = None
        self._proc_lock = threading.Lock()
        self._running = True
        self._done = threading.Event()
        # terminal job history persists across daemon restarts so replayed
        # job-topic history (subscribe_from_start) never re-executes a run
        # that already finished (reference relies on MQTT QoS for this)
        self._history_path = os.path.join(
            self.home, f"jobs_edge{self.edge_id}.json")
        self._history_lock = threading.Lock()
        self._job_history: Dict[str, str] = self._load_history()
        # serializes status reports: the dispatcher thread (stop/replay) and
        # a watcher thread (process exit) can report concurrently
        self._status_lock = threading.Lock()
        self._report_status(MLOpsMetrics.STATUS_IDLE)

    @classmethod
    def from_binding(cls, broker: PubSubBroker, bind_url: str,
                     account_id: str, http_post=None, **kwargs):
        """Hosted-platform flow (reference ``client_login.py`` →
        ``bind_account_and_device_id``): register this host under the
        account, then run the agent as the returned edge id. The transport
        is injectable; a refused binding raises instead of silently running
        as edge 0."""
        from ..core.mlops import bind_account_and_device_id

        edge_id = bind_account_and_device_id(
            bind_url, account_id, http_post=http_post)
        if not edge_id:
            raise RuntimeError(
                f"device binding refused for account {account_id} at "
                f"{bind_url}")
        return cls(edge_id, broker, **kwargs)

    def _load_history(self) -> Dict[str, str]:
        try:
            with open(self._history_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def _record_terminal(self, run_id, status: str) -> None:
        # watcher thread and poller thread can both reach terminal for the
        # same run (stop racing process exit): lock + atomic replace so a
        # torn write can never wipe the whole replay-protection history
        with self._history_lock:
            self._job_history[str(run_id)] = status
            tmp = self._history_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._job_history, f)
            os.replace(tmp, self._history_path)

    # --- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        """Subscribe to this edge's job topic and serve jobs until stop().
        Brokers with history replay deliver jobs queued before the daemon
        came up (the reference relies on MQTT retained sessions for this)."""
        topic = JOB_TOPIC_FMT.format(edge_id=self.edge_id)
        subscribe = getattr(self.broker, "subscribe_from_start", self.broker.subscribe)
        subscribe(topic, self._on_job)

    def stop(self) -> None:
        self._running = False
        self.broker.unsubscribe(JOB_TOPIC_FMT.format(edge_id=self.edge_id))
        self._kill_train_process()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until a job reaches a terminal state (test convenience)."""
        return self._done.wait(timeout)

    # --- job handling -------------------------------------------------------
    def _on_job(self, _topic: str, payload: bytes) -> None:
        if not self._running:
            return
        job = unpack_payload(payload)
        kind = job.get("msg")
        if kind == MSG_START_TRAIN:
            self._callback_start_train(job)
        elif kind == MSG_STOP_TRAIN:
            self._callback_stop_train(job)

    def _package_dirs(self, run_id) -> Dict[str, str]:
        base = os.path.join(self.home, "fedml_run", f"run_{run_id}",
                            f"edge_{self.edge_id}")
        return {
            "download": os.path.join(base, "download"),
            "run": os.path.join(base, "package"),
        }

    def retrieve_and_unzip_package(self, run_id, package_ref: str) -> str:
        """Fetch the built package (blob-store key or local path) and unzip
        it into this run's directory (reference ``:129``)."""
        dirs = self._package_dirs(run_id)
        os.makedirs(dirs["download"], exist_ok=True)
        local_zip = os.path.join(dirs["download"], os.path.basename(package_ref))
        if self.store is not None and not os.path.exists(package_ref):
            with open(local_zip, "wb") as f:
                f.write(self.store.get(package_ref))
        else:
            shutil.copyfile(package_ref, local_zip)
        shutil.rmtree(dirs["run"], ignore_errors=True)
        with zipfile.ZipFile(local_zip) as z:
            z.extractall(dirs["run"])
        return dirs["run"]

    def update_local_config(self, package_dir: str, dynamic_args: Dict[str, Any]) -> str:
        """Rewrite the packaged YAML config with the run's dynamic args
        (reference ``update_local_fedml_config:147``). Returns the rewritten
        config path."""
        cfg_dir = os.path.join(package_dir, "config")
        cfg_path = None
        for name in sorted(os.listdir(cfg_dir)):
            if name.endswith((".yaml", ".yml")):
                cfg_path = os.path.join(cfg_dir, name)
                break
        if cfg_path is None:
            raise FileNotFoundError(f"no yaml config inside {cfg_dir}")
        with open(cfg_path) as f:
            cfg = yaml.safe_load(f) or {}
        # dynamic args land in the common_args section family
        common = cfg.setdefault("common_args", {})
        for k, v in (dynamic_args or {}).items():
            common[k] = v
        with open(cfg_path, "w") as f:
            yaml.safe_dump(cfg, f)
        return cfg_path

    def _callback_start_train(self, job: Dict[str, Any]) -> None:
        """Reference ``callback_start_train:426``: package -> config -> fork."""
        run_id = job.get("run_id", 0)
        with self._history_lock:
            prior = self._job_history.get(str(run_id))
        if prior is not None:
            logging.info("edge %d: run %s already terminal (%s), skipping",
                         self.edge_id, run_id, prior)
            return
        with self._proc_lock:
            if (self._proc is not None and self._proc.poll() is None
                    and self._current_run == run_id):
                logging.info("edge %d: run %s already running, ignoring "
                             "duplicate start", self.edge_id, run_id)
                return
            superseded = (self._current_run if self._proc is not None
                          and self._proc.poll() is None else None)
        # a different run supersedes the current one (reference restarts the
        # training process on every start message); record the loser as
        # KILLED here — its watcher bows out once self._proc is reassigned
        if superseded is not None:
            with self._history_lock:
                known = str(superseded) in self._job_history
            if not known:
                self._record_terminal(superseded, MLOpsMetrics.STATUS_KILLED)
        self._kill_train_process()
        self.metrics.run_id = run_id
        self._done.clear()
        try:
            package_dir = self.retrieve_and_unzip_package(run_id, job["package"])
            cfg_path = self.update_local_config(
                package_dir, job.get("dynamic_args", {})
            )
            with open(os.path.join(package_dir, "package.json")) as f:
                entry_point = json.load(f)["entry_point"]
            entry = os.path.join(package_dir, "source", entry_point)
            env = dict(os.environ)
            env.update({str(k): str(v) for k, v in (job.get("env") or {}).items()})
            log_dir = os.path.join(self.home, "logs")
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, f"run_{run_id}_edge_{self.edge_id}.log")
            self._report_status(MLOpsMetrics.STATUS_RUNNING)
            # fork/exec outside the lock — callbacks run on one dispatcher
            # thread, so only the self._proc handoff below needs the lock
            # (the watcher thread compares identity before acting)
            # the training child is the process that claims the chip(s): this
            # daemon never initialises a JAX backend (importing fedml_tpu
            # does not — tests/test_process_start.py pins it) and runs one
            # child at a time (a superseded run was killed above)
            with open(log_path, "w") as log:
                # the child duplicates the log fd; close the parent's copy
                proc = subprocess.Popen(
                    [sys.executable, entry, "--cf", cfg_path],
                    cwd=package_dir, env=env,
                    stdout=log, stderr=subprocess.STDOUT,
                )
            with self._proc_lock:
                self._proc = proc
                self._current_run = run_id
            threading.Thread(target=self._watch_train_process,
                             args=(proc, run_id), daemon=True).start()
        except Exception:
            logging.exception("edge %d: start_train failed", self.edge_id)
            self._record_terminal(run_id, MLOpsMetrics.STATUS_FAILED)
            self._report_status(MLOpsMetrics.STATUS_FAILED)
            self._done.set()

    def _watch_train_process(self, proc: subprocess.Popen, run_id) -> None:
        rc = proc.wait()
        with self._proc_lock:
            if self._proc is not proc:
                return  # superseded by a newer run; its watcher owns status
        if rc == 0:
            status = MLOpsMetrics.STATUS_FINISHED
        elif rc < 0:
            status = MLOpsMetrics.STATUS_KILLED
        else:
            status = MLOpsMetrics.STATUS_FAILED
        self._record_terminal(run_id, status)
        self._report_status(status)
        self._done.set()

    def _callback_stop_train(self, job: Dict[str, Any]) -> None:
        """Reference ``callback_stop_train:445``."""
        run_id = job.get("run_id", self._current_run)
        if run_id is not None:
            with self._history_lock:
                terminal = str(run_id) in self._job_history
            if terminal:
                # replayed stop for an already-terminal run: no spurious KILLED
                return
        if run_id is not None and self._current_run is not None \
                and run_id != self._current_run:
            return  # stop for a run this daemon never started
        self._kill_train_process()
        if run_id is not None:
            self._record_terminal(run_id, MLOpsMetrics.STATUS_KILLED)
        self._report_status(MLOpsMetrics.STATUS_KILLED)
        self._done.set()

    def _kill_train_process(self) -> None:
        with self._proc_lock:
            if self._proc is not None and self._proc.poll() is None:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    self._proc.kill()

    # --- status FSM ---------------------------------------------------------
    def _report_status(self, status: str) -> None:
        """Reference ``callback_runner_id_status:619`` + CLI status file."""
        rec = {"status": status, "edge_id": self.edge_id, "time": time.time(),
               "run_id": getattr(self.metrics, "run_id", None)}
        # attr + status files under one lock: a watcher thread and the
        # dispatcher can report concurrently, and a torn attr/file pair
        # would show two different states to the CLI `status` command.
        # The broker publish stays outside the critical section.
        with self._status_lock:
            self.status = status
            # per-edge file: multiple agents sharing one home dir must not
            # clobber each other's state (plus the legacy shared file the
            # CLI `status` command falls back to)
            with open(os.path.join(self.home,
                                   f"status_edge{self.edge_id}.json"), "w") as f:
                json.dump(rec, f)
            with open(os.path.join(self.home, "status.json"), "w") as f:
                json.dump(rec, f)
        self.metrics.report_client_training_status(self.edge_id, status)
        self.broker.publish(STATUS_TOPIC, pack_payload(rec))


class FedMLServerRunner:
    """Server agent (reference ``FedMLServerRunner:42``): receives a run
    request and fans the training job out to the edges."""

    def __init__(
        self,
        broker: PubSubBroker,
        store: Optional[BlobStore] = None,
        sink: Optional[MetricsSink] = None,
    ):
        self.broker = broker
        self.store = store
        self.metrics = MLOpsMetrics(sink=sink)
        self.edge_status: Dict[int, str] = {}
        self.edge_run: Dict[int, Any] = {}
        self._status_lock = threading.Lock()
        self.broker.subscribe(STATUS_TOPIC, self._on_edge_status)

    def _on_edge_status(self, _topic: str, payload: bytes) -> None:
        rec = unpack_payload(payload)
        with self._status_lock:
            self.edge_status[int(rec["edge_id"])] = rec["status"]
            self.edge_run[int(rec["edge_id"])] = rec.get("run_id")

    def upload_package(self, run_id, package_path: str) -> str:
        """Publish the built package for edges to fetch. With a store, edges
        pull by key; without one they read the local path directly."""
        if self.store is None:
            return package_path
        key = f"package_run{run_id}_{os.path.basename(package_path)}"
        with open(package_path, "rb") as f:
            self.store.put(key, f.read())
        return key

    def send_training_request_to_edges(
        self,
        run_id,
        edge_ids,
        package_path: str,
        dynamic_args: Optional[Dict[str, Any]] = None,
        env: Optional[Dict[str, str]] = None,
    ) -> None:
        """Reference ``send_training_request_to_edges:426``."""
        package_ref = self.upload_package(run_id, package_path)
        self.metrics.report_server_training_status(
            run_id, MLOpsMetrics.STATUS_RUNNING)
        for edge_id in edge_ids:
            job = {
                "msg": MSG_START_TRAIN,
                "run_id": run_id,
                "package": package_ref,
                "dynamic_args": dict(dynamic_args or {}, rank=edge_id),
                "env": env or {},
            }
            self.broker.publish(
                JOB_TOPIC_FMT.format(edge_id=edge_id), pack_payload(job)
            )

    def send_stop_request_to_edges(self, run_id, edge_ids) -> None:
        for edge_id in edge_ids:
            self.broker.publish(
                JOB_TOPIC_FMT.format(edge_id=edge_id),
                pack_payload({"msg": MSG_STOP_TRAIN, "run_id": run_id}),
            )

    def wait_for_edges(self, edge_ids, terminal=("FINISHED", "FAILED", "KILLED"),
                       timeout: float = 300.0, run_id=None) -> Dict[int, str]:
        """Block until every edge reports a terminal status — scoped to
        ``run_id`` when given, so stale FINISHED messages from a previous
        run never satisfy a new dispatch."""
        deadline = time.time() + timeout

        def _done(e):
            if self.edge_status.get(e) not in terminal:
                return False
            return run_id is None or self.edge_run.get(e) == run_id

        while time.time() < deadline:
            with self._status_lock:
                if all(_done(e) for e in edge_ids):
                    break
            time.sleep(0.05)
        with self._status_lock:
            if run_id is None:
                return dict(self.edge_status)
            # scope the RESULT too: a stale status from another run must not
            # read as this run's outcome after a timeout
            return {e: s for e, s in self.edge_status.items()
                    if self.edge_run.get(e) == run_id}
