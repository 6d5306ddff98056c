"""Agent daemon e2e: cli build -> server runner fan-out -> edge daemon
fetch/rewrite/fork -> status FSM reaches FINISHED.

Reference lifecycle: client_runner.py:129 (package), :147 (config rewrite),
:426 (fork), :619 (status FSM); server_runner.py:426 (fan-out).
"""

import json
import os
import subprocess
import sys
import textwrap
import zipfile

import yaml

from fedml_tpu.cli.runner import FedMLEdgeRunner, FedMLServerRunner
from fedml_tpu.comm.pubsub import FileSystemBroker
from fedml_tpu.comm.store import FileSystemBlobStore

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENTRY = textwrap.dedent(
    """
    import argparse, json, os
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments

    p = argparse.ArgumentParser()
    p.add_argument("--cf", required=True)
    opts = p.parse_args()
    args = load_arguments(args_list=["--cf", opts.cf])
    fedml_tpu.init(args=args)
    history = fedml_tpu.run_simulation(args=args)
    with open("result.json", "w") as f:
        json.dump({"rounds": len(history), "rank": int(getattr(args, "rank", -1))}, f)
    """
)

CONFIG = {
    "common_args": {"random_seed": 0, "run_id": "agent_e2e"},
    "data_args": {"dataset": "mnist", "debug_small_data": True},
    "model_args": {"model": "lr"},
    "train_args": {
        "federated_optimizer": "FedAvg", "client_num_in_total": 4,
        "client_num_per_round": 4, "comm_round": 2, "epochs": 1,
        "batch_size": 8, "learning_rate": 0.1,
    },
    "validation_args": {"frequency_of_the_test": 1},
}


def _build_package(tmp_path) -> str:
    src = tmp_path / "src"
    cfg = tmp_path / "cfg"
    dist = tmp_path / "dist"
    src.mkdir(); cfg.mkdir()
    (src / "main.py").write_text(ENTRY)
    (cfg / "fedml_config.yaml").write_text(yaml.safe_dump(CONFIG))
    r = subprocess.run(
        [sys.executable, "-m", "fedml_tpu.cli", "build", "-t", "client",
         "-sf", str(src), "-ep", "main.py", "-cf", str(cfg), "-df", str(dist)],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env=dict(os.environ, PYTHONPATH=REPO_ROOT),
    )
    assert r.returncode == 0, r.stderr
    pkg = dist / "fedml_tpu-client-package.zip"
    assert pkg.exists()
    with zipfile.ZipFile(pkg) as z:
        names = z.namelist()
    assert "package.json" in names and "source/main.py" in names
    return str(pkg)


def test_agent_daemon_end_to_end(tmp_path):
    pkg = _build_package(tmp_path)
    broker = FileSystemBroker(root=str(tmp_path / "broker"))
    store = FileSystemBlobStore(root=str(tmp_path / "blobs"))

    server = FedMLServerRunner(broker, store=store)
    edge = FedMLEdgeRunner(
        7, broker, store=store, home_dir=str(tmp_path / "edge_home")
    )
    edge.start()
    assert edge.status == "IDLE"

    # the child is a fresh interpreter: pin the virtual CPU platform so a
    # test never claims a chip
    child_env = {
        "PYTHONPATH": REPO_ROOT,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }
    server.send_training_request_to_edges(
        run_id="r42", edge_ids=[7], package_path=pkg,
        dynamic_args={"comm_round": 2}, env=child_env,
    )
    assert edge.wait(timeout=240), "edge daemon never reached a terminal state"
    statuses = server.wait_for_edges([7], timeout=30)
    assert statuses[7] == "FINISHED", statuses

    # the forked run really executed inside the unzipped package dir
    run_dir = tmp_path / "edge_home" / "fedml_run" / "run_r42" / "edge_7" / "package"
    result = json.loads((run_dir / "result.json").read_text())
    assert result["rounds"] == 2
    assert result["rank"] == 7  # dynamic_args rewrote the packaged config
    # status file for the CLI
    status = json.loads((tmp_path / "edge_home" / "status.json").read_text())
    assert status["status"] == "FINISHED"
    edge.stop()
    broker.close()


def test_edge_daemon_reports_failure(tmp_path):
    broker = FileSystemBroker(root=str(tmp_path / "broker"))
    edge = FedMLEdgeRunner(3, broker, home_dir=str(tmp_path / "home"))
    edge.start()
    server = FedMLServerRunner(broker)
    server.send_training_request_to_edges(
        run_id="bad", edge_ids=[3], package_path=str(tmp_path / "missing.zip"),
    )
    assert edge.wait(timeout=30)
    assert server.wait_for_edges([3], timeout=10)[3] == "FAILED"
    edge.stop()
    broker.close()

def test_edge_daemon_restart_does_not_replay_finished_jobs(tmp_path):
    """A restarted daemon re-reads job-topic history (subscribe_from_start)
    but must skip runs its persisted history already records as terminal."""
    broker = FileSystemBroker(root=str(tmp_path / "broker"))
    home = str(tmp_path / "home")
    edge = FedMLEdgeRunner(5, broker, home_dir=home)
    edge.start()
    server = FedMLServerRunner(broker)
    # a job that fails fast (missing package) still reaches a terminal state
    server.send_training_request_to_edges(
        run_id="done1", edge_ids=[5], package_path=str(tmp_path / "nope.zip"))
    assert edge.wait(timeout=30)
    edge.stop()

    # restart: same home dir, fresh broker instance over the same dir
    broker2 = FileSystemBroker(root=str(tmp_path / "broker"))
    edge2 = FedMLEdgeRunner(5, broker2, home_dir=home)
    calls = []
    orig = edge2.retrieve_and_unzip_package
    edge2.retrieve_and_unzip_package = lambda *a, **k: (calls.append(a), orig(*a, **k))[1]
    edge2.start()
    import time as _time
    _time.sleep(0.5)  # let the poller replay topic history
    assert calls == [], "restarted daemon re-executed an already-terminal job"
    assert edge2._job_history == {"done1": "FAILED"}
    edge2.stop()
    broker.close()
    broker2.close()


def test_filesystem_broker_concurrent_publishers_no_loss(tmp_path):
    """Racing publishers (two broker instances over one dir, many threads)
    must never overwrite each other's sequence slots."""
    import threading as _threading

    b1 = FileSystemBroker(root=str(tmp_path / "broker"))
    b2 = FileSystemBroker(root=str(tmp_path / "broker"))
    got = []
    lock = _threading.Lock()
    b1.subscribe_from_start("t", lambda _t, p: (lock.acquire(), got.append(p), lock.release()))

    def blast(b, tag):
        for i in range(25):
            b.publish("t", f"{tag}:{i}".encode())

    threads = [_threading.Thread(target=blast, args=(b, tag))
               for b, tag in ((b1, "a"), (b2, "b"), (b1, "c"), (b2, "d"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    deadline = __import__("time").time() + 10
    while len(got) < 100 and __import__("time").time() < deadline:
        __import__("time").sleep(0.05)
    assert len(got) == 100, f"lost {100 - len(got)} messages to publisher races"
    assert len(set(got)) == 100
    b1.close()
    b2.close()
