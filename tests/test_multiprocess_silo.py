"""Multi-process hierarchical silo e2e: real OS processes joined by
jax.distributed, one sharded local update spanning both (review #9;
reference ``client_slave_manager.py:39`` semantics).

Each worker gets 2 virtual CPU devices, so the silo mesh is 2 procs x 2
devices = 4-way data parallel across a genuine process boundary.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO_ROOT, "scripts", "run_hier_silo_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_silo_round(tmp_path):
    port = _free_port()
    procs = []
    outs = [str(tmp_path / f"out_{i}.json") for i in range(2)]
    for pid in range(2):
        env = dict(
            os.environ,
            PYTHONPATH=REPO_ROOT,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, "--out", outs[pid], "--rounds", "2"],
            env=env, cwd=REPO_ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(out)
    assert all(p.returncode == 0 for p in procs), "\n----\n".join(logs)

    master = json.load(open(outs[0]))
    slave = json.load(open(outs[1]))
    # both processes saw the full 4-device world (2 local each)
    assert master["global_devices"] == 4 and master["local_devices"] == 2
    assert slave["global_devices"] == 4 and slave["local_devices"] == 2
    assert slave["slave"] is True
    hist = master["history"]
    assert len(hist) == 2
    import numpy as np

    assert np.isfinite(hist[-1]["test_acc"])
