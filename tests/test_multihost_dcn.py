"""Hierarchical FL with the GLOBAL aggregation over the DCN axis: two OS
processes joined by jax.distributed, each training one group locally, the
groups' weighted mean computed as a cross-process mesh collective
(round-4 review #7; reference cross_silo/hierarchical/
dist_trainer_launcher.py:23 torchrun world -> jax.distributed).

Complements tests/test_multiprocess_silo.py (which shards one silo's
batch axis across processes): here the processes hold DIFFERENT models
and the collective performs the cross-silo aggregation itself.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO_ROOT, "scripts", "run_dcn_hier_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_hierarchical_round_over_dcn(tmp_path):
    port = _free_port()
    outs = [str(tmp_path / f"out_{i}.json") for i in range(2)]
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            PYTHONPATH=REPO_ROOT,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                       + " --xla_force_host_platform_device_count=2").strip(),
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, "--out", outs[pid], "--group-rounds", "2"],
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

    r0, r1 = (json.load(open(o)) for o in outs)
    # both processes saw the full 4-device world
    assert r0["global_devices"] == 4 and r0["local_devices"] == 2
    assert r1["global_devices"] == 4 and r1["local_devices"] == 2
    # the groups trained DIFFERENT models (different data + init)...
    assert r0["group_vec_l2"] != pytest.approx(r1["group_vec_l2"])
    # ...yet the cross-process collective left both with the IDENTICAL
    # global model (the DCN reduction actually synchronized them)
    assert r0["merged_digest"] == pytest.approx(r1["merged_digest"], rel=1e-6)
    np.testing.assert_allclose(r0["merged_first8"], r1["merged_first8"],
                               rtol=1e-6)
    # and the merged model evaluates sanely on both groups' test splits
    assert np.isfinite(r0["test_acc"]) and np.isfinite(r1["test_acc"])
    assert r0["test_acc"] > 0.25 and r1["test_acc"] > 0.25
