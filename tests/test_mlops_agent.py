"""Hosted-MLOps agent surface + model-zoo depth (round-2 review missing #4/#5).

Device/account binding and incremental remote log upload with injectable
transports (reference client_runner.py:645-666, mlops_runtime_log.py:136);
EfficientNet compound-scaling family; SyncBN via flax axis_name psum."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.core.mlops import (
    MLOpsRuntimeLogUploader,
    bind_account_and_device_id,
    get_device_id,
)


def test_get_device_id_is_stable_hex():
    d = get_device_id()
    assert d.startswith("0x") and int(d, 16) > 0
    assert d == get_device_id()


def test_bind_account_and_device_id_schema_and_outcomes():
    posts = []

    def ok_post(url, json_params, headers, ca_path=None):
        posts.append((url, json_params, headers))
        return {"code": "SUCCESS", "data": {"id": 77}}

    edge = bind_account_and_device_id(
        "https://host/bind", "acct9", http_post=ok_post)
    assert edge == 77
    url, params, headers = posts[0]
    # reference request schema (client_runner.py:666)
    assert set(params) == {"accountid", "deviceid", "type", "gpu",
                           "processor", "network"}
    assert params["accountid"] == "acct9"
    assert headers == {"Connection": "close"}

    def refused_post(url, json_params, headers, ca_path=None):
        return {"code": "FAILED"}

    assert bind_account_and_device_id(
        "https://host/bind", "acct9", http_post=refused_post) == 0


def test_log_uploader_incremental_and_replay_on_failure(tmp_path):
    log = tmp_path / "run.log"
    log.write_text("line1\nline2\n")
    shipped = []
    fail = {"on": False}

    def post(url, body, headers, ca_path=None):
        if fail["on"]:
            raise ConnectionError("outage")
        shipped.append(body)
        return {"code": "SUCCESS"}

    up = MLOpsRuntimeLogUploader(
        run_id="r1", edge_id=5, log_file_path=str(log),
        upload_url="https://host/logs", http_post=post, interval=999)
    assert up.log_upload() == 2
    assert shipped[0]["logs"] == ["line1\n", "line2\n"]
    assert shipped[0]["edge_id"] == 5 and shipped[0]["created_by"] == "5"
    assert up.log_upload() == 0  # nothing new

    with open(log, "a") as f:
        f.write("line3\n")
    fail["on"] = True
    with pytest.raises(ConnectionError):
        up.log_upload()
    assert up.log_line_index == 2  # cursor did NOT advance on failure
    fail["on"] = False
    assert up.log_upload() == 1  # outage replays, never drops
    assert shipped[-1]["logs"] == ["line3\n"]

    # rotation/truncation: a smaller file resets the cursor instead of
    # stalling forever
    log.write_text("fresh1\n")
    assert up.log_upload() == 1
    assert shipped[-1]["logs"] == ["fresh1\n"]
    # a partial line (no newline yet) waits for the next tick
    with open(log, "a") as f:
        f.write("partial")
    assert up.log_upload() == 0
    with open(log, "a") as f:
        f.write(" done\n")
    assert up.log_upload() == 1
    assert shipped[-1]["logs"] == ["partial done\n"]


def test_edge_runner_from_binding(tmp_path):
    from fedml_tpu.cli.runner import FedMLEdgeRunner
    from fedml_tpu.comm.pubsub import InProcessBroker

    def post(url, body, headers, ca_path=None):
        return {"code": "SUCCESS", "data": {"id": 42}}

    runner = FedMLEdgeRunner.from_binding(
        InProcessBroker(), "https://host/bind", "acct", http_post=post,
        home_dir=str(tmp_path))
    assert runner.edge_id == 42
    runner.stop()

    def refuse(url, body, headers, ca_path=None):
        return {"code": "NO"}

    with pytest.raises(RuntimeError, match="binding refused"):
        FedMLEdgeRunner.from_binding(
            InProcessBroker(), "https://host/bind", "acct",
            http_post=refuse, home_dir=str(tmp_path))


# --- model-zoo depth -------------------------------------------------------

def test_efficientnet_family_scales():
    from fedml_tpu.models import EfficientNet, create

    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    sizes = {}
    for variant in ("b0", "b2"):
        m = EfficientNet(num_classes=10, variant=variant)
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (1, 10)
        sizes[variant] = sum(a.size for a in jax.tree.leaves(v))
    assert sizes["b2"] > sizes["b0"]  # compound scaling grows the net

    class A:  # factory dispatch
        model = "efficientnet-b1"
        dataset = "cifar10"

    m = create(A(), 10)
    assert m.variant == "b1"


def test_sync_batchnorm_matches_full_batch_stats():
    """SyncBN parity (reference batchnorm_utils.py:488): per-shard BN with
    the stats all-reduced over the device axis must equal plain BN over the
    concatenated batch."""
    from fedml_tpu.models.resnet import SYNC_BN_AXIS, CifarResNet

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32, 32, 3))
    sync = CifarResNet(depth=20, num_classes=10, norm_kind="sync_batch")
    plain = CifarResNet(depth=20, num_classes=10, norm_kind="batch")
    variables = plain.init(jax.random.PRNGKey(1), x[0], train=False)

    def shard_apply(xs):
        return sync.apply(variables, xs, train=True,
                          mutable=["batch_stats"])

    out_sync, stats_sync = jax.vmap(
        shard_apply, axis_name=SYNC_BN_AXIS)(x)
    out_full, stats_full = plain.apply(
        variables, x.reshape((16, 32, 32, 3)), train=True,
        mutable=["batch_stats"])
    np.testing.assert_allclose(
        np.asarray(out_sync).reshape(16, 10), np.asarray(out_full),
        rtol=2e-3, atol=2e-4)
    # synced running stats are identical on every shard and equal full-batch
    for s_sync, s_full in zip(jax.tree.leaves(stats_sync),
                              jax.tree.leaves(stats_full)):
        np.testing.assert_allclose(np.asarray(s_sync[0]),
                                   np.asarray(s_sync[1]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s_sync[0]),
                                   np.asarray(s_full), rtol=2e-3, atol=2e-4)
