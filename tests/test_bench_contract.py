"""bench.py's device contract: the driver artifact ALWAYS parses, a run
that did not measure the chip cleanly never exits 0, and every line says
what it ran on.

``bench.main()`` checks the device in-process (no probe subprocess): off a
TPU it prints exactly one JSON line with an "error" field and returns 1."""

import io
import json
from contextlib import redirect_stdout

import bench

TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def _run_main(monkeypatch, **patches):
    for name, val in patches.items():
        monkeypatch.setattr(bench, name, val, raising=True)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.main()
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    return rc, json.loads(lines[0])


def test_cpu_emits_error_json_and_returns_1(monkeypatch):
    """The test tier runs on the CPU platform: main() must refuse it."""
    def never():
        raise AssertionError("run_bench must not start off the chip")

    rc, rec = _run_main(monkeypatch, run_bench=never)
    assert rc == 1
    assert rec["metric"] == "fedavg_cifar10_resnet56_rounds_per_sec"
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert "'cpu'" in rec["error"] and "tpu" in rec["error"]


def test_backend_that_cannot_start_emits_error_json(monkeypatch):
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    rc, rec = _run_main(monkeypatch, device_stamp=no_backend)
    assert rc == 1
    assert rec["value"] is None
    assert "Unable to initialize backend" in rec["error"]


def test_bench_crash_emits_error_json(monkeypatch):
    def boom():
        raise RuntimeError("HBM exhausted mid-bench")

    rc, rec = _run_main(monkeypatch, device_stamp=lambda: TPU, run_bench=boom)
    assert rc == 1
    assert rec["value"] is None
    assert "RuntimeError: HBM exhausted mid-bench" in rec["error"]
    assert rec["device_kind"] == "TPU v5 lite"


def test_success_emits_value_stamped_with_the_device(monkeypatch):
    rc, rec = _run_main(
        monkeypatch, device_stamp=lambda: TPU,
        run_bench=lambda: (6.25, {}, {"overlap_mean": 0.8}, {}))
    assert rc == 0
    assert rec["value"] == 6.25
    assert "error" not in rec and "candidate_errors" not in rec
    assert rec["vs_baseline"] > 0
    assert rec["host_pack"] == {"overlap_mean": 0.8}
    assert {k: rec[k] for k in TPU} == TPU


def test_failed_carry_candidate_is_a_failed_run(monkeypatch):
    """A one-executor run (the other carry candidate crashed) still prints
    the survivor's number with candidate_errors — and exits non-zero."""
    rc, rec = _run_main(
        monkeypatch, device_stamp=lambda: TPU,
        run_bench=lambda: (4.5, {True: "RuntimeError: flat compile blew up"},
                           {}, {}))
    assert rc != 0
    assert rec["value"] == 4.5
    assert rec["candidate_errors"] == {
        "flat": "RuntimeError: flat compile blew up"}


def test_unreadable_baseline_still_emits(monkeypatch):
    monkeypatch.setattr(
        bench, "load_baseline",
        lambda: (_ for _ in ()).throw(ValueError("corrupt json")))
    rc, rec = _run_main(monkeypatch)
    assert rc == 1
    assert "undocumented-1.0" in rec["unit"]
