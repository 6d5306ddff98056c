"""The reader of the program's own spans (``readers/program_span.py``): over
a synthetic ring of finished spans, and over a tiny LM cell run on the CPU
through the harness's ``run_cell`` with the five metrics this reader feeds."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil

import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)
from fedml_tpu.core import telemetry

LM_SPAN_METRICS = {"input_put_ms.lm": "ms", "dispatch_ms.lm": "ms",
                   "loss_wait_ms.lm": "ms", "step_host_ms_max.lm": "ms",
                   "trainer_init_s.lm": "s"}


@pytest.fixture(scope="module")
def reader():
    spec = importlib.util.spec_from_file_location(
        "bench_program_span", os.path.join(th.BENCH, "readers", "program_span.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _step(i, input_put, dispatch, loss_wait):
    """The four spans one ``trainer.step`` leaves, children first."""
    parent = f"step{i}"
    spans = [{"name": n, "span_id": f"{parent}.{n}", "parent_span_id": parent,
              "duration": d} for n, d in (("lm.input_put", input_put),
                                          ("lm.dispatch", dispatch),
                                          ("lm.loss_wait", loss_wait))]
    return spans + [{"name": "lm.step", "span_id": parent,
                     "parent_span_id": None,
                     "duration": input_put + dispatch + loss_wait}]


@pytest.fixture
def ring(monkeypatch):
    """Set-up (the trainer's construction, three check steps that compile)
    and then a window of four steps, one of them held up on the host."""
    spans = [{"name": "lm.trainer_init", "span_id": "init",
              "parent_span_id": None, "duration": 5.5}]
    for i in range(3):
        spans += _step(i, 0.5, 30.0, 1.0)
    for i, (put, dispatch) in enumerate(
            [(0.001, 0.004), (0.002, 0.004), (0.001, 2.004), (0.002, 0.006)]):
        spans += _step(3 + i, put, dispatch, 0.275)
    fake = type("Ring", (), {"finished_spans": staticmethod(lambda: list(spans))})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: fake)
    return spans


def test_mean_and_max_over_the_windows_steps_only(reader, ring):
    ctx = {"window": {"attempted": 4}}
    assert reader.read(ctx, spans=["lm.input_put"], stat="mean") == pytest.approx(1.5)
    assert reader.read(ctx, spans=["lm.dispatch"], stat="mean") == pytest.approx(504.5)
    assert reader.read(ctx, spans=["lm.loss_wait"], stat="mean") == pytest.approx(275.0)
    # the largest sum of one step, not the sum of the largest of each
    assert reader.read(ctx, spans=["lm.input_put", "lm.dispatch"],
                       stat="max") == pytest.approx(2005.0)
    # a window of seven would reach into the check steps, which compile
    assert reader.read({"window": {"attempted": 7}}, spans=["lm.dispatch"],
                       stat="max") == pytest.approx(30000.0)


def test_a_span_of_set_up_is_read_once_in_seconds(reader, ring):
    ctx = {"window": {"attempted": 4}}
    assert reader.read(ctx, spans=["lm.trainer_init"], stat="max", unit="s",
                       once=True) == pytest.approx(5.5)


def test_too_few_spans_or_none_give_nothing(reader, ring, monkeypatch):
    assert reader.read({"window": {"attempted": 8}}, spans=["lm.dispatch"],
                       stat="mean") is None
    assert reader.read({"window": {"attempted": 4}}, spans=["lm.no_such_span"],
                       stat="mean") is None
    assert reader.read({"window": {"attempted": 0}}, spans=["lm.dispatch"],
                       stat="mean") is None
    # a program without these spans, as the parent commit is
    empty = type("Ring", (), {"finished_spans": staticmethod(lambda: [])})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: empty)
    assert reader.read({"window": {"attempted": 4}}, spans=["lm.trainer_init"],
                       stat="max", unit="s", once=True) is None


def test_manifest_enters_the_five_metrics_for_the_lm_cell(bench, manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    layer = entries["step_mfu.lm"]["layer"]
    for name, unit in LM_SPAN_METRICS.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, "lower", "program_span", layer)
        assert m["workloads"] == [th.LM_CELL]
        assert m["moves"] == ("setup_s" if name == "trainer_init_s.lm"
                              else "tokens_per_s")
        spec = bench.load_json(os.path.join(
            th.BENCH, "layer_metrics", name + ".json"))
        assert spec["reader"] == "program_span"
    assert list(entries)[-5:] == list(LM_SPAN_METRICS)  # appended, in order


@pytest.fixture(scope="module")
def tiny_lm_root(tmp_path_factory, manifest):
    """A copy of the benchmark with a tiny LM configuration and cell added,
    and the cell appended to every LM per-layer metric's ``workloads``."""
    root = str(tmp_path_factory.mktemp("bench_span_copy"))
    shutil.copytree(th.BENCH, os.path.join(root, "benchmark"))
    m = json.loads(json.dumps(manifest))
    spec = th.TINY["lm"]
    base, changes = spec["config"]
    with open(os.path.join(th.BENCH, "configs", base + ".json")) as f:
        cfg = dict(json.load(f), name="tiny_lm", **changes)
    th._write(root, "benchmark/configs/tiny_lm.json", cfg)
    th._write(root, "benchmark/traffic/tiny_lm_loop.json", spec["traffic"])
    m["configs"].append({"name": "tiny_lm", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny_lm.json", "why": "test"})
    m["workloads"].append({"name": "tiny_lm.loop", "config": "tiny_lm",
                           "traffic": "tiny_lm_loop", "chips": 1, "why": "test"})
    for entry in m["end_to_end"] + m["per_layer"]:
        if th.LM_CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny_lm.loop")
    th._write(root, "BENCHMARK.json", m)
    return root


def test_tiny_traced_lm_cell_prints_the_five_span_metrics(bench, tiny_lm_root):
    telemetry.configure(enabled=True, reset=True)
    result, _ = th._drive(bench, tiny_lm_root, "tiny_lm.loop", trace=True, seed=11)
    assert result["correct"] is True, result["compared"]
    got = result["metrics"]
    assert set(LM_SPAN_METRICS) <= set(got)
    for name, unit in LM_SPAN_METRICS.items():
        assert got[name]["unit"] == unit and got[name]["value"] > 0
    # a step is its three spans: their means lie between half the median
    # step and the mean step (which holds the profiler's switching too)
    parts = sum(got[n]["value"] for n in
                ("input_put_ms.lm", "dispatch_ms.lm", "loss_wait_ms.lm"))
    mean_step_ms = 1e3 * result["run"]["wall_s"] / result["run"]["steps"]
    assert 0.5 * result["run"]["step_ms_median"] < parts <= mean_step_ms
    assert (got["step_host_ms_max.lm"]["value"]
            >= got["input_put_ms.lm"]["value"])
    telemetry.configure(enabled=True, reset=True)
