"""The reader of set-up's first step (``readers/compile_phase.py``): over a
synthetic ring of finished spans, over a program that records no compile
phases (as the parent commit's), and over a tiny LM cell run on the CPU
through the harness's ``run_cell``."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil

import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)
from fedml_tpu.core import telemetry

CELLS = {"lm": th.LM_CELL, "nemo3": "nemo3_t8192_b1_ep16share_pretrain"}
METRICS = {"step_trace_s": ("s", "jax.trace", "duration"),
           "step_lower_s": ("s", "jax.lower", "duration"),
           "step_compile_s": ("s", "jax.compile", "duration"),
           "step_traces": ("traces", "jax.trace", "traces")}
LAYER = "process start, cache (utils/compile_cache.py)"


@pytest.fixture(scope="module")
def reader():
    spec = importlib.util.spec_from_file_location(
        "bench_compile_phase", os.path.join(th.BENCH, "readers", "compile_phase.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dispatch(i, phases):
    """One step's dispatch and the ``jax.*`` children it holds, children
    first (the order the ring receives them)."""
    span = f"dispatch{i}"
    kids = [dict({"name": name, "span_id": f"{span}.{j}",
                  "parent_span_id": span, "duration": d}, **attrs)
            for j, (name, d, attrs) in enumerate(phases)]
    return kids + [{"name": "lm.dispatch", "span_id": span,
                    "parent_span_id": f"step{i}",
                    "duration": 1.0 + sum(d for _, d, _ in phases)}]


def _ring(monkeypatch, spans):
    fake = type("Ring", (), {"finished_spans": staticmethod(lambda: list(spans))})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: fake)


def test_the_first_dispatch_with_phases_is_read(reader, monkeypatch):
    """The init's programs and a dispatch that compiled nothing come first;
    a later dispatch's trace-cache hit and a second program are not read."""
    spans = [{"name": "jax.trace", "span_id": "i.0", "parent_span_id": "init",
              "duration": 9.0, "fun": "init", "traces": 40},
             {"name": "lm.trainer_init", "span_id": "init",
              "parent_span_id": None, "duration": 12.0}]
    spans += _dispatch(0, [])
    spans += _dispatch(1, [
        ("jax.trace", 0.002, {"fun": "convert_element_type", "traces": 1}),
        ("jax.trace", 6.5, {"fun": "train_step", "traces": 412}),
        ("jax.lower", 2.25, {"fun": "train_step"}),
        ("jax.compile", 3.5, {"fun": "train_step", "cached": True})])
    spans += _dispatch(2, [("jax.trace", 0.0, {"fun": "train_step", "traces": 1}),
                           ("jax.compile", 80.0, {"fun": "train_step",
                                                  "cached": False})])
    _ring(monkeypatch, spans)
    read = lambda **kw: reader.read({}, **kw)  # noqa: E731
    assert read(phase="jax.trace") == pytest.approx(6.5)
    assert read(phase="jax.lower") == pytest.approx(2.25)
    assert read(phase="jax.compile") == pytest.approx(3.5)
    assert read(phase="jax.trace", value="traces") == 412
    assert read(phase="jax.trace", fun="convert_element_type") == pytest.approx(0.002)
    assert read(phase="jax.trace", fun="init") is None  # not the dispatch's


def test_no_phases_give_nothing(reader, monkeypatch):
    # a program that records no compile phases, as the parent commit is
    _ring(monkeypatch, _dispatch(0, []) + _dispatch(1, []))
    for _, phase, value in METRICS.values():
        assert reader.read({}, phase=phase, value=value) is None
    _ring(monkeypatch, [])
    assert reader.read({}, phase="jax.trace") is None
    # a dispatch whose phases are another function's: nothing for the step
    _ring(monkeypatch, _dispatch(0, [("jax.trace", 0.1, {"fun": "add",
                                                          "traces": 1})]))
    assert reader.read({}, phase="jax.trace") is None
    assert reader.read({}, phase="jax.compile", fun="add") is None


def test_manifest_enters_four_metrics_in_two_cells(bench):
    with open(os.path.join(th.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for suffix in ("lm", "nemo3", "ouro"):
        for base, (unit, phase, value) in METRICS.items():
            spec = bench.load_json(os.path.join(
                th.BENCH, "layer_metrics", f"{base}.{suffix}.json"))
            assert spec["reader"] == "compile_phase"
            assert spec["args"]["phase"] == phase
            assert spec["args"].get("value", "duration") == value
            if suffix not in CELLS:
                continue
            m = entries[f"{base}.{suffix}"]
            assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                    m["workloads"]) == (unit, "lower", "program_span", LAYER,
                                        "setup_s", [CELLS[suffix]])
    # the LFM2 cell's test holds its names with ==, the Ouro cell's holds
    # every metric but trainer_init_s.ouro to tokens_per_s: their entries
    # wait for a benchmark PR (PERF.md §7); the .ouro files are there
    assert not {f"{base}.{suffix}" for base in METRICS
                for suffix in ("lfm2", "ouro")} & set(entries)


@pytest.fixture(scope="module")
def tiny_lm_root(tmp_path_factory, manifest):
    """A copy of the benchmark with a tiny LM configuration and cell, joined
    to every metric of the GPT-2 cell."""
    root = str(tmp_path_factory.mktemp("bench_phase_copy"))
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


def test_tiny_traced_lm_cell_prints_the_four_metrics(bench, tiny_lm_root):
    telemetry.configure(enabled=True, reset=True)
    result, _ = th._drive(bench, tiny_lm_root, "tiny_lm.loop", trace=True, seed=5)
    assert result["correct"] is True, result["compared"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    phases = [got[f"{base}.lm"] for base in ("step_trace_s", "step_lower_s",
                                             "step_compile_s")]
    assert all(v > 0 for v in phases)
    assert got["step_traces.lm"] > 1 and got["step_traces.lm"] == int(got["step_traces.lm"])
    assert result["metrics"]["step_traces.lm"]["unit"] == "traces"
    # the three phases lie inside the first step's dispatch
    first = next(s for s in telemetry.get_tracer().finished_spans()
                 if s["name"] == "lm.dispatch")
    assert sum(phases) <= first["duration"]
    assert telemetry.get_tracer().dropped == 0
    telemetry.configure(enabled=True, reset=True)
