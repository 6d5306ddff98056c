"""The benchmark's own tests (CPU, tier 1): the manifest and the files it
names, the data-driven pick-up of a new configuration, cell and metric, the
refusal to run without a chip, the operation counts, the trace reduction on
a recorded trace, and a tiny-size rehearsal of both runners' windows and
comparisons - sound, with the control in the program's place, and with the
timed path broken underneath."""

from __future__ import annotations

import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LM_CELL, FED_CELL = "gpt2m_t1024_b8_pretrain", "fc100_r18gn_c48_fedavg"

# float32 against float32: rounding alone, measured on the CPU at these
# sizes and seeds (lm 1e-7 / 3e-7 / 7e-6; fed 2e-7 / 4e-5 / 2e-4), so the
# bfloat16 control (lm 9e-6 / 3e-4 / 7e-4; fed 3e-3 / 5e-2 / 0.13) fails
TINY_LIMITS = {
    "lm": {"loss_gap": 2e-6, "grad_gap": 3e-5, "change_gap": 2e-4},
    "fed": {"loss_gap": 1e-4, "grad_gap": 2e-3, "change_gap": 5e-3}}
TINY = {
    "lm": {
        "config": ("gpt2_medium", dict(
            n_embd=64, n_layer=2, n_head=4, n_positions=32, n_ctx=32,
            vocab_size=97, compute_dtype="float32", limits=TINY_LIMITS["lm"])),
        "traffic": dict(kind="closed_loop_steps", batch=4, seq_len=32,
                        token_pool_batches=8, check_steps=3,
                        trace_start_s=0.1, trace_slice_s=0.2),
        "end_to_end": {"tokens_per_s": "tokens/s", "step_ms_p90": "ms",
                       "setup_s": "s"},
        "per_layer": {"device_idle_share.lm": "%", "step_mfu.lm": "%",
                      "compiles_in_window.lm": "programs"}},
    "fed": {
        "config": ("fedcifar100_resnet18gn", dict(
            image_size=8, train_examples=64, client_num_in_total=8,
            examples_per_client=8, batch_size=4, compute_dtype="float32",
            learning_rate=0.01, limits=TINY_LIMITS["fed"])),
        "traffic": dict(kind="closed_loop_rounds", client_num_per_round=4,
                        max_rounds=6, check_steps=3, trace_start_s=0.1,
                        trace_slice_s=0.2),
        "end_to_end": {"rounds_per_s": "rounds/s", "step_ms_p90": "ms",
                       "setup_s": "s"},
        "per_layer": {"device_idle_share.fed": "%", "round_mfu.fed": "%",
                      "dispatch_ms.fed": "ms", "pack_wait_ms.fed": "ms",
                      "compiles_in_window.fed": "programs"}},
}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load(os.path.join(BENCH, "run.py"), "bench_run")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A copy of the benchmark with a tiny configuration, cell and per-layer
    metric of each kind ADDED as files and manifest entries: no file that the
    benchmark has is edited."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"))
    before = _tree(os.path.join(root, "benchmark"))
    m = json.loads(json.dumps(manifest))
    for kind, spec in TINY.items():
        base, changes = spec["config"]
        with open(os.path.join(BENCH, "configs", base + ".json")) as f:
            cfg = dict(json.load(f), name="tiny_" + kind, **changes)
        _write(root, f"benchmark/configs/tiny_{kind}.json", cfg)
        _write(root, f"benchmark/traffic/tiny_{kind}_loop.json", spec["traffic"])
        cell = f"tiny_{kind}.loop"
        m["configs"].append({"name": "tiny_" + kind, "source": "test",
                             "file": f"benchmark/configs/tiny_{kind}.json",
                             "reduced": [], "why": "test"})
        m["workloads"].append({"name": cell, "config": "tiny_" + kind,
                               "traffic": f"tiny_{kind}_loop", "chips": 1,
                               "why": "test"})
        # the cell joins the metrics it reports: an entry that is there gets
        # the cell's name, one that is not is added (its reader's file is)
        for group in ("end_to_end", "per_layer"):
            have = {e["name"]: e for e in m[group]}
            for name, unit in spec[group].items():
                if name in have:
                    if "workloads" in have[name]:
                        have[name]["workloads"].append(cell)
                    continue
                entry = {"name": name, "unit": unit, "better": "higher",
                         "source": "host_clock", "workloads": [cell]}
                if group == "end_to_end":
                    entry["bound"] = 0.01
                else:
                    entry.update(layer="test", moves=next(iter(spec["end_to_end"])))
                m[group].append(entry)
        # a per-layer metric of its own: a data file naming a reader
        metric = f"host_other_ms.tiny_{kind}"
        _write(root, f"benchmark/layer_metrics/{metric}.json",
               {"reader": "phase_mean", "args": {"phase": "host_other"}})
        m["per_layer"].append({
            "name": metric, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "test",
            "moves": "step_ms_p90", "workloads": [cell]})
    _write(root, "BENCHMARK.json", m)
    after = _tree(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before
    return root


def _write(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)


def _tree(top):
    out = {}
    for d, _, files in os.walk(top):
        for name in files:
            with open(os.path.join(d, name), "rb") as f:
                out[os.path.relpath(os.path.join(d, name), top)] = f.read()
    return out


def _drive(bench, root, cell_name, trace=False, seconds=0.6, seed=3_000_000_123):
    """The rest of a run after the harness's look for a chip."""
    cell = bench.load_cell(root, cell_name)
    peaks = bench.load_json(os.path.join(cell.bench_dir, "peaks.json"))
    out, err = io.StringIO(), io.StringIO()
    rc = bench.run_cell(cell, seed, seconds, trace,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        peaks["TPU v5 lite"], jax.devices()[:1], out=out, err=err)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


# --- the manifest and the files it names ---------------------------------

def test_manifest_names_units_and_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for group in (manifest["configs"], manifest["workloads"], metrics):
        assert len({e["name"] for e in group}) == len(group)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert {"tokens_per_s", "step_ms_p90", "setup_s"} <= set(e2e)
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    cells = {w["name"] for w in manifest["workloads"]}
    assert LM_CELL in cells and cells <= {LM_CELL, FED_CELL}
    assert all(w["chips"] == 1 and len(w["why"]) <= 200
               for w in manifest["workloads"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for w in m["workloads"]:  # the cell reports what the metric moves
            assert w in e2e[m["moves"]].get("workloads", cells)
    assert any("mfu" in m["name"].split(".")[0].split("_")
               for m in manifest["per_layer"])


def test_every_cell_resolves_its_files_by_name(bench, manifest):
    for w in manifest["workloads"]:
        cell = bench.load_cell(ROOT, w["name"])
        assert cell.config["runner"] in ("lm_step", "fed_round")
        assert os.path.isfile(os.path.join(
            BENCH, "runners", cell.config["runner"] + ".py"))
        assert set(cell.config["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
        assert cell.traffic["check_steps"] == 3
        assert [m["name"] for m in cell.end_to_end if m["name"] == "setup_s"]
        for m in cell.per_layer:
            spec = bench.load_json(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".json"))
            assert os.path.isfile(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    for c in manifest["configs"]:
        cfg = bench.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] == c["source"]


def test_new_config_cell_and_metric_are_picked_up_without_an_edit(bench, tiny_root):
    for kind in TINY:
        cell = bench.load_cell(tiny_root, f"tiny_{kind}.loop")
        assert cell.config["name"] == "tiny_" + kind
        assert cell.bench_dir == os.path.join(tiny_root, "benchmark")
        assert f"host_other_ms.tiny_{kind}" in [m["name"] for m in cell.per_layer]
    with pytest.raises(SystemExit):
        bench.load_cell(tiny_root, "no_such_cell")


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", LM_CELL,
         "--seed", "4123456789", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""  # no result line: there is no CPU fallback
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("correct: false") and "no TPU" in last and "cpu" in last


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", LM_CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "correct: false" in p.stderr


# --- the yardstick's arithmetic -------------------------------------------

def test_flops_reproduce_the_hand_counts(bench):
    flops = _load(os.path.join(BENCH, "flops.py"), "bench_flops")
    lm = bench.load_cell(ROOT, LM_CELL)
    # blocks: 24 x 12 x 1024^2 = 301,989,888; head: 1024 x 50257 = 51,463,168
    # 6 x 353,453,056 + attention 6 x 1024 x 1024 x 24 = 150,994,944
    assert flops.lm_train_flops_per_token(lm.config, lm.traffic) == 2_271_713_280
    fed = bench.types.SimpleNamespace(
        config=bench.load_json(os.path.join(
            BENCH, "configs", "fedcifar100_resnet18gn.json")),
        traffic=bench.load_json(os.path.join(BENCH, "traffic", "c48_fedavg.json")))
    # stem 32x32x27x64 = 1,769,472; stage 1 four 3x3 convs at 64 channels,
    # 150,994,944; stages 2-4 each 18,874,368 + 3 x 37,748,736 + a 1x1
    # projection 2,097,152 = 134,217,728; head 51,200 (3.33 GFLOP a sample)
    macs = flops.resnet18_forward_macs_per_sample(fed.config)
    assert macs == 555_468_800
    assert flops.resnet18_train_flops_per_sample(fed.config, fed.traffic) == 6 * macs
    peaks = bench.load_json(os.path.join(BENCH, "peaks.json"))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


def test_compare_worst_leaf_and_dead_leaves():
    compare = _load(os.path.join(BENCH, "compare.py"), "bench_compare")
    ref = {"loss": [4.0, 3.9, 3.8],
           "grad1": {"a": 1.0, "b": 2.0, "c": 3.0, "dead": 1e-9},
           "change": {"a": 0.1, "b": 0.2, "c": 0.3, "dead": 0.05}}
    prog = json.loads(json.dumps(ref))
    limits = TINY_LIMITS["fed"]
    ok, compared = compare.decide(prog, ref, limits)
    assert ok and all(c["value"] == 0 for c in compared.values())
    prog["change"]["dead"] = 0.5  # moves by round-off alone: left out
    assert compare.decide(prog, ref, limits)[0]
    prog["change"]["b"] = 0.4  # moved double
    ok, compared = compare.decide(prog, ref, limits)
    assert not ok and compared["change_gap"]["value"] == pytest.approx(1.0)
    assert compared["change_gap"]["at"] == "b"
    prog = json.loads(json.dumps(ref))
    prog["change"] = {k: 0.0 for k in ref["change"]}  # a state left unchanged
    assert compare.decide(prog, ref, limits)[1]["change_gap"]["value"] == 1.0
    prog["loss"] = [4.0, float("nan"), 3.8]
    assert not compare.decide(prog, ref, limits)[0]


def test_trace_reduce_repeats_on_the_recorded_trace():
    trace_reduce = _load(os.path.join(BENCH, "trace_reduce.py"), "bench_trace_reduce")
    path = os.path.join(BENCH, "testdata", "lm_slice.xplane.pb")
    assert os.path.getsize(path) < 600_000
    with open(os.path.join(BENCH, "testdata", "lm_slice.expected.json")) as f:
        expected = json.load(f)
    got = trace_reduce.reduce_file(path)
    assert got == expected  # busy union, idle share, top operations: exactly
    assert got == trace_reduce.reduce_file(path)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_trace_reduce_union_counts_overlap_once():
    trace_reduce = _load(os.path.join(BENCH, "trace_reduce.py"), "bench_trace_reduce")
    assert trace_reduce._union([(0, 10), (5, 12), (20, 30), (22, 25)]) == [
        [0, 12], [20, 30]]


# --- tiny-size rehearsal of both runners, windows and comparisons ---------

@pytest.mark.parametrize("kind", ["lm", "fed"])
def test_tiny_cell_runs_its_window_and_is_correct(bench, tiny_root, kind):
    result, err = _drive(bench, tiny_root, f"tiny_{kind}.loop")
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    rate = "tokens_per_s" if kind == "lm" else "rounds_per_s"
    assert set(result["metrics"]) == {rate, "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for name, limit in TINY_LIMITS[kind].items():
        assert result["compared"][name]["limit"] == limit
        assert 0 <= result["compared"][name]["value"] <= limit
    tail = err.strip().splitlines()
    assert tail[-1] == "correct: true" and tail[-2].startswith("compared ")


@pytest.mark.parametrize("kind", ["lm", "fed"])
def test_tiny_traced_run_reports_layer_metrics(bench, tiny_root, kind):
    result, _ = _drive(bench, tiny_root, f"tiny_{kind}.loop", trace=True, seed=7)
    assert result["correct"] is True, result["compared"]
    got = set(result["metrics"])
    mfu = "step_mfu.lm" if kind == "lm" else "round_mfu.fed"
    assert {mfu, f"compiles_in_window.{kind}"} <= got
    assert result["metrics"][f"compiles_in_window.{kind}"]["value"] == 0
    assert 0 < result["metrics"][mfu]["value"] < 100
    # the CPU's trace has no device plane: that reader returns nothing and
    # the metric is left out, never reported as 0
    assert f"device_idle_share.{kind}" not in got
    if kind == "fed":
        assert {"dispatch_ms.fed", "pack_wait_ms.fed",
                "host_other_ms.tiny_fed"} <= got
    else:  # the LM runner has no host phases: nothing to read
        assert "host_other_ms.tiny_lm" not in got


@pytest.mark.parametrize("kind,fault", [("lm", "half_batch"), ("fed", "half_cohort")])
def test_control_and_planted_fault_come_out_not_correct(bench, tiny_root, kind, fault):
    """The control: the reference in the program's place, one precision below
    the tiny configuration's float32. And the runner's planted fault."""
    cell = bench.load_cell(tiny_root, f"tiny_{kind}.loop")
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    for seed in (11, 2_200_000_022, 3_300_000_033):
        ctx = bench.types.SimpleNamespace(
            config=cell.config, traffic=cell.traffic, seed=seed, chips=1)
        ref = runner.reference(ctx)
        assert compare.decide(ref, ref, cell.config["limits"])[0]
        ok, compared = compare.decide(
            runner.reference(ctx, compute="bf16"), ref, cell.config["limits"])
        assert not ok, compared
        ok, compared = compare.decide(
            runner.reference(ctx, **runner.FAULTS[fault]), ref,
            cell.config["limits"])
        assert not ok and compared["grad_gap"]["value"] > 0.05, compared


def _lm_state_unchanged(monkeypatch):
    from fedml_tpu.parallel.trainer import DistributedLMTrainer as T
    step = T.step

    def broken(self, tokens, targets):
        kept = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        loss = step(self, tokens, targets)
        self.params, self.opt_state = kept
        return loss
    monkeypatch.setattr(T, "step", broken)


def _lm_half_batch(monkeypatch):
    from fedml_tpu.parallel.trainer import DistributedLMTrainer as T
    step = T.step
    monkeypatch.setattr(T, "step", lambda self, tokens, targets: step(
        self, tokens[: len(tokens) // 2], targets[: len(targets) // 2]))


def _fed_state_unchanged(monkeypatch):
    from fedml_tpu.simulation.fed_sim import FedSimulator as S
    dispatch = S._dispatch_even

    def broken(self, inputs, step_rng):
        kept = jax.tree.map(jnp.copy, (self.params, self.server_state))
        metrics = dispatch(self, inputs, step_rng)
        self.params, self.server_state = kept
        return metrics
    monkeypatch.setattr(S, "_dispatch_even", broken)


def _fed_half_cohort(monkeypatch):
    from fedml_tpu.simulation.fed_sim import FedSimulator as S
    build = S.build_round_inputs
    monkeypatch.setattr(S, "build_round_inputs", lambda self, round_idx, exclude=None: build(
        self, round_idx, exclude=set(range(self.cfg.client_num_per_round // 2))))


@pytest.mark.parametrize("kind,breakage", [
    ("lm", _lm_state_unchanged), ("lm", _lm_half_batch),
    ("fed", _fed_state_unchanged), ("fed", _fed_half_cohort)],
    ids=["lm-state_unchanged", "lm-half_batch",
         "fed-state_unchanged", "fed-half_cohort"])
def test_a_broken_timed_path_reads_not_correct(bench, tiny_root, monkeypatch,
                                                kind, breakage):
    breakage(monkeypatch)
    result, err = _drive(bench, tiny_root, f"tiny_{kind}.loop", seed=2_900_000_029)
    assert result["correct"] is False, result["compared"]
    assert result["attempted"] >= 1  # the window still ran: only correct fails
    over = [n for n, c in result["compared"].items() if c["value"] > c["limit"]]
    assert over, result["compared"]
    assert err.strip().splitlines()[-1] == "correct: false"
