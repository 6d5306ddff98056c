"""The LM step inside the program's span system and on a profile's clock:
host spans and counters of ``DistributedLMTrainer``, the ``fedml:<name>``
annotations every span leaves in a jax profile, the scopes that put every op
of the compiled step in one class, and the cost of it all with no profiler
session."""

from __future__ import annotations

import glob
import importlib.util
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry
from fedml_tpu.parallel.trainer import DistributedLMTrainer, DistTrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T = 4, 32
STEP_CHILDREN = ["lm.input_put", "lm.dispatch", "lm.loss_wait"]
INIT_CHILDREN = ["lm.init_params", "lm.opt_init", "lm.build_step"]


def _trainer(**cfg):
    return DistributedLMTrainer(
        DistTrainConfig(use_remat=True, remat_policy="full", **cfg),
        vocab_size=97, dim=64, num_heads=4, num_layers=2, max_len=T,
        dtype=jnp.float32)


@pytest.fixture(scope="module")
def built():
    """One tiny trainer, with the spans its construction left and those of
    its first three steps (a list a step): every later step is warm."""
    telemetry.configure(enabled=True, reset=True)
    trainer = _trainer()
    init = telemetry.get_tracer().finished_spans()
    steps = []
    for _ in range(3):
        telemetry.configure(enabled=True, reset=True)
        trainer.step(*_batch())
        steps.append(telemetry.get_tracer().finished_spans())
    return trainer, init, steps


@pytest.fixture
def trainer(built):
    telemetry.configure(enabled=True, reset=True)
    yield built[0]
    telemetry.configure(enabled=True, reset=True)


@pytest.fixture(scope="module")
def scope_reduce():
    bench_dir = os.path.join(ROOT, "benchmark")
    if bench_dir not in sys.path:  # scope_reduce imports trace_reduce beside it
        sys.path.insert(0, bench_dir)
    spec = importlib.util.spec_from_file_location(
        "bench_scope_reduce", os.path.join(bench_dir, "scope_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch():
    ids = np.random.default_rng(0).integers(0, 97, (B, T + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _family(spans, parent_name, child_names):
    """The one parent span and its children, checked for shape."""
    parents = [s for s in spans if s["name"] == parent_name]
    assert len(parents) == 1
    parent = parents[0]
    children = [s for s in spans if s["parent_span_id"] == parent["span_id"]]
    assert [c["name"] for c in children] == child_names
    assert {c["trace_id"] for c in children} == {parent["trace_id"]}
    assert sum(c["duration"] for c in children) <= parent["duration"]
    return parent, children


def _profile_host_events(trace_dir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith(telemetry.PROFILE_PREFIX)]


def test_step_leaves_one_span_with_three_children(trainer):
    loss = trainer.step(*_batch())
    assert np.isfinite(loss)
    spans = telemetry.get_tracer().finished_spans()
    assert len(spans) == 4
    parent, _ = _family(spans, "lm.step", STEP_CHILDREN)
    assert parent["parent_span_id"] is None


def test_init_leaves_one_span_with_three_children(built):
    _family([s for s in built[1] if not s["name"].startswith("jax.")],
            "lm.trainer_init", INIT_CHILDREN)


def _phases_of_dispatch(spans):
    [dispatch] = [s for s in spans if s["name"] == "lm.dispatch"]
    return [(s["name"], s["fun"]) for s in spans
            if s["parent_span_id"] == dispatch["span_id"]], dispatch


def test_first_dispatch_holds_the_steps_trace_lowering_and_compile(built):
    """jax's compile phases as children of the first step's ``lm.dispatch``;
    the init's jitted and eager programs under the init's spans."""
    first, dispatch = _phases_of_dispatch(built[2][0])
    assert first == [("jax.trace", "train_step"), ("jax.lower", "train_step"),
                     ("jax.compile", "train_step")]
    phases = [s for s in built[2][0] if s["name"].startswith("jax.")]
    assert phases[0]["traces"] > 1 and phases[2]["cached"] is False
    assert sum(s["duration"] for s in phases) <= dispatch["duration"]
    # the second step's arguments differ from the first's in how the moments'
    # sharding is spelled (one device: the same placement), so jax looks the
    # step up once more: a hit of its trace cache, no lowering, no compile
    second, _ = _phases_of_dispatch(built[2][1])
    assert second in ([], [("jax.trace", "train_step")])
    assert _phases_of_dispatch(built[2][2])[0] == []
    init_ids = {s["span_id"] for s in built[1] if s["name"] in INIT_CHILDREN}
    assert {s["name"] for s in built[1] if s["name"].startswith("jax.")
            and s["parent_span_id"] in init_ids} == {
                "jax.trace", "jax.lower", "jax.compile"}


def test_counters_advance_by_one_step_and_its_tokens(trainer):
    reg = telemetry.get_registry()
    for n in (1, 2):
        trainer.step(*_batch())
        assert reg.counter("fedml_lm_steps_total").value == n
        assert reg.counter("fedml_lm_tokens_total").value == n * B * T


def test_disabled_telemetry_records_nothing_and_annotates_nothing(
        trainer, monkeypatch):
    made = []
    monkeypatch.setattr(telemetry, "_annotation_cls",
                        lambda name: made.append(name))
    telemetry.configure(enabled=False)
    loss = trainer.step(*_batch())
    assert np.isfinite(loss)
    assert telemetry.get_tracer().finished_spans() == [] and made == []
    assert telemetry.get_registry().snapshot()["counters"] == {}


def test_spans_record_where_jax_is_absent(monkeypatch):
    telemetry.configure(enabled=True, reset=True)
    monkeypatch.setattr(telemetry, "_annotation_cls", False)
    with telemetry.get_tracer().span("lm.step"):
        pass
    assert [s["name"] for s in telemetry.get_tracer().finished_spans()] == ["lm.step"]


def test_profile_holds_the_steps_spans_on_its_clock(trainer, tmp_path):
    trainer.step(*_batch())  # compiled before the session
    with jax.profiler.trace(str(tmp_path)):
        trainer.step(*_batch())
    events = _profile_host_events(str(tmp_path))
    by_name = {name: (s, e) for name, s, e in events}
    assert set(by_name) == {"fedml:" + n for n in ["lm.step"] + STEP_CHILDREN}
    s0, e0 = by_name["fedml:lm.step"]
    inner = [by_name["fedml:" + n] for n in STEP_CHILDREN]
    assert all(s0 <= s and e <= e0 for s, e in inner)
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))  # in order


def test_cross_silo_span_lands_in_a_profile_too(tmp_path):
    from fedml_tpu.comm.message import compress_tree
    from fedml_tpu.cross_silo.aggregator import FedMLAggregator

    telemetry.configure(enabled=True, reset=True)
    frame = compress_tree({"w": np.ones((8, 8), np.float32)})
    with jax.profiler.trace(str(tmp_path)):
        FedMLAggregator._decode_upload(frame, 3)
    assert "fedml:codec.decode" in [n for n, _, _ in
                                    _profile_host_events(str(tmp_path))]


@pytest.mark.parametrize("ce_chunk", [0, 16], ids=["full_logits", "ce_chunk"])
def test_every_op_of_the_compiled_step_falls_in_one_class(
        built, scope_reduce, ce_chunk):
    t = built[0] if ce_chunk == 0 else _trainer(ce_chunk=ce_chunk)
    tokens = jnp.zeros((B, T), jnp.int32)
    text = t._train_step.lower(
        t.params, t.opt_state, t.constants, tokens, tokens).compile().as_text()
    by_class = {c: set() for c in scope_reduce.CLASSES}
    for line in text.splitlines():
        named = re.search(r'op_name="([^"]*)"', line)
        opcode = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if named and opcode:
            by_class[scope_reduce.classify(named.group(1))].add(
                (opcode.group(1), named.group(1)))
    for cls in scope_reduce.CLASSES[:-1]:
        assert by_class[cls], f"no op of the compiled step is in {cls}"
    # what the scopes leave over: arguments and the bodies of reducers,
    # no arithmetic of the model
    assert not [op for op, _ in by_class["other"]
                if op in ("dot", "convolution")]
    assert not [name for _, name in by_class["other"]
                if name.startswith("jit(train_step)")]
    optimizer = {name for _, name in by_class["optimizer"]}
    assert all(name.startswith("jit(train_step)/lm.optimizer/")
               for name in optimizer)
    assert any("jvp(lm.loss)" in name for _, name in by_class["head_ce"])
    assert any("transpose(jvp(lm.loss))" in name
               for _, name in by_class["head_ce"])


def test_fed_round_stages_are_scoped_and_its_host_phases_are_spans():
    import fedml_tpu

    telemetry.configure(enabled=True, reset=True)
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=8, client_num_per_round=4, comm_round=2,
        learning_rate=0.1, epochs=1, batch_size=10, backend="sp",
        frequency_of_the_test=10, sanitize_updates=True, comm_codec="q8"))
    from fedml_tpu.simulation import build_simulator

    sim, _ = build_simulator(args)
    real, shapes = sim._round_step, []

    def spy(*step_args):  # the shapes first: the step donates its arguments
        shapes.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), step_args))
        return real(*step_args)

    sim._round_step = spy
    sim.run(apply_fn=None, log_fn=None)
    names = {s["name"] for s in telemetry.get_tracer().finished_spans()}
    assert {"host_pack", "round_dispatch"} <= names
    text = real.lower(*shapes[0]).compile().as_text()
    for scope in ("fed.local_update", "fed.codec", "fed.sanitize",
                  "fed.aggregate", "fed.server_update"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    telemetry.configure(enabled=True, reset=True)


def test_span_group_of_a_step_costs_under_100us_without_a_session():
    """Four spans and two counters, as one ``step()`` makes them: the best of
    twelve batches of 250, so that a loaded machine does not decide it (the
    best of three thousands read 119 us once beside five busy workers, 38
    to 65 alone)."""
    telemetry.configure(enabled=True, reset=True)
    tracer, reg = telemetry.get_tracer(), telemetry.get_registry()
    best = float("inf")
    for _ in range(12):
        tracer.clear()
        t0 = time.perf_counter()
        for _ in range(250):
            with tracer.span("lm.step"):
                for name in STEP_CHILDREN:
                    with tracer.span(name):
                        pass
            reg.counter("fedml_lm_steps_total").inc()
            reg.counter("fedml_lm_tokens_total").inc(8192)
        best = min(best, (time.perf_counter() - t0) / 250)
    telemetry.configure(enabled=True, reset=True)
    assert best < 100e-6, f"{best * 1e6:.1f} us a step"
