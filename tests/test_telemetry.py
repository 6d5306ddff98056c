"""Unified telemetry: metrics registry, cross-backend trace propagation,
per-round phase attribution, exporters, and the mlops observability fixes."""

import json
import threading
import time

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.comm import LoopbackHub, Message
from fedml_tpu.comm.loopback import LoopbackCommManager
from fedml_tpu.core import telemetry
from fedml_tpu.core.mlops import (
    MetricsSink,
    MLOpsProfilerEvent,
    MLOpsRuntimeLog,
    SysStats,
)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled=True, reset=True)
    yield
    telemetry.configure(enabled=True, reset=True)


# --- registry ----------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = telemetry.get_registry()
    reg.counter("c", role="server").inc()
    reg.counter("c", role="server").inc(2)
    assert reg.counter("c", role="server").value == 3
    reg.gauge("g").set(7.5)
    assert reg.gauge("g").value == 7.5
    h = reg.histogram("h")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    assert h.count == 3
    assert h.sum == pytest.approx(0.111)
    assert 0.0005 <= h.quantile(0.5) <= 0.05


def test_registry_kind_mismatch_raises():
    reg = telemetry.get_registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_bucket_merge_across_snapshots():
    """Per-process snapshots must merge: bucket counts/sums add, so a
    multi-host run can aggregate into one registry (ISSUE: mergeable
    across processes)."""
    a = telemetry.MetricsRegistry()
    b = telemetry.MetricsRegistry()
    for reg, vals in ((a, (0.001, 0.02)), (b, (0.001, 0.5, 3.0))):
        h = reg.histogram("lat", phase="agg")
        for v in vals:
            h.observe(v)
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    merged = telemetry.MetricsRegistry()
    merged.merge_snapshot(a.snapshot())
    merged.merge_snapshot(b.snapshot())
    snap = merged.snapshot()
    assert snap["counters"]["n"] == 5
    mh = snap["histograms"]["lat{phase=agg}"]
    assert mh["count"] == 5
    assert mh["sum"] == pytest.approx(3.522)
    # bucket-by-bucket: merged counts are the elementwise sum
    ah = a.snapshot()["histograms"]["lat{phase=agg}"]
    bh = b.snapshot()["histograms"]["lat{phase=agg}"]
    assert mh["counts"] == [x + y for x, y in zip(ah["counts"], bh["counts"])]


def test_histogram_merge_scheme_mismatch_raises():
    a = telemetry.MetricsRegistry()
    a.histogram("h", scheme=telemetry.SECONDS_SCHEME).observe(0.1)
    b = telemetry.MetricsRegistry()
    b.histogram("h", scheme=telemetry.BYTES_SCHEME).observe(100)
    with pytest.raises(ValueError):
        a.merge_snapshot(b.snapshot())


def test_disabled_registry_is_cheap_noop():
    """telemetry_enabled=False must cost ~nothing on hot paths: null
    metrics, no allocation, no span records, unmodified messages."""
    telemetry.configure(enabled=False)
    reg = telemetry.get_registry()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        reg.counter("hot").inc()
        reg.histogram("lat").observe(0.1)
    per_op = (time.perf_counter() - t0) / (2 * n)
    assert per_op < 20e-6  # generous CI bound; measured ~0.1 µs
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    with telemetry.get_tracer().span("s") as ctx:
        assert ctx is None
    assert telemetry.get_tracer().finished_spans() == []
    assert telemetry.new_round_context(0) is None
    msg = Message(1, 0, 1)
    before = dict(msg.get_params())
    telemetry.inject_trace(msg)
    assert msg.get_params() == before


# --- trace propagation -------------------------------------------------------


def test_span_exception_path_records_error_status():
    tracer = telemetry.get_tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("will_fail", round_idx=3):
            raise RuntimeError("boom")
    spans = tracer.finished_spans()
    assert len(spans) == 1
    assert spans[0]["status"] == "error"
    assert spans[0]["round_idx"] == 3
    assert telemetry.current_context() is None  # context restored on raise


def test_trace_survives_message_roundtrip():
    ctx = telemetry.new_round_context(11)
    msg = Message(1, 0, 1)
    with telemetry.use_context(ctx):
        telemetry.inject_trace(msg)
    wire = Message.from_bytes(msg.to_bytes())
    got = telemetry.extract_trace(wire)
    assert got is not None
    assert (got.trace_id, got.round_idx) == (ctx.trace_id, 11)


def test_no_context_leaves_message_unstamped():
    """Handshake/status traffic outside any round must stay byte-identical
    to the pre-telemetry wire format."""
    msg = Message(1, 0, 1)
    before = msg.to_bytes()
    telemetry.inject_trace(msg)
    assert msg.to_bytes() == before
    assert telemetry.extract_trace(msg) is None


def _observed_ctx_roundtrip(make_pair, sender_rank=0, receiver_rank=1):
    """Shared harness: send one message under a fresh round context through
    a backend pair; return (sent ctx, ctx observed inside the receiver's
    observer dispatch)."""
    sender, receiver = make_pair()
    seen = []

    class Obs:
        def receive_message(self, t, msg):
            seen.append(telemetry.current_context())
            receiver.stop_receive_message()

    receiver.add_observer(Obs())
    rx = threading.Thread(target=receiver.handle_receive_message, daemon=True)
    rx.start()
    ctx = telemetry.new_round_context(5)
    with telemetry.use_context(ctx):
        msg = Message(1, sender_rank, receiver_rank)
        msg.add_params("w", np.arange(4, dtype=np.float32))
        sender.send_message(msg)
    rx.join(timeout=10)
    assert not rx.is_alive(), "receiver never saw the message"
    assert len(seen) == 1
    return ctx, seen[0]


def _assert_parity(ctx, got):
    assert got is not None, "receiver dispatched without a trace context"
    assert got.trace_id == ctx.trace_id
    assert got.round_idx == 5


def test_trace_propagation_loopback():
    hub = LoopbackHub()

    def make_pair():
        return (LoopbackCommManager(0, 2, hub=hub),
                LoopbackCommManager(1, 2, hub=hub))

    _assert_parity(*_observed_ctx_roundtrip(make_pair))


def test_trace_propagation_grpc():
    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager

    managers = []

    def make_pair():
        managers.append(GRPCCommManager(rank=0, size=2, base_port=19450))
        managers.append(GRPCCommManager(rank=1, size=2, base_port=19450))
        return managers[0], managers[1]

    try:
        _assert_parity(*_observed_ctx_roundtrip(make_pair))
    finally:
        for m in managers:
            m._server.stop(grace=0)


def test_trace_propagation_mqtt_s3():
    from fedml_tpu.comm.mqtt_s3 import MqttS3CommManager
    from fedml_tpu.comm.pubsub import InProcessBroker
    from fedml_tpu.comm.store import InMemoryBlobStore

    broker, store = InProcessBroker(), InMemoryBlobStore()

    def make_pair():
        server = MqttS3CommManager(broker, store, rank=0, size=2)
        client = MqttS3CommManager(broker, store, rank=1, size=2)
        return server, client

    _assert_parity(*_observed_ctx_roundtrip(make_pair))


def test_trace_propagation_trpc():
    from fedml_tpu.comm.trpc_backend import TRPCCommManager

    managers = []

    def make_pair():
        managers.append(TRPCCommManager(rank=0, size=2, base_port=19470))
        managers.append(TRPCCommManager(rank=1, size=2, base_port=19470))
        return managers[0], managers[1]

    try:
        _assert_parity(*_observed_ctx_roundtrip(make_pair))
    finally:
        for m in managers:
            try:
                m.stop_receive_message()
            except Exception:
                pass


def test_cross_silo_round_trace_parity_and_rtt(monkeypatch):
    """Full loopback deployment: every round's trace_id must be IDENTICAL on
    the server and on every participating client, and the server must have
    recorded per-client round-trip histograms."""
    from fedml_tpu.cross_silo import FedML_Horizontal

    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=4, client_num_per_round=2, comm_round=3,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=1,
        random_seed=0,
    ))
    telemetry.configure(enabled=True, reset=True)
    hub = LoopbackHub()
    server = FedML_Horizontal(args, 0, 2, backend="LOOPBACK", hub=hub)
    clients = [FedML_Horizontal(args, r, 2, backend="LOOPBACK", hub=hub)
               for r in (1, 2)]
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.start()
    server.run()
    for t in threads:
        t.join(timeout=60)
    assert len(server.history) == 3
    assert sorted(server.round_trace_ids) == [0, 1, 2]
    for c in clients:
        for r, tid in c.round_trace_ids.items():
            assert tid == server.round_trace_ids[r], (c.rank, r)
    snap = telemetry.get_registry().snapshot()
    rtt = [k for k in snap["histograms"]
           if k.startswith("fedml_client_round_trip_seconds")]
    assert len(rtt) == 2  # one histogram per client rank
    names = {s["name"] for s in telemetry.get_tracer().finished_spans()}
    assert {"client.train", "server.agg_and_eval"} <= names


# --- simulator phase attribution --------------------------------------------


def test_simulator_phase_breakdown_sums_to_round_time():
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=8, client_num_per_round=4, comm_round=5,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=2,
        random_seed=0,
    ))
    telemetry.configure(enabled=True, reset=True)
    history = fedml_tpu.run_simulation(args=args)
    assert len(history) == 5
    for rec in history:
        phases = rec["phases"]
        assert set(phases) >= {"device", "host_other"}
        total = sum(phases.values())
        # the accumulator drains at the same stamp round_time is taken, so
        # coverage is exact up to clock jitter (ISSUE bound: within 5%)
        assert total == pytest.approx(rec["round_time"], rel=0.05, abs=2e-4)
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"]["fedml_rounds_total"] == 5
    assert any(k.startswith("fedml_round_phase_seconds") for k in
               snap["histograms"])


def test_simulator_phase_clock_is_the_one_timer(monkeypatch):
    """``FedSimulator._phase``: nested phases file exclusive times that sum
    to the outer wall; a handle renamed in the body files under its new
    name; ``host_pack`` is a span and an MLOps event but no share of a
    round; every phase is a ``fedml:`` span when telemetry is on, and
    ``rec["phases"]`` is whole when it is off."""
    from fedml_tpu.simulation import build_simulator

    annotated = []

    class Annotation:
        def __init__(self, name):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry, "_annotation_cls", Annotation)
    events = []
    sink = MetricsSink()
    sink.emit = events.append
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=8, client_num_per_round=4, comm_round=3,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=2,
        random_seed=0, federated_optimizer="SCAFFOLD", prefetch=False,
    ))
    args.profiler = MLOpsProfilerEvent(sink=sink)
    telemetry.configure(enabled=True, reset=True)
    sim, _ = build_simulator(args)

    with sim._phase("eval") as outer:
        with sim._phase("reshard") as inner:
            time.sleep(0.002)
        with sim._phase("state_scatter") as moved:
            moved.name = "state_move"
        with sim._phase("host_pack", "0") as pack:
            time.sleep(0.001)
    with sim._phase("dispatch", "7"):
        pass
    phases = sim._drain_phases()
    assert sim._drain_phases() == {}
    assert set(phases) == {"eval", "reshard", "state_move", "dispatch"}
    assert phases["reshard"] == inner.wall >= 0.002
    assert pack.wall >= 0.001  # timed, and charged to the phase around it
    assert phases["eval"] == pytest.approx(
        outer.wall - inner.wall - moved.wall, abs=1e-12)
    assert phases["eval"] + phases["reshard"] + phases["state_move"] == \
        pytest.approx(outer.wall, abs=1e-12)
    spans = telemetry.get_tracer().finished_spans()
    # the span keeps the name it was entered under; ``dispatch`` is the one
    # phase whose span (and MLOps event) has another name
    assert [s["name"] for s in spans] == [
        "reshard", "state_scatter", "host_pack", "eval", "round_dispatch"]
    assert spans[-1]["value"] == "7" and spans[0]["value"] is None
    assert annotated == ["fedml:" + n for n in (
        "eval", "reshard", "state_scatter", "host_pack", "round_dispatch")]
    assert {e["event"] for e in events} == {"host_pack", "round_dispatch"}

    def run_whole():
        # a simulator a run: what a run times after its last completion
        # stamp (the final eval) stays in the accumulator
        sim, apply_fn = build_simulator(args)
        history = sim.run(apply_fn, log_fn=None)
        for rec in history:
            assert sum(rec["phases"].values()) == pytest.approx(
                rec["round_time"], rel=1e-6, abs=1e-9)
        # over the run (a deferred record holds its successor's dispatch);
        # SCAFFOLD's arena moves are timed inside ``dispatch``
        assert {"pack_wait", "dispatch", "device", "state_gather",
                "state_scatter", "eval", "host_other"} <= {
                    k for rec in history for k in rec["phases"]}

    telemetry.configure(enabled=False, reset=True)
    del annotated[:], events[:]
    run_whole()
    assert telemetry.get_tracer().finished_spans() == [] and annotated == []
    # the MLOps wire does not depend on the tracer: its two events, no other
    assert {e["event"] for e in events} == {"host_pack", "round_dispatch"}

    telemetry.configure(enabled=True, reset=True)
    run_whole()
    names = {s["name"] for s in telemetry.get_tracer().finished_spans()}
    assert {"pack_wait", "round_dispatch", "device", "state_gather",
            "state_scatter", "eval", "host_pack"} <= names
    assert "dispatch" not in names
    # jax's compile phases are recorded after the fact: spans, no annotation
    assert set(annotated) == {"fedml:" + n for n in names
                              if not n.startswith("jax.")}


# --- exporters ---------------------------------------------------------------


def test_prometheus_textfile_format(tmp_path):
    reg = telemetry.get_registry()
    reg.counter("fedml_rounds_total").inc(3)
    reg.gauge("fedml_cpu_utilization").set(12.5)
    reg.histogram("fedml_round_seconds").observe(0.25)
    path = tmp_path / "metrics.prom"
    telemetry.write_prometheus(str(path))
    text = path.read_text()
    assert "# TYPE fedml_rounds_total counter" in text
    assert "fedml_rounds_total 3" in text
    assert "fedml_cpu_utilization 12.5" in text
    assert "# TYPE fedml_round_seconds histogram" in text
    assert 'fedml_round_seconds_bucket{le="+Inf"} 1' in text
    assert "fedml_round_seconds_count 1" in text
    # cumulative buckets: counts are monotone nondecreasing over edges
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("fedml_round_seconds_bucket")]
    assert cums == sorted(cums)


def test_jsonl_sink_and_cli_summary(tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.cli.main import cli

    path = tmp_path / "run.jsonl"
    telemetry.configure(enabled=True, jsonl_path=str(path), reset=True)
    reg = telemetry.get_registry()
    with telemetry.get_tracer().span("server.agg_and_eval", round_idx=0):
        pass
    reg.histogram("fedml_round_phase_seconds", phase="device").observe(0.2)
    reg.counter("fedml_rounds_total").inc()
    telemetry.flush()
    telemetry.configure(enabled=True)  # detach the sink -> closes the file
    kinds = [json.loads(line)["kind"] for line in
             path.read_text().splitlines()]
    assert kinds.count("span") == 1
    assert kinds.count("registry_snapshot") == 1
    result = CliRunner().invoke(cli, ["telemetry", "summary", str(path)])
    assert result.exit_code == 0, result.output
    assert "server.agg_and_eval" in result.output
    assert "fedml_rounds_total = 1" in result.output
    assert "round phase breakdown" in result.output


# --- mlops satellites --------------------------------------------------------


def test_metrics_sink_ring_buffer_drops_oldest():
    sink = MetricsSink(max_records=3)
    for i in range(5):
        sink.emit({"i": i})
    assert len(sink.records) == 3
    assert [r["i"] for r in sink.records] == [2, 3, 4]
    assert sink.dropped_records == 2
    assert sink.records[0]["i"] == 2  # indexing still works (test contract)


def test_runtime_log_rebinds_args_on_every_get_instance():
    class A:
        rank = 0
        run_id = "first"

    class B:
        rank = 3
        run_id = "second"

    inst1 = MLOpsRuntimeLog.get_instance(A())
    inst2 = MLOpsRuntimeLog.get_instance(B())
    assert inst1 is inst2  # still a singleton...
    assert inst2.args.run_id == "second"  # ...but bound to the NEW run


def test_sys_stats_interval_deltas_and_cached_process():
    psutil = pytest.importorskip("psutil")  # noqa: F841
    SysStats._last_counters = None  # isolate from other tests
    s1 = SysStats()
    first = s1.to_dict()
    # first sample has no previous interval: deltas must be 0, not a
    # boot-cumulative lump
    assert first["net_sent_mb"] == 0.0
    assert first["net_recv_mb"] == 0.0
    assert first["interval_s"] == 0.0
    s2 = SysStats()
    assert s2._process is s1._process  # one cached psutil handle per process
    time.sleep(0.05)
    second = SysStats().to_dict()
    assert second["interval_s"] > 0.0
    assert second["net_sent_mb"] >= 0.0
    assert first["host_memory_total_gb"] > 0


def test_profiler_span_emits_ended_event_on_exception():
    sink = MetricsSink()
    ev = MLOpsProfilerEvent(sink=sink)
    with pytest.raises(ValueError):
        with ev.span("agg", event_value="r0"):
            raise ValueError("mid-span failure")
    kinds = [r["kind"] for r in sink.records]
    assert kinds == ["event_started", "event_ended"]
    assert ev._open_events == {}  # no dangling open span


def test_device_trace_start_failure_leaves_no_dangling_span(monkeypatch):
    import jax

    sink = MetricsSink()
    ev = MLOpsProfilerEvent(sink=sink)

    def boom(_dir):
        raise RuntimeError("trace already active")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="trace already active"):
        with ev.device_trace("/tmp/nowhere"):
            pass
    assert len(sink.records) == 0  # start failed BEFORE the started event
    assert ev._open_events == {}


# --- jax's compile phases ------------------------------------------------------


def _jax_phases():
    return [s for s in telemetry.get_tracer().finished_spans()
            if s["name"].startswith("jax.")]


def _programs():
    return {k: v for k, v in telemetry.get_registry().snapshot()[
        "counters"].items() if k.startswith("fedml_jax_programs_total")}


def _fresh_jit(scale):
    """A jitted function no earlier call has traced, with a jitted inner
    function traced inside it (each call of this makes new functions)."""
    import jax

    @jax.jit
    def inner(x):
        return x * scale

    def outer_fn(x):
        return inner(x) + 1.0

    return jax.jit(outer_fn)


def test_first_call_under_a_span_leaves_its_three_phases_as_children():
    import jax.numpy as jnp

    assert telemetry.install_jax_collectors()
    f = _fresh_jit(3.0)
    x = jnp.ones((4, 8), jnp.float32)  # made before the span: not its child
    telemetry.configure(enabled=True, reset=True)
    tracer = telemetry.get_tracer()
    with tracer.span("lm.dispatch") as ctx:
        f(x).block_until_ready()
    phases = _jax_phases()
    assert [s["name"] for s in phases if s["fun"] == "outer_fn"] == [
        "jax.trace", "jax.lower", "jax.compile"]
    for s in phases:
        assert s["parent_span_id"] == ctx.span_id
        assert s["trace_id"] == ctx.trace_id and s["status"] == "ok"
        assert s["duration"] >= 0
    trace = next(s for s in phases if s["name"] == "jax.trace")
    # the inner jit's trace and jnp's own jits are counted, not recorded
    assert trace["traces"] >= 2
    assert [s["name"] for s in phases].count("jax.trace") == 1
    compile_ = next(s for s in phases if s["name"] == "jax.compile")
    assert compile_["cached"] is False
    assert _programs() == {"fedml_jax_programs_total{source=compiled}": 1.0}
    span = tracer.finished_spans()[-1]
    assert span["name"] == "lm.dispatch"
    assert sum(s["duration"] for s in phases) <= span["duration"]
    # the second call finds the program: no phase, no program
    tracer.clear()
    with tracer.span("lm.dispatch"):
        f(x).block_until_ready()
    assert _jax_phases() == []
    assert _programs() == {"fedml_jax_programs_total{source=compiled}": 1.0}


def test_a_program_from_the_persistent_cache_reads_cached(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    telemetry.install_jax_collectors()
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        x = jnp.arange(16.0)
        tracer = telemetry.get_tracer()
        with tracer.span("lm.dispatch"):
            _fresh_jit(5.0)(x).block_until_ready()
        jax.clear_caches()
        tracer.clear()
        with tracer.span("lm.dispatch"):
            _fresh_jit(5.0)(x).block_until_ready()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    [compile_] = [s for s in _jax_phases() if s["name"] == "jax.compile"]
    assert compile_["fun"] == "outer_fn" and compile_["cached"] is True
    assert _programs()["fedml_jax_programs_total{source=cache}"] == 1.0


def test_a_compile_outside_any_span_is_counted_and_not_recorded():
    import jax.numpy as jnp

    telemetry.install_jax_collectors()
    assert telemetry.current_context() is None
    _fresh_jit(7.0)(jnp.ones(3)).block_until_ready()
    assert _jax_phases() == []
    assert _programs()["fedml_jax_programs_total{source=compiled}"] >= 1.0


def test_disabled_telemetry_records_and_counts_no_phase():
    import jax.numpy as jnp

    telemetry.install_jax_collectors()
    telemetry.configure(enabled=False)
    with telemetry.get_tracer().span("lm.dispatch"):
        _fresh_jit(9.0)(jnp.ones(3)).block_until_ready()
    telemetry.configure(enabled=True)
    assert telemetry.get_tracer().finished_spans() == []
    assert _programs() == {}


def test_record_lands_as_a_child_and_costs_under_20us():
    """``Tracer.record`` after the fact: the best of twelve batches of 500,
    so that a loaded machine does not decide it."""
    tracer = telemetry.get_tracer()
    with tracer.span("lm.dispatch") as ctx:
        tracer.record("jax.trace", 100.0, 100.5, fun="f", traces=3)
    rec = tracer.finished_spans()[0]
    assert rec["name"] == "jax.trace" and rec["parent_span_id"] == ctx.span_id
    assert (rec["start"], rec["duration"], rec["fun"], rec["traces"]) == (
        100.0, 0.5, "f", 3)
    hist = telemetry.get_registry().snapshot()["histograms"]
    assert hist["fedml_span_seconds{span=jax.trace}"]["count"] == 1
    best = float("inf")
    for _ in range(12):
        tracer.clear()
        with tracer.span("lm.dispatch"):
            t0 = time.perf_counter()
            for _ in range(500):
                tracer.record("jax.trace", 100.0, 100.5, fun="f")
            best = min(best, (time.perf_counter() - t0) / 500)
    assert best < 20e-6, f"{best * 1e6:.1f} us a record"
