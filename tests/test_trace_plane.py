"""Trace plane: span shipping & assembly, Perfetto export, flight recorder,
and phase-anomaly detection (PR 10 acceptance)."""

import glob
import json
import os
import threading

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.comm import LoopbackHub, Message
from fedml_tpu.comm.loopback import LoopbackCommManager
from fedml_tpu.core import telemetry, trace_plane


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled=True, reset=True)
    yield
    telemetry.configure(enabled=True, reset=True)


# --- packing -----------------------------------------------------------------


def _mkspan(i, rank=1, round_idx=3):
    return {"kind": "span", "name": f"s{i}", "trace_id": "t1",
            "span_id": f"sp{i}", "parent_span_id": None,
            "round_idx": round_idx, "start": 100.0 + i, "duration": 0.5,
            "status": "ok", "rank": rank}


def test_pack_spans_caps_and_drop_order():
    spans = [_mkspan(i) for i in range(10)]
    payload, shipped, dropped = trace_plane.pack_spans(spans, 4, 1 << 20)
    assert (shipped, dropped) == (4, 6)
    got = trace_plane.unpack_spans(payload, origin_rank=1)
    # oldest dropped first: the newest spans are the round being shipped
    assert [r["name"] for r in got] == ["s6", "s7", "s8", "s9"]

    payload, shipped, dropped = trace_plane.pack_spans(spans, 256, 200)
    assert payload is not None and len(payload) <= 200
    assert shipped + dropped == 10

    payload, shipped, dropped = trace_plane.pack_spans(spans, 256, 1)
    assert payload is None and shipped == 0 and dropped == 10


def test_unpack_stamps_origin_rank():
    payload, _, _ = trace_plane.pack_spans(
        [dict(_mkspan(0), rank=99)], 16, 1 << 20)
    got = trace_plane.unpack_spans(payload, origin_rank=4)
    # the wire sender is authoritative — a span can't lie about its origin
    assert got[0]["rank"] == 4 and got[0]["shipped"] is True


# --- disabled-path wire parity ----------------------------------------------


def test_disabled_plane_leaves_message_byte_identical():
    msg = Message(1, 1, 0)
    msg.add_params("w", np.arange(4, dtype=np.float32))
    before = msg.to_bytes()
    assert not trace_plane.active()
    trace_plane.attach_spans(msg, 0, 1)
    trace_plane.attach_clock(msg)
    assert msg.to_bytes() == before

    trace_plane.configure(ship_spans=True)
    with telemetry.get_tracer().span("client.train", round_idx=0, rank=1):
        pass
    trace_plane.attach_spans(msg, 0, 1)
    trace_plane.attach_clock(msg)
    assert trace_plane.SPANS_KEY in msg.msg_params
    assert trace_plane.CLOCK_KEY in msg.msg_params
    assert msg.to_bytes() != before


def test_configure_unknown_key_raises():
    with pytest.raises(TypeError):
        trace_plane.configure(flght_recorder=True)


# --- span shipping parity across all four backends ---------------------------


def _client_round_spans(round_idx=3, rank=1):
    """One client round: train span with a nested step span, rank-attributed."""
    ctx = telemetry.new_round_context(round_idx)
    with telemetry.use_context(ctx):
        with telemetry.get_tracer().span("client.train", rank=rank):
            with telemetry.get_tracer().span("client.step", rank=rank):
                pass
    return ctx


def _ship_roundtrip(make_pair):
    """Ship one client round's spans through a backend pair; return the
    assembler signature of the ingested round tree."""
    trace_plane.configure(ship_spans=True)
    ctx = _client_round_spans()
    sender, receiver = make_pair()
    seen = []

    class Obs:
        def receive_message(self, t, msg):
            seen.append(msg)
            receiver.stop_receive_message()

    receiver.add_observer(Obs())
    rx = threading.Thread(target=receiver.handle_receive_message, daemon=True)
    rx.start()
    msg = Message(1, 1, 0)
    msg.add_params("w", np.arange(4, dtype=np.float32))
    shipped = trace_plane.attach_spans(msg, 3, 1)
    assert shipped == 2
    with telemetry.use_context(ctx):
        sender.send_message(msg)
    rx.join(timeout=10)
    assert not rx.is_alive(), "receiver never saw the message"
    payload = seen[0].get(trace_plane.SPANS_KEY)
    assert payload is not None
    fresh = trace_plane.ingest_shipped(payload, seen[0].get_sender_id())
    assert fresh == 2
    asm = trace_plane.get_assembler()
    assert asm.trace_ids() == {3: [ctx.trace_id]}
    return asm.signature(ctx.trace_id)


EXPECTED_SIG = (("client.train", 1, (("client.step", 1, ()),)),)


def test_span_shipping_parity_loopback():
    hub = LoopbackHub()
    sig = _ship_roundtrip(lambda: (LoopbackCommManager(1, 2, hub=hub),
                                   LoopbackCommManager(0, 2, hub=hub)))
    assert sig == EXPECTED_SIG


def test_span_shipping_parity_grpc():
    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager

    managers = []

    def make_pair():
        managers.append(GRPCCommManager(rank=1, size=2, base_port=19650))
        managers.append(GRPCCommManager(rank=0, size=2, base_port=19650))
        return managers[0], managers[1]

    try:
        assert _ship_roundtrip(make_pair) == EXPECTED_SIG
    finally:
        for m in managers:
            m._server.stop(grace=0)


def test_span_shipping_parity_mqtt_s3():
    from fedml_tpu.comm.mqtt_s3 import MqttS3CommManager
    from fedml_tpu.comm.pubsub import InProcessBroker
    from fedml_tpu.comm.store import InMemoryBlobStore

    broker, store = InProcessBroker(), InMemoryBlobStore()
    sig = _ship_roundtrip(
        lambda: (MqttS3CommManager(broker, store, rank=1, size=2),
                 MqttS3CommManager(broker, store, rank=0, size=2)))
    assert sig == EXPECTED_SIG


def test_span_shipping_parity_trpc():
    from fedml_tpu.comm.trpc_backend import TRPCCommManager

    managers = []

    def make_pair():
        managers.append(TRPCCommManager(rank=1, size=2, base_port=19670))
        managers.append(TRPCCommManager(rank=0, size=2, base_port=19670))
        return managers[0], managers[1]

    try:
        assert _ship_roundtrip(make_pair) == EXPECTED_SIG
    finally:
        for m in managers:
            try:
                m.stop_receive_message()
            except Exception:
                pass


def test_assembler_dedupes_by_span_id():
    asm = trace_plane.TraceAssembler()
    assert asm.add(_mkspan(0)) is True
    assert asm.add(_mkspan(0)) is False
    assert len(asm.spans()) == 1


# --- clock skew --------------------------------------------------------------


def test_clock_offset_recorded_from_handshake():
    trace_plane.configure(ship_spans=True)
    msg = Message(1, 2, 0)
    trace_plane.attach_clock(msg)
    wall = msg.get(trace_plane.CLOCK_KEY)
    assert wall is not None
    trace_plane.note_client_clock(2, wall - 5.0)  # client clock 5 s behind
    offsets = trace_plane.clock_offsets()
    assert offsets[(None, 2)] == pytest.approx(5.0, abs=0.5)


def test_export_applies_skew_correction():
    records = [
        {"kind": "clock_offset", "rank": 1, "offset": 5.0},
        dict(_mkspan(0, rank=1), start=100.0),
        dict(_mkspan(1, rank=0), start=105.0),
    ]
    doc = trace_plane.export_chrome_trace(records)
    by_name = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    # rank 1's clock runs 5 s behind: its span lands at the same corrected
    # instant as rank 0's, on separate tracks
    assert by_name["s0"]["ts"] == pytest.approx(105.0 * 1e6)
    assert by_name["s1"]["ts"] == pytest.approx(105.0 * 1e6)
    assert by_name["s0"]["tid"] == 1 and by_name["s1"]["tid"] == 0


# --- Chrome export -----------------------------------------------------------


def test_export_two_tenants_phase_sums_preserved():
    records = []
    for tenant, rank in (("a", 0), ("a", 1), ("b", 0)):
        records.append({
            "kind": "phase_record", "tenant": tenant, "rank": rank,
            "round": 2, "end": 200.0, "round_time": 1.5,
            "phases": [["dispatch", 0.5], ["device", 0.75], ["eval", 0.25]],
        })
    records.append({"kind": "instant", "name": "quarantine", "tenant": "a",
                    "rank": 0, "ts": 199.5, "round": 2, "clients": [3]})
    doc = trace_plane.export_chrome_trace(records)
    events = doc["traceEvents"]
    procs = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs == {"tenant:a", "tenant:b"}
    slices = [e for e in events if e.get("ph") == "X"]
    by_track = {}
    for e in slices:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e)
    assert len(by_track) == 3
    for evs in by_track.values():
        # phase slices are laid back-to-back and sum exactly to round_time
        assert sum(e["dur"] for e in evs) == pytest.approx(1.5 * 1e6)
        evs = sorted(evs, key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
    instants = [e for e in events if e.get("ph") == "i"]
    assert [e["name"] for e in instants] == ["quarantine"]
    assert instants[0]["args"]["clients"] == [3]
    # tenant filter keeps only that tenant's tracks
    only_b = trace_plane.export_chrome_trace(records, tenant="b")
    assert all(e["pid"] == 0 for e in only_b["traceEvents"])
    assert sum(1 for e in only_b["traceEvents"] if e.get("ph") == "X") == 3


# --- anomaly detection -------------------------------------------------------


def test_anomaly_detector_fires_and_stays_quiet():
    det = trace_plane.PhaseAnomalyDetector(
        window=16, z_thresh=8.0, warmup=3, min_seconds=0.05)
    for i in range(8):
        assert det.observe({"dispatch": 0.1 + 0.001 * (i % 3)}) == {}
    hit = det.observe({"dispatch": 5.0})
    assert "dispatch" in hit and hit["dispatch"] >= 8.0
    # the anomalous value must not become the new normal
    assert "dispatch" in det.observe({"dispatch": 5.0})
    assert det.observe({"dispatch": 0.1}) == {}


def test_anomaly_detector_min_seconds_floor():
    det = trace_plane.PhaseAnomalyDetector(
        window=16, z_thresh=8.0, warmup=3, min_seconds=0.05)
    for _ in range(8):
        det.observe({"codec": 0.0001})
    # 100x regression, but still under the absolute wall-clock floor
    assert det.observe({"codec": 0.01}) == {}


def test_on_round_record_annotates_history_and_counts():
    trace_plane.configure(anomaly_detection=True, anomaly_warmup=2,
                          anomaly_window=16, anomaly_min_seconds=0.01)
    for i in range(6):
        rec = {"round": i, "round_time": 0.2,
               "phases": {"dispatch": 0.1, "device": 0.1}}
        trace_plane.on_round_record(rec)
        assert "phase_anomalies" not in rec
    slow = {"round": 6, "round_time": 5.1,
            "phases": {"dispatch": 5.0, "device": 0.1}}
    trace_plane.on_round_record(slow)
    assert set(slow["phase_anomalies"]) == {"dispatch"}
    counters = telemetry.get_registry().snapshot()["counters"]
    assert counters.get('fedml_phase_anomalies_total{phase=dispatch}') == 1


def test_recompile_detector_flags_post_warmup_compiles():
    trace_plane.configure(anomaly_detection=True, anomaly_warmup=2,
                          anomaly_window=16)
    reg = telemetry.get_registry()
    for i in range(4):
        if i < 2:  # warmup compiles are expected and not flagged
            reg.counter("fedml_jax_programs_total",
                        source="compiled").inc()
        rec = {"round": i, "round_time": 0.1, "phases": {"dispatch": 0.1}}
        trace_plane.on_round_record(rec)
        assert "recompile_events" not in rec
    # a program loaded from the cache is a program too
    reg.counter("fedml_jax_programs_total", source="compiled").inc()
    reg.counter("fedml_jax_programs_total", source="cache").inc()
    rec = {"round": 4, "round_time": 0.1, "phases": {"dispatch": 0.1}}
    trace_plane.on_round_record(rec)
    assert rec["recompile_events"] == 2
    counters = reg.snapshot()["counters"]
    assert counters.get("fedml_recompiles_post_warmup_total") == 2


def test_absorb_planned_compiles_quiets_detector():
    # the scan engine compiles a NEW program for each block length — e.g. a
    # plan's short tail block lands after warmup by design; absorbing it
    # must keep the recompile counter at zero while a genuinely unplanned
    # compile right after still fires
    trace_plane.configure(anomaly_detection=True, anomaly_warmup=2,
                          anomaly_window=16)
    reg = telemetry.get_registry()
    for i in range(4):
        rec = {"round": i, "round_time": 0.1, "phases": {"dispatch": 0.1}}
        trace_plane.on_round_record(rec)
    reg.counter("fedml_jax_programs_total", source="compiled").inc(3)
    trace_plane.absorb_planned_compiles()
    rec = {"round": 4, "round_time": 0.1, "phases": {"dispatch": 0.1}}
    trace_plane.on_round_record(rec)
    assert "recompile_events" not in rec
    assert reg.counter_total("fedml_recompiles_post_warmup_total") == 0
    reg.counter("fedml_jax_programs_total", source="compiled").inc()
    rec = {"round": 5, "round_time": 0.1, "phases": {"dispatch": 0.1}}
    trace_plane.on_round_record(rec)
    assert rec["recompile_events"] == 1


def test_simulator_run_annotates_anomalies_when_quiet():
    """A clean small run must complete with the detector armed and produce
    zero anomaly annotations (the detector must not cry wolf)."""
    args = fedml_tpu.init(config=dict(
        dataset="mnist", model="lr", debug_small_data=True,
        client_num_in_total=8, client_num_per_round=4, comm_round=6,
        learning_rate=0.1, epochs=1, batch_size=8, frequency_of_the_test=5,
        random_seed=0, trace_anomaly_detection=True, trace_anomaly_warmup=2,
        # generous z + high floor: compile-round noise must stay quiet
        trace_anomaly_z=50.0, trace_anomaly_min_seconds=10.0,
    ))
    assert trace_plane.config().anomaly_detection is True
    history = fedml_tpu.run_simulation(args=args)
    assert len(history) == 6
    assert all("phase_anomalies" not in h for h in history)


# --- flight recorder ---------------------------------------------------------


def test_flight_dump_bundle_roundtrip(tmp_path):
    trace_plane.configure(flight_recorder=True, flight_dir=str(tmp_path),
                          ship_spans=True)
    with telemetry.get_tracer().span("server.round", round_idx=1, rank=0):
        pass
    trace_plane.record_instant("rollback", round_idx=1,
                               attrs={"excluded": [2]})
    trace_plane.on_round_record(
        {"round": 1, "round_time": 0.3, "phases": {"dispatch": 0.3}})
    path = trace_plane.flight_dump("watchdog_rollback")
    assert path is not None and os.path.exists(path)
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["kind"] == "flight_bundle"
    assert bundle["reason"] == "watchdog_rollback"
    kinds = {r.get("kind") for r in bundle["records"]}
    assert {"span", "instant", "phase_record"} <= kinds
    assert "registry" in bundle
    # the bundle replays through the exporter without the live process
    doc = trace_plane.export_chrome_trace(trace_plane.load_records(path))
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])
    assert any(e.get("ph") == "i" and e["name"] == "rollback"
               for e in doc["traceEvents"])


def test_flight_dump_rate_limited(tmp_path):
    trace_plane.configure(flight_recorder=True, flight_dir=str(tmp_path))
    assert trace_plane.flight_dump("send_failure") is not None
    # a failure storm must not write a bundle per event
    assert trace_plane.flight_dump("send_failure") is None
    assert trace_plane.flight_dump("manual", force=True) is not None


@pytest.mark.chaos
def test_chaos_crash_leaves_flight_bundle(tmp_path):
    """ISSUE acceptance: a chaos-injected client crash auto-dumps a
    replayable black-box bundle."""
    from fedml_tpu.cross_silo.chaos import run_chaos_drill

    r = run_chaos_drill(
        join_timeout_s=90.0, fault_drop_rate=0.0,
        fault_crash_rank=1, fault_crash_at_round=1,
        flight_recorder=True, flight_dir=str(tmp_path),
        trace_ship_spans=True)
    assert r.ok, r.summary()
    bundles = glob.glob(os.path.join(str(tmp_path), "flight_*_chaos_crash.json"))
    assert bundles, "crash did not leave a flight bundle"
    records = trace_plane.load_records(bundles[0])
    assert any(rec.get("kind") == "instant" and rec.get("name") == "crash"
               for rec in records)
    doc = trace_plane.export_chrome_trace(records)
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


def test_watchdog_rollback_dumps_flight_bundle(tmp_path):
    """Simulator watchdog rollback triggers the black-box dump."""
    from fedml_tpu.simulation import build_simulator

    args = fedml_tpu.init(config=dict(
        dataset="digits", model="lr", partition_method="homo",
        client_num_in_total=10, client_num_per_round=10, comm_round=8,
        learning_rate=0.3, epochs=1, batch_size=32,
        frequency_of_the_test=7, random_seed=0,
        attack_type="scale", attacker_ratio=0.2, attack_boost=50.0,
        watchdog_factor=1.5, watchdog_window=3, max_rollbacks=3,
        sanitize_z_thresh=1e6, rollback_z_thresh=3.0,
        flight_recorder=True, flight_dir=str(tmp_path),
    ))
    sim, apply_fn = build_simulator(args)
    hist = sim.run(apply_fn, log_fn=None)
    assert any(h["rollbacks"] > 0 for h in hist)
    bundles = glob.glob(
        os.path.join(str(tmp_path), "flight_*_watchdog_rollback.json"))
    assert bundles, "rollback did not leave a flight bundle"
    records = trace_plane.load_records(bundles[0])
    assert any(rec.get("kind") == "phase_record" for rec in records)


# --- spans-dropped counter (satellite) ---------------------------------------


def test_tracer_ring_eviction_counts_drops():
    telemetry.configure(enabled=True, reset=True, span_buffer=4)
    try:
        for i in range(6):
            with telemetry.get_tracer().span(f"s{i}"):
                pass
        assert telemetry.get_tracer().dropped == 2
        counters = telemetry.get_registry().snapshot()["counters"]
        assert counters.get("fedml_spans_dropped_total") == 2
        telemetry.get_tracer().clear()
        assert telemetry.get_tracer().dropped == 0
    finally:
        telemetry.configure(enabled=True, reset=True)


# --- CLI ---------------------------------------------------------------------


def _emit_jsonl(tmp_path):
    """Write a two-tenant JSONL sink file with spans, a phase record, an
    instant, and a clock offset."""
    jsonl = str(tmp_path / "run.jsonl")
    telemetry.configure(enabled=True, reset=True, jsonl_path=jsonl)
    trace_plane.configure(ship_spans=True)
    for tenant, rank in (("a", 0), ("a", 1), ("b", 0)):
        with telemetry.tenant_scope(tenant):
            ctx = telemetry.new_round_context(1)
            with telemetry.use_context(ctx):
                with telemetry.get_tracer().span("server.round", rank=rank):
                    pass
            trace_plane.on_round_record(
                {"round": 1, "round_time": 0.4,
                 "phases": {"dispatch": 0.25, "device": 0.15}}, rank=rank)
    with telemetry.tenant_scope("a"):
        trace_plane.record_instant("shed", attrs={"tenant": "a"})
        trace_plane.note_client_clock(1, 123.0)
    telemetry.flush()
    telemetry.configure(enabled=True, reset=True)  # close the sink
    return jsonl


def test_cli_telemetry_trace_two_tenant_export(tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.cli.main import cli

    jsonl = _emit_jsonl(tmp_path)
    out = str(tmp_path / "round.trace.json")
    res = CliRunner().invoke(
        cli, ["telemetry", "trace", jsonl, "--out", out])
    assert res.exit_code == 0, res.output
    with open(out) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    procs = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs == {"tenant:a", "tenant:b"}
    span_tracks = {(e["pid"], e["tid"]) for e in events
                   if e.get("ph") == "X" and e.get("cat") == "span"}
    assert len(span_tracks) == 3  # (a,0), (a,1), (b,0)
    for rec_pid, rec_tid in span_tracks:
        phase = [e for e in events if e.get("cat") == "phase"
                 and (e["pid"], e["tid"]) == (rec_pid, rec_tid)]
        assert sum(e["dur"] for e in phase) == pytest.approx(0.4 * 1e6)
    assert any(e.get("ph") == "i" and e["name"] == "shed" for e in events)
    # tenant filter drops tenant b entirely
    res = CliRunner().invoke(
        cli, ["telemetry", "trace", jsonl, "--out", out, "--tenant", "b"])
    assert res.exit_code == 0, res.output
    with open(out) as f:
        doc = json.load(f)
    assert all(e["args"]["name"] == "tenant:b" for e in doc["traceEvents"]
               if e.get("ph") == "M" and e["name"] == "process_name")


def test_cli_telemetry_summary_tenant_filter_and_drops(tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.cli.main import cli

    jsonl = str(tmp_path / "run.jsonl")
    telemetry.configure(enabled=True, reset=True, jsonl_path=jsonl,
                        span_buffer=2)
    with telemetry.tenant_scope("a"):
        for _ in range(4):
            with telemetry.get_tracer().span("a.only"):
                pass
    with telemetry.tenant_scope("b"):
        with telemetry.get_tracer().span("b.only"):
            pass
    telemetry.flush()
    telemetry.configure(enabled=True, reset=True)
    res = CliRunner().invoke(cli, ["telemetry", "summary", jsonl])
    assert res.exit_code == 0, res.output
    assert "spans dropped (ring evictions)" in res.output
    res = CliRunner().invoke(
        cli, ["telemetry", "summary", jsonl, "--tenant", "a"])
    assert res.exit_code == 0, res.output
    assert "a.only" in res.output and "b.only" not in res.output


def test_filter_snapshot_scopes_series():
    reg = telemetry.get_registry()
    with telemetry.tenant_scope("a"):
        telemetry.scoped_registry("a").counter("fedml_rounds_total").inc(2)
    with telemetry.tenant_scope("b"):
        telemetry.scoped_registry("b").counter("fedml_rounds_total").inc(5)
    snap = telemetry.filter_snapshot(reg.snapshot(), "a")
    assert list(snap["counters"].values()) == [2]
