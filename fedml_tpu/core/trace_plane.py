"""Trace plane: cross-process round timelines, flight recorder, anomalies.

PR 2's telemetry layer left finished spans to die in each process's ring
buffer: the server could never see a client's ``client.train`` span, and a
watchdog rollback or chaos crash destroyed the evidence with the process.
This module is the forensic layer on top:

- **span shipping & assembly** — clients attach their finished spans for the
  round (bounded count, size-capped msgpack) to the model-upload message;
  the server folds them into a :class:`TraceAssembler` keyed by the
  already-propagated ``trace_id``, de-duplicated by ``span_id`` and
  clock-skew-corrected from the handshake exchange (the client stamps its
  wall clock on the CLIENT_STATUS reply; the server records
  ``offset = server_wall - client_wall``).
- **Perfetto/Chrome trace-event export** — :func:`export_chrome_trace`
  renders spans, per-round phase slices, and instant events (quarantine,
  rollback, admission, shed, crash, anomaly) as Chrome ``traceEvents``
  JSON: one process (pid) per tenant, one track (tid) per rank.
- **flight recorder** — a bounded ring of the last K rounds' phase records
  and instants, dumped with the span ring, a registry snapshot, and a log
  tail as one timestamped JSON bundle on watchdog rollback, terminal
  ``SendFailure``, chaos crash, or SIGTERM (plus manual triggers).
- **phase-anomaly detection** — robust-z regression of per-phase times
  against a rolling in-run baseline (median/MAD, warmup-gated), annotated
  into ``history[i]["phase_anomalies"]`` and counted in
  ``fedml_phase_anomalies_total{phase=}``, plus a recompile detector that
  flags post-warmup programs (``fedml_jax_programs_total``) with the round
  that triggered them.

Everything is OFF by default: with the plane disabled every hook is a
single attribute check, and no message grows a byte (the disabled wire
format stays byte-identical).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import signal
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import telemetry

# Message param keys (same family as telemetry.TRACE_ID_KEY): only stamped
# when span shipping is on, so the disabled wire format never changes.
SPANS_KEY = "telemetry_spans"
CLOCK_KEY = "telemetry_wall_clock"


# --- configuration -----------------------------------------------------------


@dataclasses.dataclass
class TracePlaneConfig:
    """The ``trace_*`` / ``flight_*`` config family (see
    docs/observability.md). All features default off."""

    ship_spans: bool = False
    ship_max_spans: int = 256
    ship_max_bytes: int = 262144
    anomaly_detection: bool = False
    anomaly_window: int = 32
    anomaly_warmup: int = 5
    anomaly_z: float = 8.0
    anomaly_min_seconds: float = 0.05
    flight_recorder: bool = False
    flight_dir: str = "flight_records"
    flight_rounds: int = 8
    flight_log_lines: int = 200
    flight_min_interval_s: float = 1.0


class _RingLogHandler(logging.Handler):
    """Bounded tail of formatted log lines for flight bundles. The deque's
    maxlen does the truncation; ``emit`` never raises into the logger."""

    def __init__(self, maxlen: int):
        super().__init__()
        self.lines: "deque[str]" = deque(maxlen=maxlen)

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self.lines.append(self.format(record))
        except Exception:
            pass


class _Plane:
    def __init__(self):
        self.cfg = TracePlaneConfig()
        self.active = False  # any feature on (single-attr fast path)
        self.lock = threading.Lock()
        self.assembler = TraceAssembler()  # defined below; _plane is
        # instantiated at the bottom of the module, after every class
        # (tenant or "", rank) -> PhaseAnomalyDetector
        self.detectors: Dict[Tuple[str, int], "PhaseAnomalyDetector"] = {}
        # recompile detector state, keyed like detectors
        self.compile_baseline: Dict[Tuple[str, int], float] = {}
        self.rounds_seen: Dict[Tuple[str, int], int] = {}
        # flight recorder ring: phase records + instants, newest last
        self.flight_ring: "deque[Dict[str, Any]]" = deque(maxlen=64)
        self.clock_offsets: Dict[Tuple[Optional[str], int], float] = {}
        self.log_handler: Optional[_RingLogHandler] = None
        self.sigterm_installed = False
        self.last_dump_wall = 0.0


def config() -> TracePlaneConfig:
    return _plane.cfg


def active() -> bool:
    return _plane.active


def configure(**kw) -> None:
    """(Re)configure the process-wide trace plane. Unknown keys raise —
    a typo silently disabling the flight recorder is the exact failure
    mode this plane exists to prevent."""
    cfg = _plane.cfg
    for key, value in kw.items():
        if not hasattr(cfg, key):
            raise TypeError(f"unknown trace-plane option {key!r}")
        setattr(cfg, key, type(getattr(TracePlaneConfig(), key))(value))
    _plane.active = bool(
        cfg.ship_spans or cfg.anomaly_detection or cfg.flight_recorder)
    with _plane.lock:
        if _plane.flight_ring.maxlen != max(cfg.flight_rounds * 8, 8):
            _plane.flight_ring = deque(
                _plane.flight_ring, maxlen=max(cfg.flight_rounds * 8, 8))
    if cfg.flight_recorder:
        _install_log_handler()
        _install_sigterm()
    elif _plane.log_handler is not None:
        logging.getLogger().removeHandler(_plane.log_handler)
        _plane.log_handler = None


def configure_from_args(args) -> None:
    """Map the flat ``trace_*`` / ``flight_*`` config keys onto
    :func:`configure` — the single read site for this config family."""
    configure(
        ship_spans=bool(getattr(args, "trace_ship_spans", False)),
        ship_max_spans=int(getattr(args, "trace_ship_max_spans", 256)),
        ship_max_bytes=int(getattr(args, "trace_ship_max_bytes", 262144)),
        anomaly_detection=bool(
            getattr(args, "trace_anomaly_detection", False)),
        anomaly_window=int(getattr(args, "trace_anomaly_window", 32)),
        anomaly_warmup=int(getattr(args, "trace_anomaly_warmup", 5)),
        anomaly_z=float(getattr(args, "trace_anomaly_z", 8.0)),
        anomaly_min_seconds=float(
            getattr(args, "trace_anomaly_min_seconds", 0.05)),
        flight_recorder=bool(getattr(args, "flight_recorder", False)),
        flight_dir=str(getattr(args, "flight_dir", "flight_records")),
        flight_rounds=int(getattr(args, "flight_rounds", 8)),
        flight_log_lines=int(getattr(args, "flight_log_lines", 200)),
    )


def reset() -> None:
    """Restore the default (all-off) state — test isolation hook, called by
    ``telemetry.configure(reset=True)``."""
    if _plane.log_handler is not None:
        logging.getLogger().removeHandler(_plane.log_handler)
    old_sigterm = _plane.sigterm_installed
    _plane.__init__()
    # signal handlers are process-global and cannot be meaningfully
    # re-installed per test; remember so configure() doesn't re-stack them
    _plane.sigterm_installed = old_sigterm


def _install_log_handler() -> None:
    if _plane.log_handler is not None:
        _plane.log_handler.lines = deque(
            _plane.log_handler.lines, maxlen=_plane.cfg.flight_log_lines)
        return
    handler = _RingLogHandler(_plane.cfg.flight_log_lines)
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s"))
    logging.getLogger().addHandler(handler)
    _plane.log_handler = handler


def _install_sigterm() -> None:
    if _plane.sigterm_installed:
        return
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            flight_dump("sigterm")
            if callable(prev):
                prev(signum, frame)
            else:
                raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):
        return  # not the main thread / no signal support — dump-less exit
    _plane.sigterm_installed = True


# --- span shipping -----------------------------------------------------------


def _msgpack():
    import msgpack

    return msgpack


def pack_spans(spans: List[Dict[str, Any]], max_spans: int,
               max_bytes: int) -> Tuple[Optional[bytes], int, int]:
    """Serialize a span list under both caps. Oldest spans are dropped
    first (the newest spans are the round being shipped). Returns
    ``(payload, shipped, dropped)``; payload None when nothing fits."""
    dropped = max(0, len(spans) - max_spans)
    spans = spans[dropped:]
    msgpack = _msgpack()
    while spans:
        payload = msgpack.packb(spans, use_bin_type=True)
        if len(payload) <= max_bytes:
            return payload, len(spans), dropped
        shed = max(1, len(spans) // 2)
        dropped += shed
        spans = spans[shed:]
    return None, 0, dropped


def unpack_spans(payload: bytes, origin_rank: int) -> List[Dict[str, Any]]:
    """Decode a shipped span payload, stamping each span with its origin
    rank (the wire sender is authoritative — a span can't lie about which
    process recorded it)."""
    spans = _msgpack().unpackb(payload, raw=False)
    out = []
    for rec in spans:
        if isinstance(rec, dict):
            rec = dict(rec, rank=int(origin_rank), shipped=True)
            out.append(rec)
    return out


def spans_for_round(round_idx: int, rank: int) -> List[Dict[str, Any]]:
    """This process's finished spans for ``round_idx`` attributable to
    ``rank``. In a multi-process deployment the ring only holds local
    spans; over loopback (all actors in one process sharing the tracer)
    the client/rank attribute keeps each actor shipping only its own."""
    out = []
    for rec in telemetry.get_tracer().finished_spans():
        if rec.get("round_idx") != round_idx:
            continue
        owner = rec.get("rank", rec.get("client"))
        if owner is None or int(owner) != int(rank):
            continue
        out.append(rec)
    return out


def attach_spans(msg, round_idx: int, rank: int) -> int:
    """Client-side: attach this round's finished spans to the upload
    message. No-op (zero wire change) unless span shipping is on."""
    if not _plane.active or not _plane.cfg.ship_spans \
            or not telemetry.enabled():
        return 0
    cfg = _plane.cfg
    payload, shipped, dropped = pack_spans(
        spans_for_round(round_idx, rank),
        cfg.ship_max_spans, cfg.ship_max_bytes)
    if dropped:
        telemetry.get_registry().counter(
            "fedml_trace_spans_ship_dropped_total").inc(dropped)
    if payload is None:
        return 0
    msg.add_params(SPANS_KEY, payload)
    telemetry.get_registry().counter(
        "fedml_trace_spans_shipped_total").inc(shipped)
    return shipped


def ingest_shipped(payload: bytes, origin_rank: int) -> int:
    """Server-side: fold a shipped span payload into the assembler and
    re-emit each span (rank-stamped) to the JSONL sink so the CLI trace
    export sees every rank's spans in one file."""
    if not telemetry.enabled():
        return 0
    try:
        spans = unpack_spans(payload, origin_rank)
    except Exception:
        logging.exception("trace_plane: undecodable span payload from rank %s",
                          origin_rank)
        return 0
    tenant = telemetry.current_tenant()
    fresh = 0
    for rec in spans:
        if tenant is not None and "tenant" not in rec:
            rec["tenant"] = tenant
        if _plane.assembler.add(rec):
            fresh += 1
            telemetry.emit_record(rec)
    if fresh:
        telemetry.get_registry().counter(
            "fedml_trace_spans_ingested_total").inc(fresh)
    return fresh


def get_assembler() -> "TraceAssembler":
    return _plane.assembler


# --- clock skew --------------------------------------------------------------


def attach_clock(msg) -> None:
    """Client-side handshake reply: stamp this process's wall clock so the
    server can estimate per-rank skew. Gated on span shipping (the stamp is
    useless without spans to correct, and the wire must not change)."""
    if _plane.active and _plane.cfg.ship_spans and telemetry.enabled():
        msg.add_params(CLOCK_KEY, time.time())


def note_client_clock(rank: int, client_wall) -> None:
    """Server-side: record ``offset = server_wall - client_wall`` for a
    rank (one-way message latency biases the estimate by at most the wire
    delay — good enough to line tracks up on one timeline). The offset is
    also emitted as a sink record so offline export can apply it."""
    if client_wall is None or not telemetry.enabled():
        return
    tenant = telemetry.current_tenant()
    offset = time.time() - float(client_wall)
    with _plane.lock:
        _plane.clock_offsets[(tenant, int(rank))] = offset
    rec = {"kind": "clock_offset", "rank": int(rank), "offset": offset}
    if tenant is not None:
        rec["tenant"] = tenant
    telemetry.emit_record(rec)


def clock_offsets() -> Dict[Tuple[Optional[str], int], float]:
    with _plane.lock:
        return dict(_plane.clock_offsets)


# --- assembler ---------------------------------------------------------------


class TraceAssembler:
    """Per-round span trees across ranks, keyed by ``trace_id``.

    Spans are de-duplicated by ``span_id`` (over loopback the server's own
    ring already holds the client spans a ship re-delivers) and evicted
    oldest-first past ``max_spans``.
    """

    def __init__(self, max_spans: int = 16384):
        self._lock = threading.Lock()
        self._spans: Dict[str, Dict[str, Any]] = {}
        self._order: "deque[str]" = deque()
        self.max_spans = int(max_spans)

    def add(self, rec: Dict[str, Any]) -> bool:
        span_id = rec.get("span_id")
        if not span_id:
            return False
        with self._lock:
            if span_id in self._spans:
                return False
            self._spans[span_id] = dict(rec)
            self._order.append(span_id)
            while len(self._order) > self.max_spans:
                self._spans.pop(self._order.popleft(), None)
        return True

    def spans(self, trace_id: Optional[str] = None,
              round_idx: Optional[int] = None,
              tenant: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            recs = [dict(r) for r in self._spans.values()]
        if trace_id is not None:
            recs = [r for r in recs if r.get("trace_id") == trace_id]
        if round_idx is not None:
            recs = [r for r in recs if r.get("round_idx") == round_idx]
        if tenant is not None:
            recs = [r for r in recs if r.get("tenant") == tenant]
        recs.sort(key=lambda r: (float(r.get("start", 0.0)),
                                 str(r.get("span_id"))))
        return recs

    def trace_ids(self) -> Dict[Optional[int], List[str]]:
        """``{round_idx: [trace_id...]}`` for every assembled round."""
        out: Dict[Optional[int], List[str]] = {}
        for rec in self.spans():
            tid = rec.get("trace_id")
            if tid and tid not in out.setdefault(rec.get("round_idx"), []):
                out[rec.get("round_idx")].append(tid)
        return out

    def signature(self, trace_id: str):
        """Canonical structure of one round tree: nested
        ``(name, rank, (children...))`` tuples sorted by (name, rank) —
        identical for the same logical round regardless of backend, span
        ids, or wall-clock."""
        recs = self.spans(trace_id=trace_id)
        by_id = {r["span_id"]: r for r in recs}
        children: Dict[Optional[str], List[Dict[str, Any]]] = {}
        for r in recs:
            parent = r.get("parent_span_id")
            if parent not in by_id:
                parent = None  # orphan (parent not shipped) -> root
            children.setdefault(parent, []).append(r)

        def build(rec):
            kids = tuple(sorted(
                (build(c) for c in children.get(rec["span_id"], [])),
            ))
            rank = rec.get("rank", rec.get("client"))
            return (str(rec.get("name")),
                    int(rank) if rank is not None else None, kids)

        return tuple(sorted(build(r) for r in children.get(None, [])))


# --- round records, instants, anomaly detection ------------------------------


class PhaseAnomalyDetector:
    """Robust-z regression detector over per-phase round times.

    Each phase keeps a rolling window; an observation is anomalous when its
    z-score against the window's median/MAD exceeds ``z_thresh`` AND it
    clears the absolute ``min_seconds`` floor (micro-phases jitter by large
    ratios that mean nothing in wall-clock). Anomalous values are NOT fed
    back into the baseline — a regression must keep firing, not become the
    new normal. The first ``warmup`` samples per phase only feed the
    baseline (compile rounds are always "anomalous" against nothing).
    """

    def __init__(self, window: int = 32, z_thresh: float = 8.0,
                 warmup: int = 5, min_seconds: float = 0.05):
        self.window = int(window)
        self.z_thresh = float(z_thresh)
        self.warmup = max(int(warmup), 2)
        self.min_seconds = float(min_seconds)
        self._baseline: Dict[str, "deque[float]"] = {}

    def observe(self, phases: Dict[str, float]) -> Dict[str, float]:
        anomalies: Dict[str, float] = {}
        for name in sorted(phases):
            dt = float(phases[name])
            base = self._baseline.setdefault(
                name, deque(maxlen=self.window))
            if len(base) >= self.warmup and dt > self.min_seconds:
                ordered = sorted(base)
                med = ordered[len(ordered) // 2]
                mad = sorted(abs(x - med) for x in ordered)[len(ordered) // 2]
                # MAD floor: a near-constant baseline must not turn every
                # microsecond of jitter into an infinite z
                scale = 1.4826 * mad + 0.05 * med + 1e-6
                z = (dt - med) / scale
                if z >= self.z_thresh:
                    anomalies[name] = round(z, 2)
                    continue
            base.append(dt)
        return anomalies


def _detector_key() -> Tuple[str, int]:
    return (telemetry.current_tenant() or "", 0)


def _recompile_delta(key: Tuple[str, int]) -> float:
    """Post-warmup delta of ``fedml_jax_programs_total`` (programs XLA
    compiled or loaded from the cache) since the last round — a nonzero
    value names the round that re-triggered XLA."""
    total = telemetry.get_registry().counter_total("fedml_jax_programs_total")
    prev = _plane.compile_baseline.get(key)
    _plane.compile_baseline[key] = total
    return 0.0 if prev is None else max(0.0, total - prev)


def absorb_planned_compiles(rank: int = 0) -> None:
    """Fold a PLANNED compilation into the recompile detector's baseline.

    The multi-round scan engine compiles one program per block length, so
    the first dispatch of a new length (a plan's short tail block, a
    resume that re-anchors mid-block) legitimately triggers XLA after
    warmup. The engine calls this right after such a dispatch, so
    ``fedml_recompiles_post_warmup_total`` keeps meaning "unexpected
    shape/donation instability" whether rounds are fused or not."""
    if not _plane.active or not telemetry.enabled():
        return
    total = telemetry.get_registry().counter_total("fedml_jax_programs_total")
    _plane.compile_baseline[
        (telemetry.current_tenant() or "", int(rank))] = total


def on_round_record(rec: Dict[str, Any], rank: int = 0) -> None:
    """Fold one finished round into the trace plane: emit a phase record
    (the Chrome export's phase slices), run anomaly + recompile detection
    (annotating ``rec`` in place — it IS ``history[i]``), and feed the
    flight ring. Cheap no-op when the plane is off."""
    if not _plane.active or not telemetry.enabled():
        return
    cfg = _plane.cfg
    tenant = telemetry.current_tenant()
    phases = rec.get("phases") or {}
    record: Dict[str, Any] = {
        "kind": "phase_record",
        "rank": int(rank),
        "round": int(rec.get("round", -1)),
        "end": time.time(),
        "round_time": float(rec.get("round_time",
                                    sum(phases.values()) or 0.0)),
        "phases": [[name, float(dt)] for name, dt in phases.items()],
    }
    if tenant is not None:
        record["tenant"] = tenant
    if cfg.anomaly_detection and phases:
        key = (tenant or "", int(rank))
        det = _plane.detectors.get(key)
        if det is None:
            det = _plane.detectors[key] = PhaseAnomalyDetector(
                cfg.anomaly_window, cfg.anomaly_z, cfg.anomaly_warmup,
                cfg.anomaly_min_seconds)
        anomalies = det.observe(phases)
        if anomalies:
            rec["phase_anomalies"] = anomalies
            record["anomalies"] = anomalies
            reg = telemetry.get_registry()
            for name in anomalies:
                reg.counter("fedml_phase_anomalies_total", phase=name).inc()
            record_instant("phase_anomaly", round_idx=record["round"],
                           rank=rank, attrs={"phases": anomalies})
        n_seen = _plane.rounds_seen.get(key, 0) + 1
        _plane.rounds_seen[key] = n_seen
        delta = _recompile_delta(key)
        if n_seen > cfg.anomaly_warmup and delta > 0:
            rec["recompile_events"] = delta
            record["recompile_events"] = delta
            telemetry.get_registry().counter(
                "fedml_recompiles_post_warmup_total").inc(delta)
            record_instant("recompile", round_idx=record["round"], rank=rank,
                           attrs={"events": delta})
    if cfg.flight_recorder:
        with _plane.lock:
            _plane.flight_ring.append(record)
    telemetry.emit_record(record)


def record_instant(name: str, round_idx: Optional[int] = None, rank: int = 0,
                   attrs: Optional[Dict[str, Any]] = None) -> None:
    """One point-in-time event (quarantine / rollback / admission / shed /
    crash / anomaly, plus the serving plane's ``promote`` /
    ``rollback_served`` swaps) on a rank's track. No-op when the plane is
    off."""
    if not _plane.active or not telemetry.enabled():
        return
    rec: Dict[str, Any] = {
        "kind": "instant", "name": str(name), "ts": time.time(),
        "rank": int(rank),
    }
    tenant = telemetry.current_tenant()
    if tenant is not None:
        rec["tenant"] = tenant
    if round_idx is not None:
        rec["round"] = int(round_idx)
    if attrs:
        rec.update(attrs)
    if _plane.cfg.flight_recorder:
        with _plane.lock:
            _plane.flight_ring.append(rec)
    telemetry.emit_record(rec)


# --- comm instrumentation ----------------------------------------------------


def comm_send_span(backend: str, msg, rank: int):
    """Span around one backend send, only for in-round traffic with span
    shipping on — out-of-round messages (probes, handshakes) and the
    disabled path never allocate a span."""
    if not _plane.active or not _plane.cfg.ship_spans \
            or telemetry.current_context() is None:
        return contextlib.nullcontext()
    return telemetry.get_tracer().span(
        "comm.send", backend=backend, rank=int(rank),
        receiver=int(msg.get_receiver_id()))


# --- flight recorder ---------------------------------------------------------


def flight_dump(reason: str, force: bool = False) -> Optional[str]:
    """Write one flight-recorder bundle: the round/instant ring, the span
    ring, clock offsets, a registry snapshot, and the log tail. Returns the
    bundle path (None when the recorder is off or rate-limited). ``force``
    bypasses the enable check for manual ``--flight-record`` triggers."""
    cfg = _plane.cfg
    if not (cfg.flight_recorder or force) or not telemetry.enabled():
        return None
    now = time.time()
    with _plane.lock:
        if not force and now - _plane.last_dump_wall < cfg.flight_min_interval_s:
            return None  # a failure storm must not write a bundle per event
        _plane.last_dump_wall = now
        ring = list(_plane.flight_ring)
        offsets = dict(_plane.clock_offsets)
    records: List[Dict[str, Any]] = []
    records.extend(telemetry.get_tracer().finished_spans()[-2048:])
    records.extend(ring)
    for (tenant, rank), offset in sorted(
            offsets.items(), key=lambda kv: (kv[0][0] or "", kv[0][1])):
        rec = {"kind": "clock_offset", "rank": rank, "offset": offset}
        if tenant is not None:
            rec["tenant"] = tenant
        records.append(rec)
    bundle = {
        "kind": "flight_bundle",
        "reason": str(reason),
        "wall": now,
        "records": records,
        "registry": telemetry.get_registry().snapshot(),
        "log_tail": (list(_plane.log_handler.lines)
                     if _plane.log_handler is not None else []),
    }
    path = os.path.join(
        cfg.flight_dir, f"flight_{int(now * 1000)}_{reason}.json")
    try:
        os.makedirs(cfg.flight_dir, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(bundle, f, default=str)
        os.replace(tmp, path)
    except OSError:
        logging.exception("trace_plane: flight dump failed")
        return None
    logging.warning("trace_plane: flight bundle (%s) -> %s", reason, path)
    return path


# --- Chrome trace-event export -----------------------------------------------


def load_records(source: str) -> List[Dict[str, Any]]:
    """Read trace-plane records from a telemetry JSONL file or a flight
    bundle (dispatch on content, not extension)."""
    with open(source) as f:
        first = f.readline()
        f.seek(0)
        try:
            head = json.loads(first) if first.strip() else None
        except json.JSONDecodeError:
            head = None
        if isinstance(head, dict) and head.get("kind") == "flight_bundle":
            bundle = json.load(f)
            return list(bundle.get("records") or [])
        records = []
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
        return records


def export_chrome_trace(records: Iterable[Dict[str, Any]],
                        out_path: Optional[str] = None,
                        tenant: Optional[str] = None,
                        round_idx: Optional[int] = None) -> Dict[str, Any]:
    """Render trace-plane records as Chrome trace-event JSON (loadable in
    Perfetto / ``chrome://tracing``): pid per tenant, tid per rank,
    ``ph:"X"`` slices for spans and phases, ``ph:"i"`` instants, skew
    correction from ``clock_offset`` records. Phase slices are laid
    sequentially inside ``[end - round_time, end]`` so their durations sum
    exactly to the recorded ``round_time``."""
    spans: Dict[str, Dict[str, Any]] = {}
    phase_recs: List[Dict[str, Any]] = []
    instants: List[Dict[str, Any]] = []
    offsets: Dict[Tuple[Optional[str], int], float] = {}
    for rec in records:
        kind = rec.get("kind")
        if tenant is not None and kind != "clock_offset" \
                and rec.get("tenant") != tenant:
            continue
        if kind == "span":
            rnd = rec.get("round_idx")
            if round_idx is not None and rnd != round_idx:
                continue
            sid = rec.get("span_id") or f"anon{len(spans)}"
            spans.setdefault(sid, rec)  # span_id dedupe: shipped copies
        elif kind == "phase_record":
            if round_idx is None or rec.get("round") == round_idx:
                phase_recs.append(rec)
        elif kind == "instant":
            if round_idx is None or rec.get("round", round_idx) == round_idx:
                instants.append(rec)
        elif kind == "clock_offset":
            offsets[(rec.get("tenant"), int(rec.get("rank", 0)))] = float(
                rec.get("offset", 0.0))

    def rank_of(rec) -> int:
        owner = rec.get("rank", rec.get("client", 0))
        try:
            return int(owner)
        except (TypeError, ValueError):
            return 0

    def corrected(rec, ts: float) -> float:
        return ts + offsets.get((rec.get("tenant"), rank_of(rec)), 0.0)

    tenants = sorted({r.get("tenant") for r in
                      list(spans.values()) + phase_recs + instants},
                     key=lambda t: (t is not None, t))
    pid_of = {t: i for i, t in enumerate(tenants)}
    events: List[Dict[str, Any]] = []
    tracks = set()
    for t, pid in pid_of.items():
        events.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name",
                       "args": {"name": f"tenant:{t}" if t else "default"}})
    for rec in sorted(spans.values(),
                      key=lambda r: (float(r.get("start", 0.0)),
                                     str(r.get("span_id")))):
        pid = pid_of.get(rec.get("tenant"), 0)
        tid = rank_of(rec)
        tracks.add((pid, tid))
        events.append({
            "ph": "X", "pid": pid, "tid": tid, "cat": "span",
            "name": str(rec.get("name", "?")),
            "ts": corrected(rec, float(rec.get("start", 0.0))) * 1e6,
            "dur": float(rec.get("duration", 0.0)) * 1e6,
            "args": {k: rec.get(k) for k in
                     ("trace_id", "span_id", "round_idx", "status", "backend",
                      "receiver") if rec.get(k) is not None},
        })
    for rec in phase_recs:
        pid = pid_of.get(rec.get("tenant"), 0)
        tid = rank_of(rec)
        tracks.add((pid, tid))
        cursor = corrected(
            rec, float(rec.get("end", 0.0)) - float(rec.get("round_time", 0.0)))
        for name, dt in rec.get("phases") or []:
            events.append({
                "ph": "X", "pid": pid, "tid": tid, "cat": "phase",
                "name": str(name), "ts": cursor * 1e6,
                "dur": float(dt) * 1e6,
                "args": {"round": rec.get("round")},
            })
            cursor += float(dt)
    for rec in instants:
        pid = pid_of.get(rec.get("tenant"), 0)
        tid = rank_of(rec)
        tracks.add((pid, tid))
        args = {k: v for k, v in rec.items()
                if k not in ("kind", "name", "ts", "rank", "tenant")}
        events.append({
            "ph": "i", "pid": pid, "tid": tid, "cat": "instant", "s": "p",
            "name": str(rec.get("name", "?")),
            "ts": corrected(rec, float(rec.get("ts", 0.0))) * 1e6,
            "args": args,
        })
    for pid, tid in sorted(tracks):
        events.append({"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_name",
                       "args": {"name": f"rank {tid}"}})
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if out_path:
        d = os.path.dirname(os.path.abspath(out_path))
        os.makedirs(d, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f)
    return doc


_plane = _Plane()
