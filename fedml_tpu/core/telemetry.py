"""Unified telemetry: metrics registry, span tracer, exporters, collectors.

One process-wide, thread-safe home for every number the framework emits —
the generalization of PR 1's ad-hoc ``pack_time``/``pack_wait`` history
fields into a subsystem all layers report through:

- **MetricsRegistry** — counters, gauges, and histograms (fixed exponential
  buckets) keyed by (name, labels). Snapshots are plain dicts; snapshots
  from different processes merge (counters/histogram buckets add, gauges
  last-write-wins) so multi-process cross-silo runs aggregate offline.
- **Tracer** — spans carrying ``trace_id``/``span_id``/``round_idx``
  context (a contextvar, restored explicitly on receive threads). The
  context rides ``comm.Message`` params on all four backends, so the
  server and client sides of one FL round share a ``trace_id`` and round
  latency decomposes into server compute, wire time, and straggler tail.
- **Exporters** — JSONL (``MetricsSink``), a Prometheus textfile writer
  (node-exporter textfile-collector format), and the
  ``python -m fedml_tpu.cli telemetry summary`` pretty-printer.
- **Collectors** — jax's compile phases (``jax.monitoring``) as spans
  ``jax.trace`` / ``jax.lower`` / ``jax.compile`` and the counter
  ``fedml_jax_programs_total{source}``, and a daemon-thread sampler for
  ``SysStats`` + ``device.memory_stats()``.

The defining constraint is overhead (<1% of round wall-clock): when
disabled, every accessor returns a shared null metric whose methods are
empty, ``inject``/``extract`` are no-ops, and spans neither allocate ids,
record, nor annotate. Enabled-path costs are a few dict lookups and
``perf_counter`` calls per round — microseconds against rounds that take
milliseconds to seconds (what a span costs on the LM step is measured on the
chip in PERF.md and bounded in ``tests/test_lm_tracing.py``).

Every span is also a ``jax.profiler.TraceAnnotation`` named
``fedml:<span name>``: under a profiler session (``MLOps.device_trace``, a
benchmark's traced slice) the program's spans land on the profile's host
lines, on the device trace's clock; with no session the annotation is a
flag test.
"""

from __future__ import annotations

import bisect
import contextlib
import contextvars
import dataclasses
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

# --- bucket schemes ---------------------------------------------------------

# (start, factor, count): bounds[i] = start * factor**i, plus a +Inf overflow
# bucket. Mergeability across processes requires IDENTICAL schemes, so these
# are named constants, not per-call tuning knobs.
SECONDS_SCHEME = (1e-4, 2.0, 24)   # 0.1 ms .. ~14 min
BYTES_SCHEME = (64.0, 4.0, 16)     # 64 B .. ~69 GB


def _bounds(scheme: Tuple[float, float, int]) -> List[float]:
    start, factor, count = scheme
    return [start * factor ** i for i in range(int(count))]


# --- metric types -----------------------------------------------------------


class Counter:
    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    __slots__ = ("_value",)

    def __init__(self):
        self._value = 0.0

    def set(self, v: float) -> None:
        # last-writer-wins by design: one GIL-atomic float store keeps the
        # sampler path lock-free — graftcheck: disable=thread-hazard
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-exponential-bucket histogram. ``counts`` has one extra slot for
    the +Inf overflow bucket; ``bounds`` are upper edges (le semantics)."""

    __slots__ = ("scheme", "bounds", "counts", "sum", "count", "_lock")

    def __init__(self, scheme: Tuple[float, float, int] = SECONDS_SCHEME):
        self.scheme = tuple(scheme)
        self.bounds = _bounds(self.scheme)
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (upper edge of the bucket
        holding the q-th observation; +Inf bucket reports the last edge)."""
        if not self.count:
            return 0.0
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                return self.bounds[min(i, len(self.bounds) - 1)]
        return self.bounds[-1]


class _NullMetric:
    """Shared do-nothing stand-in returned when telemetry is disabled."""

    __slots__ = ()
    value = 0.0
    sum = 0.0
    count = 0
    mean = 0.0

    def inc(self, n: float = 1.0) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def quantile(self, q: float) -> float:
        return 0.0


_NULL = _NullMetric()


# --- tenant scoping ----------------------------------------------------------

# The multi-tenant control plane (core/tenancy.py) isolates telemetry by
# stamping a ``tenant`` label on every series created while a tenant scope is
# active. The scope is a contextvar — it does NOT inherit into new threads,
# so per-tenant worker threads must enter :func:`tenant_scope` inside their
# own thread body (the multi-run driver and chaos drill both do).
_tenant_var: "contextvars.ContextVar[Optional[str]]" = (
    contextvars.ContextVar("fedml_tpu_tenant", default=None))


def current_tenant() -> Optional[str]:
    return _tenant_var.get()


@contextlib.contextmanager
def tenant_scope(tenant: Optional[str]):
    """Attribute every metric created in the block to ``tenant``. ``None``
    is a no-op scope (series stay unlabeled — byte-identical to today)."""
    token = _tenant_var.set(None if tenant is None else str(tenant))
    try:
        yield tenant
    finally:
        _tenant_var.reset(token)


# --- registry ---------------------------------------------------------------


def _key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class MetricsRegistry:
    """Process-wide metric store: ``(name, labels) -> metric``.

    First creation wins the type/scheme; later accessors with the same key
    return the existing instance (a kind mismatch raises — silent type
    punning would corrupt exports).
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        # key -> (kind, labels-dict, metric)
        self._metrics: Dict[str, Tuple[str, Dict[str, Any], Any]] = {}

    def _get(self, kind: str, name: str, labels: Dict[str, Any],
             factory: Callable[[], Any]):
        if not self.enabled:
            return _NULL
        # active tenant scope: the series splits per tenant (an explicit
        # tenant= label from the caller wins over the ambient scope)
        tenant = _tenant_var.get()
        if tenant is not None and "tenant" not in labels:
            labels = dict(labels, tenant=tenant)
        key = _key(name, labels)
        with self._lock:
            ent = self._metrics.get(key)
            if ent is None:
                ent = (kind, dict(labels), factory())
                self._metrics[key] = ent
            elif ent[0] != kind:
                raise TypeError(
                    f"metric {key!r} already registered as {ent[0]}, "
                    f"requested as {kind}")
            return ent[2]

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels, Counter)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels, Gauge)

    def histogram(self, name: str,
                  scheme: Tuple[float, float, int] = SECONDS_SCHEME,
                  **labels) -> Histogram:
        return self._get("histogram", name, labels,
                         lambda: Histogram(scheme))

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()

    def counter_total(self, name: str) -> float:
        """Sum of one counter family across every label set. Cheap — no
        histogram bucket copies — so per-round pollers (the trace plane's
        recompile detector) can afford it."""
        with self._lock:
            items = list(self._metrics.items())
        return sum(m.value for key, (kind, _labels, m) in items
                   if kind == "counter"
                   and (key == name or key.startswith(name + "{")))

    def snapshot(self) -> Dict[str, Any]:
        """Plain-dict dump, stable across processes and mergeable."""
        out: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
        with self._lock:
            items = list(self._metrics.items())
        for key, (kind, _labels, m) in items:
            if kind == "counter":
                out["counters"][key] = m.value
            elif kind == "gauge":
                out["gauges"][key] = m.value
            else:
                out["histograms"][key] = {
                    "scheme": list(m.scheme),
                    "counts": list(m.counts),
                    "sum": m.sum,
                    "count": m.count,
                }
        return out

    def merge_snapshot(self, snap: Dict[str, Any]) -> None:
        """Fold another process's snapshot into this registry: counters and
        histogram buckets add; gauges last-write-wins. Histogram scheme
        mismatches raise — adding buckets with different edges is silent
        data corruption."""
        for key, v in (snap.get("counters") or {}).items():
            _name, labels = _parse_key(key)
            with self._lock:
                ent = self._metrics.setdefault(
                    key, ("counter", labels, Counter()))
            ent[2].inc(v)
        for key, v in (snap.get("gauges") or {}).items():
            _name, labels = _parse_key(key)
            with self._lock:
                ent = self._metrics.setdefault(key, ("gauge", labels, Gauge()))
            ent[2].set(v)
        for key, h in (snap.get("histograms") or {}).items():
            _name, labels = _parse_key(key)
            scheme = tuple(h["scheme"])
            with self._lock:
                ent = self._metrics.setdefault(
                    key, ("histogram", labels, Histogram(scheme)))
            hist = ent[2]
            if tuple(hist.scheme) != scheme:
                raise ValueError(
                    f"histogram {key!r} scheme mismatch: "
                    f"{hist.scheme} vs {scheme}")
            with hist._lock:
                for i, c in enumerate(h["counts"]):
                    hist.counts[i] += int(c)
                hist.sum += float(h["sum"])
                hist.count += int(h["count"])


class TenantRegistry:
    """Tenant-scoped facade over a :class:`MetricsRegistry`: every series
    accessed through it carries ``tenant=<name>``, and :meth:`snapshot`
    keeps only that tenant's series — the isolated registry view the chaos
    drill and the multi-run driver hand each job."""

    def __init__(self, tenant: str, registry: Optional[MetricsRegistry] = None):
        self.tenant = str(tenant)
        self._reg = registry if registry is not None else _state.registry

    @property
    def enabled(self) -> bool:
        return self._reg.enabled

    def counter(self, name: str, **labels) -> Counter:
        labels.setdefault("tenant", self.tenant)
        return self._reg.counter(name, **labels)

    def gauge(self, name: str, **labels) -> Gauge:
        labels.setdefault("tenant", self.tenant)
        return self._reg.gauge(name, **labels)

    def histogram(self, name: str,
                  scheme: Tuple[float, float, int] = SECONDS_SCHEME,
                  **labels) -> Histogram:
        labels.setdefault("tenant", self.tenant)
        return self._reg.histogram(name, scheme, **labels)

    def snapshot(self) -> Dict[str, Any]:
        """The underlying snapshot restricted to this tenant's series."""
        return filter_snapshot(self._reg.snapshot(), self.tenant)


def filter_snapshot(snap: Dict[str, Any], tenant: str) -> Dict[str, Any]:
    """Restrict a registry snapshot to one tenant's series — the filtering
    :class:`TenantRegistry` applies, shared so offline consumers (the CLI
    ``telemetry summary --tenant``) match it exactly."""
    tenant = str(tenant)
    out: Dict[str, Any] = {}
    for kind, series in snap.items():
        out[kind] = {
            k: v for k, v in series.items()
            if _parse_key(k)[1].get("tenant") == tenant
        }
    return out


def scoped_registry(tenant: str,
                    registry: Optional[MetricsRegistry] = None) -> TenantRegistry:
    return TenantRegistry(tenant, registry)


def _parse_key(key: str) -> Tuple[str, Dict[str, str]]:
    if "{" not in key:
        return key, {}
    name, rest = key.split("{", 1)
    labels = {}
    for pair in rest.rstrip("}").split(","):
        if "=" in pair:
            k, v = pair.split("=", 1)
            labels[k] = v
    return name, labels


# --- trace context ----------------------------------------------------------


@dataclasses.dataclass
class TraceContext:
    trace_id: str
    span_id: str
    round_idx: Optional[int] = None


_current: "contextvars.ContextVar[Optional[TraceContext]]" = (
    contextvars.ContextVar("fedml_tpu_trace", default=None))

# Message param keys the trace context rides on (plain msgpack-able scalars;
# every backend's send stamps them, every receive path restores them).
TRACE_ID_KEY = "telemetry_trace_id"
SPAN_ID_KEY = "telemetry_span_id"
ROUND_IDX_KEY = "telemetry_round_idx"


def _new_id() -> str:
    # 64 random bits as 16 hex digits; a fifth of uuid4's cost, which a
    # span pays once or twice
    return os.urandom(8).hex()


def current_context() -> Optional[TraceContext]:
    return _current.get()


@contextlib.contextmanager
def use_context(ctx: Optional[TraceContext]):
    """Install ``ctx`` as the current trace context for the block (receive
    paths restore the sender's context around observer dispatch)."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def new_round_context(round_idx: int) -> Optional[TraceContext]:
    """Fresh root context for one FL round (server-side round start). All
    messages sent under it — and every reply sent from within their
    handlers — share its ``trace_id``."""
    if not _state.enabled:
        return None
    return TraceContext(trace_id=_new_id(), span_id=_new_id(),
                        round_idx=int(round_idx))


def inject_trace(msg) -> None:
    """Stamp the current trace context onto an outbound ``comm.Message``.
    No context (or disabled telemetry) means no stamp — messages outside
    any round/span stay byte-identical to the pre-telemetry wire format."""
    if not _state.enabled:
        return
    ctx = _current.get()
    if ctx is None or TRACE_ID_KEY in msg.msg_params:
        return
    msg.add_params(TRACE_ID_KEY, ctx.trace_id)
    msg.add_params(SPAN_ID_KEY, ctx.span_id)
    if ctx.round_idx is not None:
        msg.add_params(ROUND_IDX_KEY, int(ctx.round_idx))


def extract_trace(msg) -> Optional[TraceContext]:
    """Read a trace context off an inbound ``comm.Message`` (None if the
    sender stamped nothing)."""
    if not _state.enabled:
        return None
    trace_id = msg.get(TRACE_ID_KEY)
    if trace_id is None:
        return None
    rnd = msg.get(ROUND_IDX_KEY)
    return TraceContext(trace_id=str(trace_id),
                        span_id=str(msg.get(SPAN_ID_KEY) or _new_id()),
                        round_idx=int(rnd) if rnd is not None else None)


PROFILE_PREFIX = "fedml:"  # a span's name on a jax profile's host lines

# jax.profiler.TraceAnnotation, looked up at the first span (importing jax
# initialises no backend); False where jax is absent
_annotation_cls = None


def _trace_annotation():
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # jax absent/old — telemetry must not require it
            _annotation_cls = False
        else:
            _annotation_cls = TraceAnnotation
    return _annotation_cls


def _child_context(parent: Optional[TraceContext],
                   round_idx: Optional[int]) -> TraceContext:
    """A new span's context: its parent's trace and round, or a new trace."""
    return TraceContext(
        trace_id=parent.trace_id if parent else _new_id(),
        span_id=_new_id(),
        round_idx=(int(round_idx) if round_idx is not None
                   else (parent.round_idx if parent else None)),
    )


class Tracer:
    """Span recorder. Finished spans land in a bounded ring (inspection /
    tests), the JSONL sink when configured, and the
    ``fedml_span_seconds{name=...}`` histogram; for its life a span is also
    a profiler annotation ``fedml:<name>``, so it shows on a device trace."""

    def __init__(self, registry: MetricsRegistry, buffer: int = 4096):
        self.registry = registry
        self._finished: "deque[Dict[str, Any]]" = deque(maxlen=buffer)
        self.sink = None  # optional MetricsSink
        # oldest-span evictions from the ring (mirrors
        # MetricsSink.dropped_records — a silent discard is a lie in the data)
        self.dropped = 0

    @contextlib.contextmanager
    def span(self, name: str, round_idx: Optional[int] = None, **attrs):
        if not _state.enabled:
            yield None
            return
        parent = _current.get()
        ctx = _child_context(parent, round_idx)
        token = _current.set(ctx)
        annotation_cls = _trace_annotation()
        annotation = (annotation_cls(PROFILE_PREFIX + name)
                      if annotation_cls else None)
        if annotation is not None:
            annotation.__enter__()
        wall0 = time.time()
        t0 = time.perf_counter()
        status = "ok"
        try:
            yield ctx
        except BaseException:
            status = "error"
            raise
        finally:
            duration = time.perf_counter() - t0
            if annotation is not None:
                annotation.__exit__(None, None, None)
            _current.reset(token)
            self._finish(name, ctx, parent, wall0, duration, status, attrs)

    def record(self, name: str, start: float, end: float, **attrs) -> None:
        """Record a span after the fact: ``start`` and ``end`` are wall-clock
        seconds (``time.time()``) of an interval that has already passed,
        under the current context as its parent. It lands where a span does
        (ring, sink, ``fedml_span_seconds``) but leaves no profiler
        annotation: the interval is over before the record is made."""
        if not _state.enabled:
            return
        parent = _current.get()
        self._finish(name, _child_context(parent, None), parent, start,
                     end - start, "ok", attrs)

    def _finish(self, name: str, ctx: TraceContext,
                parent: Optional[TraceContext], start: float,
                duration: float, status: str, attrs: Dict[str, Any]) -> None:
        """One finished span into the ring, the sink and the histogram."""
        rec = {
            "kind": "span",
            "name": name,
            "trace_id": ctx.trace_id,
            "span_id": ctx.span_id,
            "parent_span_id": parent.span_id if parent else None,
            "round_idx": ctx.round_idx,
            "start": start,
            "duration": duration,
            "status": status,
        }
        if attrs:
            rec.update(attrs)
        tenant = _tenant_var.get()
        if tenant is not None:
            rec["tenant"] = tenant
        if len(self._finished) == self._finished.maxlen:
            self.dropped += 1
            self.registry.counter("fedml_spans_dropped_total").inc()
        self._finished.append(rec)
        if self.sink is not None:
            try:
                self.sink.emit(rec)
            except Exception:  # a full disk must not fail the traced op
                logging.exception("telemetry: span sink emit failed")
        self.registry.histogram(
            "fedml_span_seconds", span=name).observe(duration)

    def finished_spans(self) -> List[Dict[str, Any]]:
        return list(self._finished)

    def clear(self) -> None:
        self._finished.clear()
        self.dropped = 0


# --- global state / configuration -------------------------------------------


class _State:
    def __init__(self):
        self.enabled = True
        self.registry = MetricsRegistry(enabled=True)
        self.tracer = Tracer(self.registry)
        self.prometheus_path: Optional[str] = None
        self.jsonl_sink = None
        self.sampler: Optional["SysStatsSampler"] = None
        self.atexit_registered = False


_state = _State()


def get_registry() -> MetricsRegistry:
    return _state.registry


def get_tracer() -> Tracer:
    return _state.tracer


def enabled() -> bool:
    return _state.enabled


def configure(enabled: bool = True,
              jsonl_path: Optional[str] = None,
              prometheus_path: Optional[str] = None,
              sysstats_interval_s: float = 0.0,
              span_buffer: int = 4096,
              reset: bool = False) -> None:
    """(Re)configure the process-wide telemetry state. Idempotent; called by
    ``fedml_tpu.init()`` from the ``telemetry.*`` config family."""
    _state.enabled = bool(enabled)
    _state.registry.enabled = bool(enabled)
    if reset:
        _state.registry.reset()
        _state.tracer.clear()
        from . import trace_plane

        trace_plane.reset()
    if _state.tracer._finished.maxlen != span_buffer:
        old = list(_state.tracer._finished)
        _state.tracer._finished = deque(old, maxlen=int(span_buffer))
    if _state.jsonl_sink is not None and (
            not jsonl_path or _state.jsonl_sink.path != jsonl_path):
        _state.jsonl_sink.close()
        _state.jsonl_sink = None
    if jsonl_path and _state.jsonl_sink is None:
        from .mlops import MetricsSink

        _state.jsonl_sink = MetricsSink(path=jsonl_path)
    _state.tracer.sink = _state.jsonl_sink
    _state.prometheus_path = prometheus_path
    if _state.sampler is not None:
        _state.sampler.stop()
        _state.sampler = None
    if enabled and sysstats_interval_s and sysstats_interval_s > 0:
        _state.sampler = SysStatsSampler(float(sysstats_interval_s))
        _state.sampler.start()
    if enabled:
        install_jax_collectors()
    if (jsonl_path or prometheus_path) and not _state.atexit_registered:
        import atexit

        atexit.register(flush)
        _state.atexit_registered = True


def configure_from_args(args) -> None:
    """Map the flat ``telemetry_*`` config keys onto :func:`configure`."""
    configure(
        enabled=bool(getattr(args, "telemetry_enabled", True)),
        jsonl_path=getattr(args, "telemetry_jsonl_path", None),
        prometheus_path=getattr(args, "telemetry_prometheus_path", None),
        sysstats_interval_s=float(
            getattr(args, "telemetry_sysstats_interval_s", 0.0) or 0.0),
        span_buffer=int(getattr(args, "telemetry_span_buffer", 4096)),
    )
    from . import trace_plane

    trace_plane.configure_from_args(args)


def flush() -> None:
    """Export current state: Prometheus textfile (if configured) + one
    registry-snapshot record on the JSONL sink (if configured)."""
    if not _state.enabled:
        return
    if _state.prometheus_path:
        try:
            write_prometheus(_state.prometheus_path)
        except OSError:
            logging.exception("telemetry: prometheus write failed")
    if _state.jsonl_sink is not None:
        _state.jsonl_sink.emit({
            "kind": "registry_snapshot",
            "timestamp": time.time(),
            "registry": _state.registry.snapshot(),
        })


def emit_record(rec: Dict[str, Any]) -> None:
    """Write one record to the JSONL sink, if configured. The trace plane
    uses this for its ``phase_record`` / ``instant`` / ``clock_offset`` /
    shipped-span kinds; a full disk never fails the emitting operation."""
    if not _state.enabled or _state.jsonl_sink is None:
        return
    try:
        _state.jsonl_sink.emit(rec)
    except Exception:
        logging.exception("telemetry: record emit failed")


# --- comm-plane helpers (hot path: one guard + dict lookup per message) -----


def record_send(backend: str, nbytes: Optional[int],
                serialize_s: Optional[float] = None) -> None:
    if not _state.enabled:
        return
    reg = _state.registry
    reg.counter("fedml_comm_messages_total",
                backend=backend, direction="send").inc()
    if nbytes is not None:
        reg.histogram("fedml_comm_message_bytes", scheme=BYTES_SCHEME,
                      backend=backend, direction="send").observe(nbytes)
    if serialize_s is not None:
        reg.histogram("fedml_comm_serialize_seconds",
                      backend=backend).observe(serialize_s)


def record_receive(backend: str, nbytes: Optional[int] = None) -> None:
    if not _state.enabled:
        return
    reg = _state.registry
    reg.counter("fedml_comm_messages_total",
                backend=backend, direction="recv").inc()
    if nbytes is not None:
        reg.histogram("fedml_comm_message_bytes", scheme=BYTES_SCHEME,
                      backend=backend, direction="recv").observe(nbytes)


# --- resilience hooks (comm retry loop + fault injector + dispatch guard) ----


def record_send_retry(backend: str) -> None:
    if _state.enabled:
        _state.registry.counter("fedml_send_retries_total",
                                backend=backend).inc()


def record_send_failure(backend: str) -> None:
    if _state.enabled:
        _state.registry.counter("fedml_send_failures_total",
                                backend=backend).inc()


def record_fault(action: str) -> None:
    if _state.enabled:
        _state.registry.counter("fedml_faults_injected_total",
                                action=action).inc()


def record_observer_error(msg_type) -> None:
    if _state.enabled:
        _state.registry.counter("fedml_observer_errors_total",
                                msg_type=str(msg_type)).inc()


# --- exporters --------------------------------------------------------------


def _prom_name(name: str) -> str:
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


def _prom_labels(labels: Dict[str, Any], extra: str = "") -> str:
    pairs = [f'{_prom_name(str(k))}="{v}"' for k, v in sorted(labels.items())]
    if extra:
        pairs.append(extra)
    return "{" + ",".join(pairs) + "}" if pairs else ""


def write_prometheus(path: str, registry: Optional[MetricsRegistry] = None) -> None:
    """Prometheus text exposition (textfile-collector format), written
    atomically (tmp + rename) so a scraper never reads a torn file."""
    reg = registry or _state.registry
    with reg._lock:
        items = sorted(reg._metrics.items())
    lines: List[str] = []
    typed: set = set()
    for key, (kind, labels, m) in items:
        name = _prom_name(_parse_key(key)[0])
        if name not in typed:
            lines.append(f"# TYPE {name} {kind}")
            typed.add(name)
        if kind in ("counter", "gauge"):
            lines.append(f"{name}{_prom_labels(labels)} {m.value}")
        else:
            cum = 0
            for i, edge in enumerate(m.bounds):
                cum += m.counts[i]
                le = 'le="%s"' % edge
                lines.append(f"{name}_bucket{_prom_labels(labels, le)} {cum}")
            cum += m.counts[-1]
            le = 'le="+Inf"'
            lines.append(f"{name}_bucket{_prom_labels(labels, le)} {cum}")
            lines.append(f"{name}_sum{_prom_labels(labels)} {m.sum}")
            lines.append(f"{name}_count{_prom_labels(labels)} {m.count}")
    body = "\n".join(lines) + "\n"
    tmp = f"{path}.tmp.{os.getpid()}"
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(tmp, "w") as f:
        f.write(body)
    os.replace(tmp, path)


# --- JAX collectors ---------------------------------------------------------


_jax_collectors_installed = False

# jax's own time spans of a program's three phases (jax 0.9,
# ``jax._src.dispatch``), each with the ``fun_name`` it was made for
_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_PHASES = {
    _JAX_TRACE: "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# fired inside the backend phase, on its thread, where the persistent cache
# hands the executable back instead of XLA compiling it
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _JaxThread(threading.local):
    """Per thread: the cache-hit flag, and a count for each open trace."""

    cache_hit = False

    def __init__(self):
        self.traces: List[int] = []


_jax_thread = _JaxThread()


def _jax_fun(fun_name: str) -> str:
    """``jit(train_step)`` -> ``train_step``: the three phases of one
    program under one name."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_jax_event(event: str, **kw) -> None:
    if event == _JAX_CACHE_HIT:
        _jax_thread.cache_hit = True


def _on_jax_phase_start(event: str, value: float, **kw) -> None:
    if event == _JAX_TRACE:
        _jax_thread.traces.append(0)


def _on_jax_phase(event: str, start: float, end: float, **kw) -> None:
    """One phase of a program, at its end. Every backend phase is counted in
    ``fedml_jax_programs_total{source}``. jax reports a trace for every call
    of a jitted function made while tracing, its trace cache's hits too (a
    step of 24 layers makes thousands), so only the outermost trace becomes a
    span, with ``traces`` = the trace phases inside it, itself included. A
    phase becomes a span (a child of the current one) only while a span is
    open: the eager ops and small jits run outside the program's spans leave
    the ring alone."""
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    attrs: Dict[str, Any] = {"fun": _jax_fun(str(kw.get("fun_name", "")))}
    if name == "jax.trace":
        stack = _jax_thread.traces
        traces = 1 + (stack.pop() if stack else 0)
        if stack:  # counted in the trace that holds it
            stack[-1] += traces
            return
        attrs["traces"] = traces
    elif name == "jax.compile":
        attrs["cached"] = _jax_thread.cache_hit
        _jax_thread.cache_hit = False
        if _state.enabled:
            _state.registry.counter(
                "fedml_jax_programs_total",
                source="cache" if attrs["cached"] else "compiled").inc()
    if _state.enabled and _current.get() is not None:
        _state.tracer.record(name, start, end, **attrs)


def install_jax_collectors() -> bool:
    """Turn jax's compile phases into spans and one counter (above) via
    ``jax.monitoring`` listeners. Registration is global and permanent in
    jax, so this installs once per process; the listeners consult the
    enabled flag at fire time."""
    global _jax_collectors_installed
    if _jax_collectors_installed:
        return True
    try:
        from jax import monitoring
    except Exception:  # jax absent/old — telemetry must not require it
        return False
    try:
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_scalar_listener(_on_jax_phase_start)
        monitoring.register_event_time_span_listener(_on_jax_phase)
    except Exception:
        return False
    _jax_collectors_installed = True
    return True


class SysStatsSampler:
    """Daemon thread sampling ``SysStats`` (psutil + device.memory_stats())
    into registry gauges at a fixed cadence, flushing the Prometheus file
    each tick when one is configured (textfile-collector scrape pattern)."""

    def __init__(self, interval_s: float,
                 registry: Optional[MetricsRegistry] = None):
        self.interval_s = float(interval_s)
        self.registry = registry or _state.registry
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample_once(self) -> None:
        from .mlops import SysStats

        s = SysStats()
        reg = self.registry
        reg.gauge("fedml_cpu_utilization").set(s.cpu_utilization)
        reg.gauge("fedml_process_memory_gb").set(s.process_memory_gb)
        reg.gauge("fedml_host_memory_used_gb").set(s.host_memory_used_gb)
        reg.gauge("fedml_net_sent_mb_interval").set(s.net_sent_mb)
        reg.gauge("fedml_net_recv_mb_interval").set(s.net_recv_mb)
        for dm in s.device_memory:
            reg.gauge("fedml_device_bytes_in_use_gb",
                      device=dm["device"]).set(dm["bytes_in_use_gb"])
            reg.gauge("fedml_device_bytes_limit_gb",
                      device=dm["device"]).set(dm["bytes_limit_gb"])

    def start(self) -> None:
        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.sample_once()
                except Exception:
                    logging.exception("telemetry: sysstats sample failed")
                if _state.prometheus_path:
                    try:
                        write_prometheus(_state.prometheus_path, self.registry)
                    except OSError:
                        logging.exception("telemetry: prometheus write failed")

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="telemetry-sysstats")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
