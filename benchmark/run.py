"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: set-up (build, seeded weights and data, the first steps
that compile and feed the comparison), the measured window, the memory
reading, then the plain reference and the comparison that decides ``correct``.
The last line of standard output is the result; with no TPU, or fewer chips
than the cell asks for, there is no result and the exit code is 4.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is found by its name in BENCHMARK.json:
``configs/<config>.json`` (the manifest's ``file``), ``traffic/<traffic>.json``,
``runners/<runner>.py``, ``layer_metrics/<metric>.json`` and
``readers/<reader>.py``."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP = 4


class CompileMeter:
    """Compiles from jax's own monitoring events (after chip_smoke.py's):
    programs requested, persistent-cache hits, and those XLA compiled anew.
    A cache hit is followed, on the same thread, by its own duration event."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.totals = {"requests": 0, "cache_hits": 0, "compiled": 0,
                       "xla_s": 0.0, "cache_load_s": 0.0}
        self._hit_pending = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == self._REQUEST:
            self.totals["requests"] += 1
        elif event == self._HIT:
            self.totals["cache_hits"] += 1
            self._hit_pending = True

    def _on_duration(self, event: str, secs: float, **_):
        if event != self._BACKEND:
            return
        if self._hit_pending:
            self._hit_pending = False
            self.totals["cache_load_s"] += secs
        else:
            self.totals["compiled"] += 1
            self.totals["xla_s"] += secs

    def snapshot(self) -> dict:
        return dict(self.totals)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> types.SimpleNamespace:
    """The cell, its configuration, traffic and metrics, all by name."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = cells[name]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    applies = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return types.SimpleNamespace(
        name=name, chips=cell["chips"], bench_dir=bench_dir,
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(
            bench_dir, "traffic", cell["traffic"] + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m)],
        per_layer=[m for m in manifest["per_layer"] if applies(m)])


def device_stamp(chips: int, peaks: dict):
    """(device dict, peaks row, devices) or an error string. No fallback: a
    platform other than the TPU, too few chips or an unknown device_kind is
    a failure."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        return f"no TPU: jax reports platform={d0.platform!r}"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips, jax reports {len(devices)}"
    if d0.device_kind not in peaks:
        return f"device_kind {d0.device_kind!r} is not in peaks.json"
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devices)}, peaks[d0.device_kind], devices)


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest device: the allocator's peak plus what the runtime
    reserved for programs' temporaries, which this backend counts apart
    (``peak_bytes_reserved``; a program with 2 GiB of temporaries leaves
    ``peak_bytes_in_use`` unmoved). A backend that keeps no counters gives 0."""
    def peak(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


class GcMeter:
    """Python's collector inside the window: collections and their seconds."""

    def __init__(self):
        self.collections, self.pause_s, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.collections += 1
            self.pause_s += time.perf_counter() - self._t0
            self._t0 = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        return {"collections": self.collections, "pause_s": self.pause_s}


def configure_cache(root: str) -> None:
    """Persistent compilation cache: where JAX_COMPILATION_CACHE_DIR says, or
    at a fixed path in the checkout; every program persisted, whatever it
    took to compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Tracer:
    """Profiles one slice of the window: on at ``start_s``, off ``slice_s``
    later, switched between steps by the runner's tick. ``overhead_s`` is
    the host time the switching itself took inside the window (writing the
    trace out takes seconds), which the traced run's rates leave out."""

    def __init__(self, enabled: bool, out_dir: str, start_s: float,
                 slice_s: float):
        self.dir = out_dir if enabled else None
        self.start_s, self.stop_s = start_s, start_s + slice_s
        self.state = "off" if enabled else "done"
        self.overhead_s = 0.0

    def tick(self, elapsed: float) -> None:
        if self.state == "done":
            return
        import jax

        t0 = time.perf_counter()
        if self.state == "off" and elapsed >= self.start_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_s:
            jax.profiler.stop_trace()
            self.state = "done"
        self.overhead_s += time.perf_counter() - t0

    def reduce(self):
        import jax

        if self.dir is None:
            return None
        if self.state == "on":
            jax.profiler.stop_trace()
        trace_reduce = importlib.import_module("trace_reduce")
        path = trace_reduce.find_xplane(self.dir)
        reduced = trace_reduce.reduce_file(path) if path else None
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def span(name: str):
    """A host span of the benchmark's own on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def no_span(name: str):
    return contextlib.nullcontext()


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation over every value."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end_values(window: dict, setup_s: float) -> dict:
    """The end-to-end metrics, over all the work and all the time of the
    window: the set-up time, the runner's rate under the name the runner
    gives it, and the tail over every step."""
    return {
        "setup_s": setup_s,
        window["rate_name"]: window["units"] / window["wall_s"],
        "step_ms_p90": 1e3 * percentile(window["step_s"], 0.90),
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             peak: dict, devices, out=sys.stdout, err=sys.stderr) -> int:
    """Everything after the look for a chip. Returns the exit code."""
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    meter = CompileMeter()
    ctx = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, seed=seed, chips=cell.chips,
        span=span if trace else no_span)

    run = runner.Run(ctx)
    setup_compiles = meter.snapshot()
    tracer = Tracer(trace, os.path.join(ROOT, ".bench_out", "trace"),
                    cell.traffic["trace_start_s"], cell.traffic["trace_slice_s"])
    setup_s = time.perf_counter() - T_PROCESS
    gc.collect()
    gc_meter = GcMeter()
    window = run.window(seconds, tracer.tick)
    gc_in_window = gc_meter.close()
    after = meter.snapshot()
    window["compiles"] = after["compiled"] - setup_compiles["compiled"]
    window["trace_overhead_s"] = tracer.overhead_s
    device = dict(device, memory_peak_bytes=memory_peak_bytes(devices))
    memory_stats = {k: int(v) for k, v in
                    (devices[0].memory_stats() or {}).items()}
    traced = tracer.reduce()
    prog = run.readings
    run.close()
    del run
    gc.collect()

    t_ref = time.perf_counter()
    ref = runner.reference(ctx)
    reference_s = time.perf_counter() - t_ref
    correct, compared = compare.decide(prog, ref, cell.config["limits"])
    correct = bool(correct and window["failed"] == 0 and window["attempted"] > 0)

    values = end_to_end_values(window, setup_s)
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        rctx = {"config": cell.config, "traffic": cell.traffic, "peak": peak,
                "chips": cell.chips, "window": window, "trace": traced}
        for m in cell.per_layer:
            spec = load_json(os.path.join(
                cell.bench_dir, "layer_metrics", m["name"] + ".json"))
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(rctx, **spec["args"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": device}
    if trace and traced:
        device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["run"] = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "steps": window["attempted"], "wall_s": window["wall_s"],
        "step_ms_median": 1e3 * statistics.median(window["step_s"]),
        "setup_s": setup_s, "reference_s": reference_s,
        "compiles_setup": setup_compiles, "compiles_end": after,
        "memory_stats": memory_stats, "gc_in_window": gc_in_window,
        "slowest_steps_ms": sorted(
            ((round(1e3 * t, 1), i) for i, t in enumerate(window["step_s"])),
            reverse=True)[:5]}
    result["compared"] = compared
    print(json.dumps(result), file=out, flush=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g}, "
              f"at {c['at']})", file=err)
    print(f"correct: {str(correct).lower()}", file=err, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "fedml_tpu")):
        print("correct: false - the system under test (fedml_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    configure_cache(ROOT)
    stamp = device_stamp(cell.chips, load_json(
        os.path.join(cell.bench_dir, "peaks.json")))
    if isinstance(stamp, str):
        print(f"correct: false - {stamp}", file=sys.stderr)
        return NO_CHIP
    return run_cell(cell, args.seed, args.seconds, bool(args.trace), *stamp)


if __name__ == "__main__":
    sys.exit(main())
