"""From a profiler trace (.xplane.pb) of the scoped LM step to device time per
step by class of work, and every idle gap shared out over the program's own
host spans. Imports nothing from the program: run it on a trace kept with
``BENCH_KEEP_TRACE=1``,

    python benchmark/scope_reduce.py .bench_out/trace [step span's name]

**Which op belongs to which class.** The TPU plane names each device event by
its HLO instruction's text and keeps the instruction's ``op_name`` (jax's name
stack: ``jit(train_step)/transpose(jvp(TransformerLM))/.../block_3/MLPBlock_0/
Dense_1/dot_general``) as the stat ``tf_op`` of the event's *metadata*, which
``jax.profiler.ProfileData`` does not show; ``event_op_names`` reads it from
the file's bytes. A fusion carries the ``op_name`` of its root instruction, so
it counts whole under its root's class. Classes, tested in this order:
``optimizer`` (under ``lm.optimizer``), ``head_ce`` (under ``lm.loss`` or the
``head`` module), ``recompute`` (``rematted_computation``), ``backward``
(``transpose(``), ``forward`` (anything else under ``TransformerLM``),
``other``. Within forward / recompute / backward a second split by flax
module: attention_core (``SelfAttention_0._local_attention``: scores, softmax
and their product with the values, dense or flash), attention_proj (the rest
of ``SelfAttention_0``: qkv and proj), mlp, norm, rest (embeddings, residual
adds).

**Device time** is shared out so that the classes sum to the busy union: at
each instant the innermost running event (the one that started last) takes
it. Steps are the ``fedml:lm.step`` host spans; a device event belongs to the
step whose span holds its start (the device works on a step while the host
waits in ``lm.loss_wait``), and per-step numbers are means over the steps of
the slice.

**Idle gaps** over 10 us (between merged busy intervals, per device) are
shared out by overlap over the innermost ``fedml:`` span at each instant (the
gap between two steps runs from the tail of ``lm.loss_wait`` through
``lm.input_put`` into ``lm.dispatch``); what no ``fedml:`` span covers goes to
the ``bench:`` span there, else to ``unattributed``."""

from __future__ import annotations

import bisect
import json
import os
import sys

# beside trace_reduce.py, found as the harness finds it: by the directory
from trace_reduce import DEVICE_PREFIX, OPS_LINE, SHORT_GAP_NS, _union
from trace_reduce import find_xplane as find_in_dir

PROGRAM_PREFIX, BENCH_PREFIX = "fedml:", "bench:"
STEP_SPAN = "fedml:lm.step"
CLASSES = ("optimizer", "head_ce", "recompute", "backward", "forward", "other")
MODULES = ("attention_core", "attention_proj", "mlp", "norm", "rest")


def classify(op_name: str) -> str:
    if "lm.optimizer" in op_name:
        return "optimizer"
    if "lm.loss" in op_name or "/head/" in op_name:
        return "head_ce"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "TransformerLM" in op_name:
        return "forward"
    return "other"


def module_of(op_name: str) -> str:
    if "SelfAttention_" in op_name:
        return ("attention_core" if "_local_attention" in op_name
                else "attention_proj")
    if "MLPBlock_" in op_name:
        return "mlp"
    if "LayerNorm_" in op_name or "/ln_f/" in op_name:
        return "norm"
    return "rest"


# --- the event metadata's stats, from the file's bytes ---------------------

def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def event_op_names(path: str, stat: str = "tf_op") -> dict:
    """{plane name: {event name: op_name}} from XEventMetadata.stats (XSpace
    .planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
    5, .ref_value = 7; XStatMetadata.id = 1, .name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                md = dict(_fields(_map_value(v)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        names = {}
        for md in events:
            ev_name, op_name = "", ""
            for f, v in _fields(md):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == stat:
                        op_name = (bytes(st[5]).decode() if 5 in st
                                   else stat_names.get(st.get(7), ""))
            names[ev_name] = op_name
        out[name] = names
    return out


# --- sharing time out over overlapping intervals ---------------------------

def share(intervals, lo=None, hi=None):
    """``intervals``: (start, end, key), sorted by start. Returns ({key: time},
    covered) over [lo, hi]: at each instant the interval that started last
    and still runs takes it, so the times sum to the union, ``covered``."""
    out: dict = {}
    stack: list = []
    cursor = lo

    def run_to(t):
        nonlocal cursor
        while stack:
            end, key = stack[-1]
            upto = min(end, t)
            if upto > cursor:
                out[key] = out.get(key, 0) + upto - cursor
                cursor = upto
            if end > t:
                return
            stack.pop()
        cursor = max(cursor, t)

    for s, e, key in intervals:
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if cursor is None:
            cursor = s
        if e <= cursor:
            continue
        run_to(s)
        stack.append((e, key))
    if stack:
        run_to(max(e for e, _ in stack))
    return out, sum(out.values())


def _name_gaps(gaps, program, bench) -> dict:
    named: dict = {}

    def add(key, t):
        if t > 0:
            named[key] = named.get(key, 0) + t

    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            add("between_ops_under_10us", g1 - g0)
            continue
        by_span, covered = share(
            [s for s in program if s[1] > g0 and s[0] < g1], g0, g1)
        for key, t in by_span.items():
            add(key, t)
        if covered < g1 - g0:
            # the rest of the gap, by the benchmark's span at its middle
            mid = (g0 + g1) // 2
            outer = [k for s, e, k in bench if s <= mid < e]
            add(outer[-1] if outer else "unattributed", g1 - g0 - covered)
    return named


def reduce_profile(profile, op_names: dict,
                   step_span: str = STEP_SPAN) -> dict | None:
    """``profile``: a jax.profiler.ProfileData; ``op_names``: what
    ``event_op_names`` gave for the same file; ``step_span``: the host span
    that bounds one step. None with no device plane."""
    program, bench, devices = [], [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    names = op_names.get(plane.name, {})
                    events = sorted(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         names.get(ev.name, "")) for ev in line.events)
                    if events:
                        devices.append(events)
        else:
            for line in plane.lines:
                for ev in line.events:
                    span = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                            ev.name)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append(span)
                    elif ev.name.startswith(BENCH_PREFIX):
                        bench.append(span)
    if not devices:
        return None
    program.sort()
    bench.sort()
    steps = [(s, e) for s, e, name in program + bench if name == step_span]
    step_starts = [s for s, _ in steps]

    def step_of(t):
        i = bisect.bisect_right(step_starts, t) - 1
        return i if i >= 0 and t < steps[i][1] else None

    n_dev = len(devices)
    total = {"busy": 0, "in_steps": 0, "ops_in_steps": 0, "outside": 0,
             "idle": 0}
    by_class = dict.fromkeys(CLASSES, 0)
    by_module = {c: dict.fromkeys(MODULES, 0)
                 for c in ("forward", "recompute", "backward")}
    gaps_named: dict = {}
    steps_seen = 0
    for events in devices:
        merged = _union((s, e) for s, e, _ in events)
        total["busy"] += sum(e - s for s, e in merged)
        in_steps = [ev for ev in events if step_of(ev[0]) is not None]
        total["ops_in_steps"] += len(in_steps)
        steps_seen += len({step_of(ev[0]) for ev in in_steps})
        shared, covered = share(in_steps)
        total["in_steps"] += covered
        total["outside"] += len(events) - len(in_steps)
        for op_name, t in shared.items():
            cls = classify(op_name)
            by_class[cls] += t
            if cls in by_module:
                by_module[cls][module_of(op_name)] += t
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        total["idle"] += sum(g1 - g0 for g0, g1 in gaps
                             if g1 - g0 >= SHORT_GAP_NS)
        for key, t in _name_gaps(gaps, program, bench).items():
            gaps_named[key] = gaps_named.get(key, 0) + t
    per_step_ms = lambda ns: ns / 1e6 / steps_seen if steps_seen else None  # noqa: E731
    by_program = sum(t for k, t in gaps_named.items()
                     if k.startswith(PROGRAM_PREFIX))
    return {
        "devices": n_dev,
        "steps": steps_seen / n_dev,
        "ops_per_step": total["ops_in_steps"] / steps_seen if steps_seen else None,
        "ops_outside_steps": total["outside"] / n_dev,
        "busy_s": total["busy"] / n_dev / 1e9,
        "busy_ms_per_step": per_step_ms(total["in_steps"]),
        "class_ms_per_step": {c: per_step_ms(t) for c, t in by_class.items()},
        "module_ms_per_step": {c: {m: per_step_ms(t) for m, t in mods.items()}
                               for c, mods in by_module.items()},
        "idle_over_10us_s": total["idle"] / n_dev / 1e9,
        "idle_gaps_s": {k: t / n_dev / 1e9 for k, t in sorted(
            gaps_named.items(), key=lambda kv: -kv[1])},
        "idle_named_by_program_share": (
            by_program / total["idle"] if total["idle"] else None),
    }


def find_xplane(path: str) -> str | None:
    """The file itself, or the newest trace under a profiler's directory."""
    return path if os.path.isfile(path) else find_in_dir(path)


def reduce_file(path: str, step_span: str = STEP_SPAN) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), event_op_names(path),
                          step_span)


if __name__ == "__main__":
    found = find_xplane(sys.argv[1])
    if found is None:
        sys.exit(f"no .xplane.pb under {sys.argv[1]}")
    print(json.dumps(reduce_file(found, *sys.argv[2:3]), indent=1))
