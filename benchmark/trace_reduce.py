"""From a profiler trace (.xplane.pb) to device busy time, idle share, the
operations that took most time and the longest idle gaps.

Busy is the union of the intervals in which an operation ran on a device,
idle share is 1 - busy / window, and the window is the span from the first
device operation's start to the last one's end, per device, averaged over
the devices that ran anything. Gaps are named by the benchmark's own host
span (names starting ``bench:``) that covers the middle of the gap."""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
SHORT_GAP_NS = 10_000
NAME_CHARS = 120  # an XLA op's trace name runs to a thousand characters


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _ops_line(plane):
    lines = list(plane.lines)
    for line in lines:
        if line.name == OPS_LINE:
            return line
    return max(lines, key=lambda l: sum(1 for _ in l.events), default=None)


def _union(intervals):
    """Sorted (start, end) pairs -> merged busy pairs."""
    merged = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce_profile(profile, top: int = 10) -> dict | None:
    """``profile``: a jax.profiler.ProfileData. None when no device plane
    holds an event (nothing to read)."""
    spans = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            line = _ops_line(plane)
            if line is None:
                continue
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in line.events]
            if events:
                devices.append((plane.name, sorted(events)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if not devices:
        return None
    busy_s, window_s = [], []
    op_time: dict = {}
    gaps = []
    for _, events in devices:
        merged = _union((s, e) for s, e, _ in events)
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        window_s.append((merged[-1][1] - merged[0][0]) / 1e9)
        for s, e, name in events:
            name = name[:NAME_CHARS]
            op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
        gaps.extend((b[0] - a[1], (a[1] + b[0]) / 2)
                    for a, b in zip(merged, merged[1:]))
    n = len(devices)
    spans.sort()
    starts = [s for s, _, _ in spans]
    gap_time: dict = {}
    for dur, mid in gaps:
        if dur < SHORT_GAP_NS:
            name = "between_ops_under_10us"
        else:
            # the innermost benchmark span that covers the gap's middle
            name = "unattributed"
            for s, e, nm in reversed(spans[:bisect.bisect_right(starts, mid)]):
                if e >= mid:
                    name = nm
                    break
        gap_time[name] = gap_time.get(name, 0.0) + dur / 1e9
    busy, window = sum(busy_s) / n, sum(window_s) / n
    ranked = lambda d: [[k, v / n] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "devices": n,
        "busy_s": busy,
        "window_s": window,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gap_time),
        "longest_gap_s": max((g[0] for g in gaps), default=0) / 1e9,
    }


def reduce_file(path: str, top: int = 10) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top)
