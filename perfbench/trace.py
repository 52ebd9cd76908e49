"""From a profiler trace to device busy time, idle gaps and top ops.

The benchmark wraps each call it makes into a layer in a
``jax.profiler.TraceAnnotation`` (host spans) and the whole measured
window in one span named ``WINDOW``.  ``load`` keeps, from the
``.xplane.pb`` the profiler writes, the device's op events and those host
spans; ``reduce`` then computes, inside the window:

- ``busy_s``: the union of the intervals in which an op ran on a device,
  averaged over the devices seen;
- ``idle_share``: one minus busy over the window;
- ``device_ops``: seconds per op name, the longest first;
- ``idle_gaps``: the idle time of the first device, each gap given to
  the benchmark span that overlaps it most (``no_span`` where none does),
  summed per span name, the longest first.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

WINDOW = "window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "no_span"


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    return files[0]


def load(path: str, span_names) -> dict:
    """Device op events per device and the named host spans, in ns."""
    from jax.profiler import ProfileData

    span_names = set(span_names) | {WINDOW}
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in span_names)
    return {"devices": devices, "spans": spans}


def _union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle time, top ops and attributed gaps inside the window.

    Returns None where the trace holds no window span or no device op in
    it: then there is nothing to read.
    """
    windows = [(s, s + d) for name, s, d in events["spans"]
               if name == WINDOW]
    if not windows:
        return None
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    busy_per_device, op_time = [], collections.Counter()
    first_union = None
    for dev in sorted(events["devices"]):
        ivs = []
        for name, s, d in events["devices"][dev]:
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                ivs.append((a, b))
                op_time[name] += (b - a) * 1e-9
        union = _union(ivs)
        if not union:
            continue
        busy_per_device.append(sum(b - a for a, b in union) * 1e-9)
        if first_union is None:
            first_union = union
    if not busy_per_device:
        return None
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_per_device) / len(busy_per_device)
    gaps, cur = [], lo
    for a, b in first_union:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    spans = sorted((s, s + d, name) for name, s, d in events["spans"]
                   if name != WINDOW)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    idle = collections.Counter()
    for a, b in gaps:
        overlap = collections.Counter()
        # spans that start before the gap ends and may still reach into it
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and spans[i][0] >= a - longest:
            s, e, name = spans[i]
            x, y = _clip(s, e, a, b)
            if y > x:
                overlap[name] += y - x
            i -= 1
        label = overlap.most_common(1)[0][0] if overlap else NO_SPAN
        idle[label] += (b - a) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[k, v] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }
