"""Device time by stage of the forward, from a profiler trace.

The program runs each stage of its forward (``repro.core.propagation.
STAGES``) under a ``donn.<stage>`` named scope, and ``propagation.
stage_map()`` gives, for every cached executable, {(module, HLO
instruction): stage} from the compiled program's op_name metadata.  The
program also writes host spans named ``donn.*`` (``donn.dispatch`` around
each launch, ``donn.compile`` around each compile).  This module joins the
two:

- ``load`` reads what ``trace.load`` reads (device ops of the ``XLA Ops``
  line, the benchmark's spans, the ``window`` span) and, beside each
  device op, its HLO module and instruction; it also keeps the ``donn.*``
  host spans;
- ``stage_seconds`` gives each stage its self time inside the window: an
  op's duration minus the part of it that ops nested in it on the same
  line cover, averaged over the devices that ran ops, as ``busy_s`` is.
  Ops the map does not know, container ops (while, conditional, call: the
  map leaves them out) included, count as ``unscoped``, so the stages sum
  to the busy time;
- ``program_gaps`` gives each idle gap of the first device to the
  innermost ``donn.*`` span over it, ``no_span`` where none is.

``trace.reduce`` reads the same loaded events unchanged.
"""
from __future__ import annotations

import bisect
import collections
import re

from perfbench import trace

PROGRAM_SPAN_PREFIX = "donn."
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
# a TPU op event is named after its HLO text ("%fusion.12 = f32[...] ..."),
# a CPU one after its instruction; a module event after the module
# ("jit_run(12)")
_INSTR = re.compile(r"^%?([\w.\-]+)")
_MODULE = re.compile(r"^([^(\s]+)")


def _stats(event) -> dict:
    return {k: str(v) for k, v in event.stats}


def _module_at(modules, t):
    """The module whose event on the device covers time ``t``, or None."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return None


def load(path: str, span_names) -> dict:
    """``trace.load``'s events plus ``hlo`` (per device, the (module,
    instruction) of each op, in the order of ``devices``) and
    ``program_spans`` (the ``donn.*`` host spans), in ns."""
    from jax.profiler import ProfileData

    span_names = set(span_names) | {trace.WINDOW}
    data = ProfileData.from_file(path)
    devices, hlo, spans, program = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            modules = []
            if MODULES_LINE in lines:
                for e in lines[MODULES_LINE].events:
                    m = _MODULE.match(e.name)
                    modules.append((e.start_ns, e.start_ns + e.duration_ns,
                                    m.group(1) if m else e.name))
                modules.sort()
            ops = devices.setdefault(plane.name, [])
            keys = hlo.setdefault(plane.name, [])
            if trace.OPS_LINE in lines:
                for e in lines[trace.OPS_LINE].events:
                    st = _stats(e)
                    m = _INSTR.match(e.name)
                    instr = st.get("hlo_op") or (m.group(1) if m else e.name)
                    module = st.get("hlo_module") or _module_at(
                        modules, e.start_ns)
                    ops.append((e.name, e.start_ns, e.duration_ns))
                    keys.append((module, instr))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM_SPAN_PREFIX):
                        program.append((e.name, e.start_ns, e.duration_ns))
    return {"devices": devices, "spans": spans, "hlo": hlo,
            "program_spans": program}


def _window(events):
    windows = [(s, s + d) for name, s, d in events["spans"]
               if name == trace.WINDOW]
    if not windows:
        return None
    return min(a for a, _ in windows), max(b for _, b in windows)


def _self_ns(ops, lo, hi) -> list:
    """Self time in [lo, hi] of each of ``ops`` [(start, end)] on one line:
    its clipped length minus what the ops directly nested in it cover."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    self_ns = [0.0] * len(ops)
    stack = []  # indices of the ops that enclose the current one
    for i in order:
        a, b = ops[i]
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        x, y = trace._clip(a, b, lo, hi)
        self_ns[i] += max(0.0, y - x)
        if stack:
            p = stack[-1]
            px, py = trace._clip(*ops[p], lo, hi)
            cx, cy = max(x, px), min(y, py)
            if cy > cx:
                self_ns[p] -= cy - cx
        stack.append(i)
    return self_ns


def stage_seconds(events: dict, stage_map: dict) -> dict:
    """{stage: self seconds inside the window}, ``unscoped`` included,
    averaged over the devices that ran ops there; None without a window."""
    win = _window(events)
    if win is None:
        return None
    lo, hi = win
    totals, seen = collections.Counter(), 0
    for dev in sorted(events["devices"]):
        ops = events["devices"][dev]
        keys = events.get("hlo", {}).get(dev) or [(None, None)] * len(ops)
        spans = [(s, s + d) for _, s, d in ops]
        self_ns = _self_ns(spans, lo, hi)
        if not any(t > 0 for t in self_ns):
            continue
        seen += 1
        for key, t in zip(keys, self_ns):
            totals[stage_map.get(key, UNSCOPED)] += t
    if not seen:
        return None
    return {k: v * 1e-9 / seen for k, v in totals.items()}


def program_gaps(events: dict) -> dict:
    """{span name: idle seconds} of the first device inside the window,
    each stretch of a gap given to the innermost ``donn.*`` span over it
    (the one that started last), ``no_span`` where none is."""
    win = _window(events)
    if win is None:
        return None
    lo, hi = win
    busy = []
    for dev in sorted(events["devices"]):
        busy = trace._union(
            iv for iv in (trace._clip(s, s + d, lo, hi)
                          for _, s, d in events["devices"][dev])
            if iv[1] > iv[0])
        if busy:
            break
    if not busy:
        return None
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    spans = sorted((s, s + d, name) for name, s, d in events["program_spans"])
    idle = collections.Counter()
    for a, b in gaps:
        over = [(s, e, n) for s, e, n in spans if s < b and e > a]
        cuts = sorted({a, b} | {t for s, e, _ in over for t in (s, e)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            inside = [(s, n) for s, e, n in over if s <= x and e >= y]
            label = max(inside)[1] if inside else trace.NO_SPAN
            idle[label] += (y - x) * 1e-9
    return dict(idle)
