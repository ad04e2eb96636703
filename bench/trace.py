"""From a profiler trace (``.xplane.pb``) to the numbers per-layer metrics
read: device busy time, device time per program and per op, and device
idle time inside the benchmark's own host spans.

Device planes are those named ``/device:TPU:<n>``.  On each, the ``XLA
Modules`` line holds one event per program execution, named after the
jitted function (``jit_ragged_step(...)``), and the ``XLA Ops`` line one
event per operation.  Host planes hold the benchmark's spans
(``jax.profiler.TraceAnnotation`` names starting ``bench.``) and what
the host was doing between them.  All events share the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns on the trace's clock
    end: int
    where: str = ""  # device plane, or host thread


@dataclasses.dataclass
class Trace:
    ops: list  # Event per device op, ``where`` = device plane
    modules: list  # Event per program execution on a device
    spans: list  # the benchmark's host spans
    host: list  # every other host event with a duration
    devices: int

    @property
    def window(self) -> tuple[int, int]:
        """First span start to last span end: the traced window."""
        if not self.spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        return min(s.start for s in self.spans), max(s.end for s in self.spans)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} .xplane.pb files under {directory}, want 1")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans, host = [], [], [], []
    devices = 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices += 1
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                into = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    start = int(e.start_ns)
                    into.append(Event(e.name, start, start + int(e.duration_ns),
                                      plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    start = int(e.start_ns)
                    ev = Event(e.name, start, start + int(e.duration_ns),
                               line.name)
                    (spans if e.name.startswith(SPAN_PREFIX) else host).append(ev)
    return Trace(ops, modules, spans, host, devices)


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def covered(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that disjoint ``intervals`` cover."""
    return sum(b - a for a, b in clip(intervals, lo, hi))


def busy(trace: Trace, lo: int | None = None, hi: int | None = None) -> float:
    """Device-busy nanoseconds in ``[lo, hi)`` (the traced window when
    unset): the union of op intervals on each device, averaged over the
    devices."""
    if lo is None:
        lo, hi = trace.window
    total = 0
    for plane in sorted({e.where for e in trace.ops}):
        total += covered(union((e.start, e.end) for e in trace.ops
                               if e.where == plane), lo, hi)
    return total / max(trace.devices, 1)


def in_window(trace: Trace, events) -> list:
    lo, hi = trace.window
    return [e for e in events if e.end > lo and e.start < hi]


def program_ns(trace: Trace, *names: str) -> int:
    """Device time of the executions of the named jitted programs (a
    module event is named ``jit_<function>(<id>)``) inside the window."""
    want = set(names)
    return sum(e.end - e.start for e in in_window(trace, trace.modules)
               if e.name.split("(")[0] in want)


def ops_ns(trace: Trace, pattern: str) -> int:
    """Device time of the ops whose name matches ``pattern`` in the window."""
    rx = re.compile(pattern)
    return sum(e.end - e.start for e in in_window(trace, trace.ops)
               if rx.search(e.name))


def spans(trace: Trace, name: str) -> list:
    return [s for s in trace.spans if s.name == name]


def idle_in_spans(trace: Trace, name: str) -> int:
    """Device-idle nanoseconds inside the host spans called ``name``."""
    total = 0
    for s in spans(trace, name):
        total += (s.end - s.start) - busy(trace, s.start, s.end)
    return total


def short_name(op: str) -> str:
    """``%fusion.46 = s32[...] fusion(...)`` -> ``fusion.46``."""
    return op.split(" = ", 1)[0].lstrip("%")


def self_ns(events) -> dict[str, int]:
    """Exclusive time per op name on one device: an op's duration less
    that of the ops nested directly in it (a ``while`` holds its body)."""
    out: dict[str, int] = {}
    stack: list[Event] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            out[stack[-1].name] -= e.end - e.start
        out[e.name] = out.get(e.name, 0) + e.end - e.start
        stack.append(e)
    return out


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The ``n`` device ops with most exclusive time in the window, and the
    ``n`` longest idle gaps on the first device, each named by the
    narrowest host event around its middle."""
    lo, hi = trace.window
    first = min((e.where for e in trace.ops), default=None)
    by_op: dict[str, int] = {}
    for name, ns in self_ns([e for e in in_window(trace, trace.ops)
                             if e.where == first]).items():
        key = short_name(name)
        by_op[key] = by_op.get(key, 0) + ns
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
    cover = union((e.start, e.end) for e in trace.ops if e.where == first)
    gaps, t = [], lo
    for a, b in clip(cover, lo, hi) + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    named = []
    for a, b in gaps:
        mid = (a + b) // 2
        around = [h for h in trace.host + trace.spans if h.start <= mid < h.end]
        what = min(around, key=lambda h: h.end - h.start).name if around else "host idle"
        named.append([what, (b - a) * 1e-9])
    return {"device_ops": [[k, v * 1e-9] for k, v in top], "idle_gaps": named}
