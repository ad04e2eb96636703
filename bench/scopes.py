#!/usr/bin/env python3
"""The program's own scopes and host spans in a profiler trace.

    python3 bench/scopes.py <trace directory or .xplane.pb>

prints, per ``bench.merge_call`` or ``bench.engine_step`` span of a kept
``--trace 1`` trace (``bench/run.py --keep-trace <dir>``), the device
time of each ``repro.*`` scope and the device-idle time inside each
``serve.*`` host span, beside the device time of the programs they lie
in.

Scopes: the program wraps its phases in ``obs.span`` (``jax.named_scope``),
which writes each op's ``op_name`` path, e.g.
``jit(ragged_step)/repro.decoder_layers/while/body/dynamic_slice``.  A
device trace keeps it as the ``tf_op`` stat of the op's event metadata,
which ``jax.profiler.ProfileData`` does not expose, so :func:`op_scopes`
reads it from the file's protobuf wire format
(``XSpace.planes -> XPlane.event_metadata -> XEventMetadata.stats``).

Host spans: the serving engine wraps each phase of a step in
``obs.host_span`` (``jax.profiler.TraceAnnotation``), which the trace
holds as host events on the device ops' clock; :func:`idle_in` is the
device-idle time inside them.
"""

from __future__ import annotations

import bisect
import pathlib
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

#: field numbers of the ``XSpace`` protos (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_META_ID, _META_NAME, _META_STATS = 1, 2, 5  # X{Event,Stat}Metadata
_STAT_METADATA_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_SCOPE = "tf_op"

#: the scopes a phase of each program runs under (``obs.span`` names)
MERGE_SCOPES = ("repro.corank", "repro.slab_relayout", "repro.tile_merge")
TOPK_SCOPES = ("repro.topk_block_sort", "repro.topk_tournament",
               "repro.topk_draw")
LAYER_SCOPES = ("repro.attention", "repro.ffn")
#: the serving engine's host spans, inside ``serve.step``
PREP_SPANS = ("serve.admit", "serve.build_inputs", "serve.dispatch")
RETIRE_SPANS = ("serve.fetch_tokens", "serve.retire")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of each field in ``buf[lo:hi]``: an int
    for a varint, a ``(lo, hi)`` range for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, entry: tuple[int, int]):
    return [v for f, v in _fields(buf, *entry) if f == _MAP_VALUE]


def op_scopes(path: str) -> dict[tuple[str, str], str]:
    """``{(device plane, op event name): op_name path}`` of every device
    op whose event metadata carries a scope (a plane names each event's
    metadata once, so the name is the key)."""
    buf = pathlib.Path(path).read_bytes()
    out: dict[tuple[str, str], str] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != _SPACE_PLANES:
            continue
        name, stat_names, metas = "", {}, []
        for f, v in _fields(buf, *plane):
            if f == _PLANE_NAME:
                name = _text(buf, v)
            elif f == _PLANE_STAT_METADATA:
                for meta in _map_values(buf, v):
                    got = dict(_fields(buf, *meta))
                    stat_names[got.get(_META_ID)] = _text(
                        buf, got.get(_META_NAME, (0, 0)))
            elif f == _PLANE_EVENT_METADATA:
                metas += _map_values(buf, v)
        if not tr.DEVICE_PLANE.match(name):
            continue
        scope_id = {v: k for k, v in stat_names.items()}.get(_STAT_SCOPE)
        for meta in metas:
            op, scope = "", ""
            for f, v in _fields(buf, *meta):
                if f == _META_NAME:
                    op = _text(buf, v)
                elif f == _META_STATS:
                    stat = dict(_fields(buf, *v))
                    if stat.get(_STAT_METADATA_ID) != scope_id:
                        continue
                    if _STAT_STR in stat:
                        scope = _text(buf, stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        scope = stat_names.get(stat[_STAT_REF], "")
            if scope:
                # ``<op_name>:<op type>``; the type is empty for XLA ops
                out[(name, op)] = scope.rsplit(":", 1)[0]
    return out


def exclusive_ns(events) -> list[tuple[tr.Event, int]]:
    """Each event with its exclusive time on one device: its duration
    less that of the events nested directly in it (a ``while`` holds its
    body), as ``trace.self_ns`` counts it."""
    out: list[list] = []
    stack: list[list] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        if stack:
            stack[-1][1] -= e.end - e.start
        item = [e, e.end - e.start]
        out.append(item)
        stack.append(item)
    return [(e, ns) for e, ns in out]


def scoped_ops(trace: tr.Trace, scopes: dict) -> list:
    """``(op, exclusive ns, scope path)`` of every device op in the
    window, in time order on each device.

    XLA drops the ``op_name`` of some fusions (on a v5e, the scatters of
    the top-k's ranked merges); an op without one takes the path of the
    op before it in the same program execution on its device, and ``""``
    where none came before."""
    ops = tr.in_window(trace, trace.ops)
    out = []
    for plane in sorted({e.where for e in ops}):
        runs = sorted((m for m in trace.modules if m.where == plane),
                      key=lambda m: m.start)
        starts = [m.start for m in runs]
        last, last_run = "", None
        for e, ns in exclusive_ns([e for e in ops if e.where == plane]):
            i = bisect.bisect_right(starts, e.start) - 1
            run = i if i >= 0 and runs[i].end > e.start else None
            path = scopes.get((plane, e.name))
            if path is None:
                path = last if run is not None and run == last_run else ""
            last, last_run = path, run
            out.append((e, ns, path))
    return out


def in_scope(path: str, scope: str, exclude=()) -> bool:
    """Whether ``scope`` is a component of the path and none of
    ``exclude`` is."""
    parts = path.split("/")
    return scope in parts and not any(x in parts for x in exclude)


def scoped_ns(trace: tr.Trace, scopes: dict, scope: str,
              exclude=()) -> int:
    """Device time in the window of the ops whose scope path holds
    ``scope`` and none of ``exclude`` (exclusive times, so a scoped
    ``while`` counts its own time and not its body's)."""
    return sum(ns for _, ns, path in scoped_ops(trace, scopes)
               if in_scope(path, scope, exclude))


def idle_in(trace: tr.Trace, names) -> int:
    """Device-idle nanoseconds inside the host events called one of
    ``names`` (``trace.busy`` averaged over the devices, from one cover
    of the ops instead of one per event)."""
    want = set(names)
    events = [h for h in trace.host if h.name in want]
    covers = []
    for plane in sorted({e.where for e in trace.ops}):
        cover = tr.union((e.start, e.end) for e in trace.ops
                         if e.where == plane)
        covers.append((cover, [a for a, _ in cover]))
    total = 0
    for h in events:
        busy = 0
        for cover, starts in covers:
            i = max(bisect.bisect_right(starts, h.start) - 1, 0)
            while i < len(cover) and cover[i][0] < h.end:
                a, b = cover[i]
                busy += max(0, min(b, h.end) - max(a, h.start))
                i += 1
        total += (h.end - h.start) - busy / max(trace.devices, 1)
    return total


def split(trace: tr.Trace, scopes: dict) -> dict[str, float]:
    """Milliseconds per call or step of each scope and engine phase that
    the trace holds, with the programs' own device time beside them."""
    calls = len(tr.spans(trace, "bench.merge_call"))
    steps = len(tr.spans(trace, "bench.engine_step"))
    per = calls or steps
    if not per or not trace.ops:
        return {}
    ms = lambda ns: ns / per * 1e-6  # noqa: E731
    programs: dict[str, int] = {}
    for e in tr.in_window(trace, trace.modules):
        name = e.name.split("(")[0]
        programs[name] = programs.get(name, 0) + e.end - e.start
    out = {f"program {k}": ms(v) for k, v in sorted(programs.items())}
    ops = scoped_ops(trace, scopes)
    parts = {scope: (scope, ()) for scope in
             MERGE_SCOPES + TOPK_SCOPES + LAYER_SCOPES}
    parts["repro.decoder_layers (scan's own)"] = ("repro.decoder_layers",
                                                   LAYER_SCOPES)
    for name, (scope, exclude) in parts.items():
        out[name] = ms(sum(ns for _, ns, path in ops
                           if in_scope(path, scope, exclude)))
    out["ops without a scope path"] = ms(sum(ns for _, ns, path in ops
                                             if not path))
    if steps:
        for name in PREP_SPANS + RETIRE_SPANS + ("serve.step",):
            out[f"idle in {name}"] = ms(idle_in(trace, [name]))
        out["idle in bench.engine_step"] = ms(
            tr.idle_in_spans(trace, "bench.engine_step"))
    return {k: v for k, v in out.items() if v}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = args[0]
    if pathlib.Path(path).is_dir():
        path = tr.find_xplane(path)
    trace = tr.load(path)
    for name, value in split(trace, op_scopes(path)).items():
        print(f"{value:14.4f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
