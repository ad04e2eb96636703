"""Jit-safe metrics registry: record points that cost nothing when off.

The enable check happens at **trace time** (a plain Python ``if``), so a
disabled record point contributes zero operations to the jaxpr — the
compiled HLO of an instrumented function is identical to the
un-instrumented program, modulo debug metadata (asserted in
``tests/test_obs.py``).  When
enabled, a traced value rides a ``jax.debug.callback`` to the host,
where it is normalised (numpy -> plain Python) and appended to the
active sink as one JSONL-shaped record; a host value (a record point in
host code, such as the serving engine's) is appended directly, with no
callback and no device round trip.

Because enablement is baked in at trace time, toggling it must not let
stale compilations leak: :func:`enable` / :func:`disable` call
``jax.clear_caches()`` whenever the enabled state actually changes.
Swapping *sinks* while staying enabled is free — the baked-in callback
is a trampoline that reads the current sink at call time — which is what
lets ``capture()`` nest cheaply inside an enabled run.

Under ``vmap`` the callback fires once per lane; under ``shard_map``
once per device (pass ``lax.axis_index(axis)`` as a label to tell them
apart — array-valued labels are forwarded through the callback).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time

import numpy as np

__all__ = [
    "enable",
    "disable",
    "enabled",
    "capture",
    "record",
    "counter",
    "gauge",
    "histogram",
    "log_event",
    "set_step",
    "flush",
    "totals",
]

_log = logging.getLogger("repro.obs")

# Arrays longer than this are summarised instead of stored verbatim; the
# per-peer vectors the hot paths emit (p, k, E <= a few hundred) stay exact.
_MAX_VERBATIM = 1024


@dataclasses.dataclass
class _ObsState:
    enabled: bool = False
    sink: object | None = None
    step: int | None = None
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)


_STATE = _ObsState()


def enabled() -> bool:
    """Trace-time switch every record point checks first."""
    return _STATE.enabled


def enable(metrics_dir: str | None = None, sink=None) -> None:
    """Turn metric emission on.

    ``metrics_dir`` opens a :class:`repro.obs.sink.JsonlSink` there;
    ``sink`` passes an explicit sink (tests).  Exactly one must be given.
    Clears jit caches on the off->on transition so functions traced while
    disabled (callback-free HLO) are re-traced with their record points.
    """
    from repro.obs.sink import JsonlSink

    if (metrics_dir is None) == (sink is None):
        raise ValueError("enable() needs exactly one of metrics_dir / sink")
    if sink is None:
        sink = JsonlSink(metrics_dir)
    with _STATE.lock:
        was_enabled = _STATE.enabled
        old = _STATE.sink
        _STATE.sink = sink
        _STATE.enabled = True
    if old is not None and old is not sink:
        old.close()
    if not was_enabled:
        import jax

        jax.clear_caches()


def disable() -> None:
    """Turn emission off and drop the sink (flushing it first).

    Clears jit caches on the on->off transition: compilations traced
    while enabled carry callback ops and would silently keep emitting
    (into a dead sink) and keep their runtime cost.
    """
    with _STATE.lock:
        was_enabled = _STATE.enabled
        old, _STATE.sink = _STATE.sink, None
        _STATE.enabled = False
        _STATE.step = None
    if old is not None:
        old.close()
    if was_enabled:
        import jax

        jax.clear_caches()


@contextlib.contextmanager
def capture():
    """Collect records in memory for the duration of a ``with`` block.

    Yields the live ``list`` of record dicts.  If obs was already
    enabled, the previous sink is restored (not closed) on exit and no
    cache clearing happens; otherwise this is a scoped enable/disable.
    """
    from repro.obs.sink import ListSink

    sink = ListSink()
    with _STATE.lock:
        was_enabled, prev = _STATE.enabled, _STATE.sink
    if was_enabled:
        with _STATE.lock:
            _STATE.sink = sink
        try:
            yield sink.records
        finally:
            with _STATE.lock:
                _STATE.sink = prev
    else:
        enable(sink=sink)
        try:
            yield sink.records
        finally:
            disable()


def set_step(step: int | None) -> None:
    """Host-side step label stamped on subsequent records."""
    _STATE.step = None if step is None else int(step)


def flush() -> None:
    """Drain the active sink's buffer (launchers call this per step)."""
    import jax

    sink = _STATE.sink
    if sink is not None:
        # effects_barrier guarantees every already-dispatched callback has
        # landed before the buffer is written out.
        jax.effects_barrier()
        sink.flush()


def totals() -> dict[str, float]:
    """Running counter totals accumulated by the active sink."""
    sink = _STATE.sink
    return dict(sink.totals) if sink is not None else {}


# ---------------------------------------------------------------------------
# record points
# ---------------------------------------------------------------------------


def record(name: str, value, *, kind: str = "gauge", **labels) -> None:
    """The one record point: no-op when disabled; when enabled, a record
    made while tracing rides a callback, one made in host code goes
    straight to the sink.

    ``value`` may be a traced scalar or array, or a host number or array.
    ``labels`` are attached to the record; plain Python values stay
    host-side, traced values are forwarded through the callback (e.g.
    ``device=lax.axis_index("x")``).  Inside a trace the callback fires
    on every execution, whatever the value; outside one nothing is
    bound, so a record point in host code costs no device round trip.
    """
    if not _STATE.enabled:
        return
    import jax
    import jax.numpy as jnp

    if jax.core.trace_ctx.is_top_level():  # host code: nothing traced
        _emit(name, kind, np.asarray(value), {
            k: _normalise(np.asarray(v)) if isinstance(v, jax.Array) else v
            for k, v in labels.items()})
        return

    static = {}
    traced_keys: list[str] = []
    traced_vals = []
    for k, v in labels.items():
        if isinstance(v, jax.Array) or hasattr(v, "aval"):
            traced_keys.append(k)
            traced_vals.append(v)
        else:
            static[k] = v

    def _cb(v, *tv):
        lbl = dict(static)
        for k, t in zip(traced_keys, tv):
            lbl[k] = _normalise(np.asarray(t))
        _emit(name, kind, np.asarray(v), lbl)

    jax.debug.callback(_cb, jnp.asarray(value), *traced_vals)


def counter(name: str, inc=1, **labels) -> None:
    """Monotonic increment event (sinks accumulate ``totals[name]``)."""
    record(name, inc, kind="counter", **labels)


def gauge(name: str, value, **labels) -> None:
    """Point-in-time value; arrays are stored verbatim (<= 1024 elems)."""
    record(name, value, kind="gauge", **labels)


def histogram(name: str, values, **labels) -> None:
    """Distribution summary: count/min/p50/p90/max/sum of ``values``."""
    record(name, values, kind="histogram", **labels)


def log_event(name: str, **fields) -> None:
    """Host-side (untraced) event: config choices, compile reports.

    Always logged through ``logging.getLogger('repro.obs')``; also lands
    in the sink when metrics are enabled.  Never traced — safe to call
    from dispatch code that runs at trace time.
    """
    fields = {k: _normalise(v) for k, v in fields.items()}
    _log.info("%s %s", name, fields)
    if _STATE.enabled:
        _emit(name, "event", None, fields)


# ---------------------------------------------------------------------------
# host-side normalisation + emission
# ---------------------------------------------------------------------------


def _normalise(v):
    """numpy scalar/array -> plain Python (JSON-serialisable)."""
    if isinstance(v, np.ndarray):
        if v.ndim == 0:
            return v.item()
        return v.tolist()
    if isinstance(v, (np.generic,)):
        return v.item()
    return v


def _summary(arr: np.ndarray) -> dict:
    flat = arr.astype(np.float64).reshape(-1)
    return {
        "count": int(flat.size),
        "min": float(flat.min()),
        "p50": float(np.percentile(flat, 50)),
        "p90": float(np.percentile(flat, 90)),
        "max": float(flat.max()),
        "sum": float(flat.sum()),
    }


def _emit(name: str, kind: str, value, labels: dict) -> None:
    rec: dict = {"ts": time.time(), "metric": name, "kind": kind}
    if _STATE.step is not None:
        rec["step"] = _STATE.step
    if value is not None:
        arr = np.asarray(value)
        if kind == "histogram":
            rec.update(_summary(arr))
        elif arr.ndim > 0 and arr.size > _MAX_VERBATIM:
            rec.update(_summary(arr))
            rec["truncated"] = True
        else:
            rec["value"] = _normalise(arr)
    if labels:
        rec["labels"] = labels
    sink = _STATE.sink
    if sink is not None:
        sink.write(rec)
