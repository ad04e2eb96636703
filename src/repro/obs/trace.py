"""Profiler spans and compile-time reports.

Three span flavours:

* :func:`span` — for *traced* code, always on: ``jax.named_scope`` so the
  subsystem boundary shows up as a scope prefix in the ``op_name``
  metadata of every op it emits, which the profiler's HLO-op view groups
  by and a device trace carries as each op's ``tf_op``.  Metadata only:
  with it stripped the compiled program is the same with or without the
  scope (asserted in ``tests/test_obs.py``).
* :func:`host_span` — for host code, always on:
  ``jax.profiler.TraceAnnotation``, a named region on the host timeline,
  on the same clock as the device ops.  About a microsecond when no
  profiler session records.
* :func:`step_span` — the launcher loop marker, only while obs is
  enabled: ``jax.profiler.StepTraceAnnotation`` so traces viewed in
  TensorBoard / Perfetto get per-step boundaries.

Plus the opt-in trace dump (:func:`start_profile` / :func:`stop_profile`
— ``--profile-steps`` on the launchers) and :func:`attach_hlo_report`,
which parses a jitted entrypoint's compiled HLO with
``repro.launch.hlo_stats`` and logs the predicted collective traffic so
runtime byte counters have a static yardstick to reconcile against.
"""

from __future__ import annotations

import contextlib

import jax

from repro.obs.registry import enabled, log_event

__all__ = [
    "span",
    "host_span",
    "step_span",
    "start_profile",
    "stop_profile",
    "attach_hlo_report",
]


def span(name: str):
    """Scope traced ops under ``name`` (``jax.named_scope``)."""
    return jax.named_scope(name)


def host_span(name: str):
    """Host-timeline annotation (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def step_span(name: str, step: int):
    """Per-step profiler marker (``StepTraceAnnotation``) when enabled."""
    if not enabled():
        yield
        return
    with jax.profiler.StepTraceAnnotation(name, step_num=step):
        yield


_PROFILING = False


def start_profile(log_dir: str) -> bool:
    """Begin a ``jax.profiler`` trace dump into ``log_dir`` (idempotent)."""
    global _PROFILING
    if _PROFILING:
        return False
    jax.profiler.start_trace(log_dir)
    _PROFILING = True
    log_event("obs.profile_started", log_dir=log_dir)
    return True


def stop_profile() -> bool:
    """End the running trace dump, if any."""
    global _PROFILING
    if not _PROFILING:
        return False
    jax.profiler.stop_trace()
    _PROFILING = False
    log_event("obs.profile_stopped")
    return True


def attach_hlo_report(name: str, hlo_or_lowered, **labels) -> dict | None:
    """Log the HLO-predicted collective traffic of a jitted entrypoint.

    ``hlo_or_lowered`` is compiled HLO text, or anything with
    ``.compile()`` (a ``jax.stages.Lowered``) or ``.as_text()`` (a
    ``Compiled``).  Returns the stats dict
    ``{total_bytes, per_op_bytes, op_counts}`` from
    ``repro.launch.hlo_stats.collective_bytes`` and emits it as an
    ``hlo.collectives`` event, so runtime per-peer byte counters can be
    reconciled against the compiler's schedule (the acceptance check in
    ``tests/_obs_check.py``).

    A report must never kill the launcher that asked for it: any failure
    (backend refusing to compile for introspection, HLO parse drift, …)
    is logged as an ``hlo.report_failed`` event carrying the exception
    type, and ``None`` is returned.
    """
    from repro.launch.hlo_stats import collective_bytes

    try:
        txt = hlo_or_lowered
        if hasattr(txt, "compile"):
            txt = txt.compile()
        if hasattr(txt, "as_text"):
            txt = txt.as_text()
        stats = collective_bytes(txt)
    except Exception as e:
        log_event(
            "hlo.report_failed",
            entry=name,
            error_type=type(e).__name__,
            error=repr(e),
            **labels,
        )
        return None
    log_event(
        "hlo.collectives",
        entry=name,
        total_bytes=stats["total_bytes"],
        per_op_bytes=stats["per_op_bytes"],
        op_counts=stats["op_counts"],
        **labels,
    )
    return stats
