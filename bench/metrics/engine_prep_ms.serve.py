"""Device-idle time per engine step inside the serving engine's host
spans that prepare the step: admission (``serve.admit``), the input
arrays and their transfer (``serve.build_inputs``) and the dispatch of
the decode step and the sampler (``serve.dispatch``)."""

from bench import scopes
from bench import trace as tr


def read(trace, facts, peak):
    steps = len(tr.spans(trace, "bench.engine_step"))
    if not steps or not trace.ops or not any(
            h.name in scopes.PREP_SPANS for h in trace.host):
        return None
    return scopes.idle_in(trace, scopes.PREP_SPANS) / steps * 1e-6
