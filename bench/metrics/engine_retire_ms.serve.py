"""Device-idle time per engine step inside the serving engine's host
spans that end the step: the wait for the sampled tokens
(``serve.fetch_tokens``) and the per-slot bookkeeping and retirement
(``serve.retire``)."""

from bench import scopes
from bench import trace as tr


def read(trace, facts, peak):
    steps = len(tr.spans(trace, "bench.engine_step"))
    if not steps or not trace.ops or not any(
            h.name in scopes.RETIRE_SPANS for h in trace.host):
        return None
    return scopes.idle_in(trace, scopes.RETIRE_SPANS) / steps * 1e-6
