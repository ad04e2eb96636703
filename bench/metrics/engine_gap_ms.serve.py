"""Device-idle time per engine step inside the benchmark's host span
around ``DecodeEngine.step``: the engine's host work (admission, array
building, dispatch, the wait for sampled tokens) that the device waits
on."""

from bench import trace as tr

SPAN = "bench.engine_step"


def read(trace, facts, peak):
    steps = len(tr.spans(trace, SPAN))
    if not steps or not trace.ops:
        return None
    return tr.idle_in_spans(trace, SPAN) / steps * 1e-6
