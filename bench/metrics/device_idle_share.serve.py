"""Share of the traced serving window in which no op ran on the device."""

from bench import trace as tr


def read(trace, facts, peak):
    if not trace.ops or not tr.spans(trace, "bench.engine_step"):
        return None
    return 100.0 * (1.0 - tr.busy(trace) * 1e-9 / trace.window_s)
