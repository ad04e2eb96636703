"""Device time per engine step of the jitted ragged decode step (the
whole model: embedding, every layer through the KV cache, unembedding)."""

from bench import trace as tr


def read(trace, facts, peak):
    steps = len(tr.spans(trace, "bench.engine_step"))
    ns = tr.program_ns(trace, "jit_ragged_step")
    if not steps or not ns:
        return None
    return ns / steps * 1e-6
