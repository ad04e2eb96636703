"""Device time per engine step of the sampling programs: the batched
top-k or greedy sampler and the per-slot key derivation."""

from bench import trace as tr

PROGRAMS = ("jit_sample_topk_batched", "jit_sample_greedy",
            "jit__sample_keys")


def read(trace, facts, peak):
    steps = len(tr.spans(trace, "bench.engine_step"))
    ns = tr.program_ns(trace, *PROGRAMS)
    if not steps or not ns:
        return None
    return ns / steps * 1e-6
