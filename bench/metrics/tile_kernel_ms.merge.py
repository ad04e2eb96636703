"""Device time per merge call of the Pallas tile-merge kernel
(``kernels/merge.py``)."""

from bench import trace as tr

#: the Pallas custom calls, named after ``kernels.merge.merge_kway_pallas``
KERNEL = r"^%merge_kway_pallas[\w.]* = .*? custom-call\("


def read(trace, facts, peak):
    calls = len(tr.spans(trace, "bench.merge_call"))
    ns = tr.ops_ns(trace, KERNEL)
    if not calls or not ns:
        return None
    return ns / calls * 1e-6
