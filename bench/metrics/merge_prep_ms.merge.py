"""Device time per merge call of the merge program outside the tile
kernel: the co-rank of every tile boundary (``core/kway.py``,
``core/engine.py``) and the relayout of runs into slabs."""

from bench import trace as tr

#: the Pallas custom calls, named after ``kernels.merge.merge_kway_pallas``
KERNEL = r"^%merge_kway_pallas[\w.]* = .*? custom-call\("


def read(trace, facts, peak):
    calls = len(tr.spans(trace, "bench.merge_call"))
    ns = tr.program_ns(trace, facts["program"])
    if not calls or not ns:
        return None
    return (ns - tr.ops_ns(trace, KERNEL)) / calls * 1e-6
