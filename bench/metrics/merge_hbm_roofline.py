"""Whole merge call's share of its HBM roofline: the least bytes the
merges of the traced window need (every real key and payload value read
once and written once; ``bench/flops.py``) at the chip's peak HBM
bandwidth, over the device time of the merge program.  Its operations
are negligible, so bandwidth bounds it."""

from bench import trace as tr


def read(trace, facts, peak):
    ns = tr.program_ns(trace, facts["program"])
    if peak is None or not ns or not facts.get("calls"):
        return None
    return 100.0 * facts["min_bytes"] / peak["hbm_bytes_per_s"] / (ns * 1e-9)
