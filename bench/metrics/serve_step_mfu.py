"""Whole serving step's share of the chip's bf16 peak: model FLOPs of the
tokens processed in the traced window (one per active slot per step:
2 x matmul weights, unembedding included, plus attention over each slot's
cached positions; ``bench/flops.py``) over the window's seconds."""

from bench import trace as tr


def read(trace, facts, peak):
    if peak is None or not facts.get("flops") or not tr.spans(
            trace, "bench.engine_step"):
        return None
    return 100.0 * facts["flops"] / trace.window_s / peak["bf16_flops_per_s"]
