"""Each cell cut to a size the CPU runs in seconds, its widths and
lengths scaled down together; everything else as committed, but for the
limits that depend on the size."""

import dataclasses

TINY_LM = {
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "vocab_size": 512, "serve": {"slots": 4, "max_len": 64},
}
TINY_CHAT = {
    "clients": 4,
    "prompt_len": {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
    "output_len": {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
    "requests": 64, "warm_steps": 10, "check_requests": 8,
    "trace_seconds": 1,
}
TINY_MERGE_N = 1 << 14
#: ``kv_rel_err`` scales with width and depth: at this size the program
#: read 0.0078-0.0086 and the int8 control 0.0271-0.031 on 8 seeds per
#: serving cell (CPU), so the limit sits between them as the cell's own
#: does between its chip readings
TINY_KV_LIMIT = 0.016


def tiny(cell):
    """``cell`` at the size of a CPU test; everything else as committed."""
    if cell.traffic["driver"] == "serve":
        kv = dict(cell.limits["kv_rel_err"], limit=TINY_KV_LIMIT)
        return dataclasses.replace(
            cell, config=dict(cell.config, **TINY_LM),
            traffic=dict(cell.traffic, **TINY_CHAT),
            limits=dict(cell.limits, kv_rel_err=kv))
    return dataclasses.replace(
        cell, config=dict(cell.config, n=TINY_MERGE_N),
        traffic=dict(cell.traffic, trace_seconds=1))
