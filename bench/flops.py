"""Operations and bytes the work needs, from shapes alone.

They count what the algorithm must do, whatever implements it, so a
later change that does the same work another way is measured against
the same numbers.
"""

from __future__ import annotations


def dense_matmul_params(cfg: dict) -> int:
    """Weights a token meets in matrix products: every layer's attention
    projections and SwiGLU MLP, and the (tied) unembedding.  The embedding
    lookup is a gather, not a product."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or d // h
    per_layer = d * hd * (2 * h + 2 * kv) + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def attention_flops(cfg: dict, context: int) -> int:
    """Scores and weighted values of one token over ``context`` cached
    positions, all layers: 2 * (q.k + p.v) per head and position."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return cfg["num_hidden_layers"] * 4 * h * hd * context


def serve_token_flops(cfg: dict, context: int) -> int:
    """Model FLOPs of one processed token that attends over ``context``
    positions (its own included)."""
    return 2 * dense_matmul_params(cfg) + attention_flops(cfg, context)


def merge_min_bytes(real_elements: int, key_bytes: int,
                    payload_bytes: int = 0) -> int:
    """Least HBM traffic of a merge: every real key (and payload value)
    read once and written once.  Its operations (one comparison per
    element and level) are negligible against these bytes on any chip."""
    return 2 * real_elements * (key_bytes + payload_bytes)
