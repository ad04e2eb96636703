"""Top-k selection built on the k-way co-rank merge primitive.

Two-stage tournament (the classic distributed-selection shape, with
every stage expressed as stable merges):

  1. split the row into blocks of ``block`` elements, merge-sort each
     block descending (vectorised over blocks),
  2. collapse all per-block candidate lists with a *k-way* candidate
     merge: groups of up to ``fanout`` lists merge in one co-ranked step
     and only the top ``k`` of each merged ``fanout*k`` list survive.
     With ``nb <= fanout`` blocks the whole tournament is a single k-way
     merge; otherwise it takes ``log_fanout(nb)`` rounds instead of the
     pairwise tree's ``log2(nb)``.

The batched form (:func:`merge_topk_batch`) runs the same tournament
over ``b`` rows at once: the batch is just a leading group dimension on
every block sort and candidate merge, so a whole decode batch's top-k
costs **one** ``merge_kway_ranked`` cut per round instead of ``b``
per-request tournaments — the serving-side formulation
(``repro.serving.sampling``).  Per-row results are bit-identical to the
single-row :func:`merge_topk` by construction: the row-wise operations
never read across rows (group reshapes always tile within a row).

Stability: equal keys resolve to the lower original index (lower run
index wins ties in the k-way merge, and the in-block sort is stable),
matching ``jax.lax.top_k`` semantics.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.mergesort import (
    DEFAULT_FANOUT,
    _padded_pow2,
    merge_runs_ranked,
    sentinel_max,
)

__all__ = [
    "merge_topk",
    "merge_topk_batch",
    "candidate_blocks",
    "tournament_rounds",
]

# Candidate lists merged per tournament round; 16 collapses any
# realistic block count in one or two rounds.
TOURNAMENT_FANOUT = 16


def _desc_sort_blocks(keys: jax.Array, vals: jax.Array):
    """Stable ascending sort within each row of ``keys``/``vals`` (r, w)."""
    r, w = keys.shape
    width = 1
    k, v = keys, vals
    while width < w:
        group = min(DEFAULT_FANOUT, w // width)
        g = (r * w) // (group * width)
        k2, v2 = merge_runs_ranked(
            k.reshape(g, group, width), v.reshape(g, group, width)
        )
        k, v = k2.reshape(r, w), v2.reshape(r, w)
        width *= group
    return k, v


def candidate_blocks(n: int, k: int, block: int = 128) -> tuple[int, int]:
    """Static stage-1 shape of the tournament for a row of ``n`` logits:
    ``(resolved block width, number of candidate runs)``.  The block is
    rounded to a power of two >= k so the in-block sort's run reshapes
    stay aligned."""
    block = _padded_pow2(max(block, k))
    return block, -(-n // block)


def tournament_rounds(nb: int, fanout: int = 0) -> list[int]:
    """Run counts *entering* each tournament round (after padding to a
    group multiple), for ``nb`` stage-1 candidate runs.

    ``len()`` of the result is the number of ``merge_kway_ranked`` cuts
    a top-k takes; the last entry times ``k`` is the candidate count of
    the final cut.  Empty when ``nb <= 1`` (no merging needed).  The
    serving layer records both as ``serve.topk_*`` metrics.
    """
    fanout = fanout or TOURNAMENT_FANOUT
    rounds = []
    r = nb
    while r > 1:
        group = min(fanout, r)
        if r % group:
            r += group - r % group
        rounds.append(r)
        r //= group
    return rounds


@partial(jax.jit, static_argnames=("k", "block", "fanout"))
def merge_topk_batch(x: jax.Array, k: int, block: int = 128,
                     fanout: int = 0):
    """Row-wise top-k of a 2-D array: ``(b, n) -> (values, indices)``,
    both ``(b, k)`` descending.

    The whole batch moves through every stage together: one vectorised
    block sort and one ``merge_runs_ranked`` call per tournament round,
    regardless of ``b`` — group reshapes tile strictly within rows, so
    row ``i`` of the result equals ``merge_topk(x[i], ...)`` bit for bit.

    Keys are negated so the underlying ascending stable merge yields a
    descending order with ties broken toward the lower index.
    ``fanout=0`` (the config-field convention) means
    ``TOURNAMENT_FANOUT``.
    """
    fanout = fanout or TOURNAMENT_FANOUT
    if fanout < 2:
        raise ValueError(f"fanout must be >= 2, got {fanout}")
    b, n = x.shape
    block, nb = candidate_blocks(n, k, block)
    pad = nb * block - n
    with obs.span("repro.topk_block_sort"):
        neg = (-x.astype(jnp.float32)
               if jnp.issubdtype(x.dtype, jnp.floating) else -x)
        sentinel = sentinel_max(neg.dtype)
        keys = jnp.concatenate(
            [neg, jnp.full((b, pad), sentinel, neg.dtype)], axis=1
        )
        idx = jnp.broadcast_to(
            jnp.arange(nb * block, dtype=jnp.int32), (b, nb * block)
        )
        keys, idx = _desc_sort_blocks(
            keys.reshape(b * nb, block), idx.reshape(b * nb, block)
        )  # ascending in negated keys
        # per-block top-k candidates: (b, nb, k)
        keys = keys.reshape(b, nb, block)[:, :, :k]
        idx = idx.reshape(b, nb, block)[:, :, :k]

    # Tournament: k-way merge candidate lists, keep top-k each round —
    # one cut for the whole batch per round.
    with obs.span("repro.topk_tournament"):
        r = nb
        while r > 1:
            group = min(fanout, r)
            if r % group:  # pad with sentinel lists to a group multiple
                extra = group - r % group
                keys = jnp.concatenate(
                    [keys, jnp.full((b, extra, k), sentinel, keys.dtype)],
                    axis=1,
                )
                idx = jnp.concatenate(
                    [idx, jnp.zeros((b, extra, k), idx.dtype)], axis=1
                )
                r += extra
            mk, mi = merge_runs_ranked(
                keys.reshape(b * (r // group), group, k),
                idx.reshape(b * (r // group), group, k),
            )
            keys = mk.reshape(b, r // group, group * k)[:, :, :k]
            idx = mi.reshape(b, r // group, group * k)[:, :, :k]
            r //= group

        vals = -keys[:, 0]
        return vals.astype(x.dtype), idx[:, 0]


@partial(jax.jit, static_argnames=("k", "block", "fanout"))
def merge_topk(x: jax.Array, k: int, block: int = 128,
               fanout: int = 0):
    """Top-k of a 1-D array: returns ``(values, indices)`` descending.

    Single-row view of :func:`merge_topk_batch` (same tournament, same
    tie-breaking, same padding).
    """
    vals, idx = merge_topk_batch(x[None], k, block=block, fanout=fanout)
    return vals[0], idx[0]
