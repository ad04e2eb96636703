"""Multi-way co-ranking and perfectly load-balanced k-way stable merge.

Generalises the paper's two-sequence co-rank (Siebert & Träff 2013,
Lemma 1) to ``k`` sorted runs, following the "Multi-Way Co-Ranking"
formulation (Joshi 2025) and the diagonal view of Merge Path (Green et
al. 2014): an output rank ``i`` induces a unique *cut vector*
``(j_0, ..., j_{k-1})`` with ``sum(j_r) == i`` such that the first ``i``
elements of the stable k-way merge are exactly
``runs[0][:j_0] ∪ ... ∪ runs[k-1][:j_{k-1}]``.

Stability is "run index breaks ties": element ``(r, t)`` precedes
``(r', t')`` iff ``(value, r, t) < (value', r', t')`` lexicographically.
Under that order the *merged rank* of element ``(r, t)`` is

    rank(r, t) = t + sum_{r' < r} |{u : runs[r'][u] <= runs[r][t]}|
                   + sum_{r' > r} |{u : runs[r'][u] <  runs[r][t]}|

— the ``<=`` / ``<`` asymmetry is exactly Lemma 1's, applied pairwise to
every other run.  ``rank(r, ·)`` is strictly increasing, so the cut

    j_r(i) = |{t : rank(r, t) < i}|

is found, over the keys mapped to int32 in the same order, by bisecting
the *key* of the merged order's ``i``-th element (one round per key bit,
each ``k`` searches, one per run), then splitting that key's ties across
the runs in run order (``engine.co_rank_value_search``).  ``sum_r
j_r(i) == i`` holds exactly because ``rank`` is a bijection onto
``0..k*w-1``.  Keys of at most 32 bits are taken: integers widen
(uint32 flips its top bit), floats widen to float32 and map to int32 by
the radix-sort key map, ``-0.0`` tied with ``+0.0`` as ``<`` ties them
(NaN has no place in the order, as in the merge kernels).

On top of the cut sit two merges:

* ``merge_kway_ranked`` — fully data-parallel: every element's output
  position is its merged rank (k-1 vectorised ``searchsorted`` per run),
  one scatter.  The fast pure-XLA path used by the fan-out merge sort.
* ``merge_kway`` — the paper-faithful partitioned form: ``p`` processing
  elements each co-rank the two endpoints of an output block of size
  ``ceil(total/p)`` (perfect balance, Proposition 2 carries over
  verbatim) and run a sequential k-finger merge of exactly their
  segments.

Ragged runs are supported via ``lengths``: rows must stay sorted over
their full width (pad with a value >= every real element, e.g. dtype
max); padded positions are never counted or emitted.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import engine
from repro.core.engine import SIDE_STRICT, SIDE_TIES
from repro.core.merge import partition_bounds

__all__ = [
    "co_rank_kway",
    "co_rank_kway_batch",
    "kway_positions",
    "merge_kway_ranked",
    "merge_kway",
]


_INT32_RANGE = (-(2**31), 2**31 - 1)


def _int32_keys(runs: jax.Array) -> tuple[jax.Array, tuple[int, int]]:
    """Keys as int32 in the same order, and the range they lie in.

    Integers of at most 32 bits widen (uint32 flips its top bit).
    Floats of at most 32 bits widen to float32; ``-0.0`` becomes
    ``+0.0``, then the bits of negatives flip below the sign, which
    orders the int32 patterns as the floats.
    """
    dtype = runs.dtype
    if dtype.itemsize <= 4 and jnp.issubdtype(dtype, jnp.floating):
        x = runs.astype(jnp.float32)
        b = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
        return b ^ ((b >> 31) & jnp.int32(2**31 - 1)), _INT32_RANGE
    if dtype.itemsize <= 4 and jnp.issubdtype(dtype, jnp.integer):
        if dtype == jnp.uint32:
            flipped = runs ^ jnp.uint32(2**31)
            return lax.bitcast_convert_type(flipped, jnp.int32), _INT32_RANGE
        info = jnp.iinfo(dtype)
        return runs.astype(jnp.int32), (int(info.min), int(info.max))
    raise TypeError(f"k-way co-rank takes keys of at most 32 bits, not {dtype}")


def _row_counts(rows: jax.Array, v: jax.Array) -> jax.Array:
    """``searchsorted(rows[r], v, SIDE_TIES)`` for every row: ``(k, w)``
    rows and ``(b,)`` keys -> ``(k, b)`` int32 counts.

    All ``k * b`` binary searches step in lock-step over the flattened
    rows, so each step is one plain gather.  ``jax.vmap`` of
    ``jnp.searchsorted`` gathers with a batch dimension instead, which a
    v5e ran 2-3x slower at k=16 and in a time that swung with the keys
    (the value search of a 2^24-key merge's 4,097 tile boundaries: 335
    ms against 709-998 ms).
    """
    k, w = rows.shape
    flat = rows.reshape(-1)
    base = (jnp.arange(k, dtype=jnp.int32) * w)[:, None]

    def step(_, lo_hi):
        # ``jnp.searchsorted``'s scan: ``mid < hi <= w`` always.
        lo, hi = lo_hi
        mid = (lo + hi) // 2
        counted = engine.count_below(flat[base + mid], v, ties=True)
        return jnp.where(counted, mid, lo), jnp.where(counted, hi, mid)

    shape = (k, *v.shape)
    lo_hi = (jnp.zeros(shape, jnp.int32), jnp.full(shape, w, jnp.int32))
    return lax.fori_loop(0, w.bit_length(), step, lo_hi)[1]


def co_rank_kway(
    i: jax.Array, runs: jax.Array, lengths: jax.Array | None = None
) -> jax.Array:
    """Cut vector ``j`` (shape ``(k,)``) of output rank ``i`` into ``runs``.

    Args:
      i: output rank, ``0 <= i <= sum(lengths)`` (scalar, may be traced).
      runs: ``(k, w)`` array, every row sorted ascending over its full
        width (pad ragged rows with row-wise maximal values).
      lengths: optional ``(k,)`` real lengths; defaults to ``w`` each.

    Returns:
      int32 ``(k,)`` cut indices with ``j.sum() == min(i, total)``; the
      stable k-way merge of the runs restricted to ``runs[r][:j[r]]`` is
      exactly its first ``i`` elements.
    """
    i = jnp.asarray(i, jnp.int32)
    return co_rank_kway_batch(i[None], runs, lengths)[0]


def co_rank_kway_batch(
    i: jax.Array, runs: jax.Array, lengths: jax.Array | None = None
) -> jax.Array:
    """Vectorised cuts for ranks ``i`` of shape ``(b,)`` -> ``(b, k)``.

    All ``b`` ranks search together by key value
    (``engine.co_rank_value_search``): one round per bit of the keys'
    range, ``k`` searches a round.
    """
    k, w = runs.shape
    if lengths is None:
        lengths = jnp.full((k,), w, jnp.int32)
    keys, key_range = _int32_keys(runs)
    j = engine.co_rank_value_search(
        jnp.asarray(i, jnp.int32),
        partial(_row_counts, keys),
        jnp.asarray(lengths, jnp.int32)[:, None],
        key_range,
        metric="kway.corank_rounds",
        labels={"k": k, "w": w, "search": "value"},
    )
    return j.T


#: Rows up to this width are searched by comparing every query with every
#: element (dense compares and a sum); wider rows by binary search.  A
#: TPU pays far more for the binary search's batched gathers than for a
#: few dozen vector compares (the sampler's block sort and tournament
#: search rows of at most 128).
COMPARE_ALL_WIDTH = 128


def _search_method(w: int) -> str:
    """``jnp.searchsorted`` method for a row of width ``w``; every method
    counts with the same total order, so the counts are identical."""
    return "compare_all" if w <= COMPARE_ALL_WIDTH else "scan"


def kway_positions(
    runs: jax.Array, lengths: jax.Array | None = None
) -> jax.Array:
    """Merged rank of *every* element: ``(k, w) -> (k, w)`` int32.

    The element-wise form of the cut characterisation — the k-way
    generalisation of ``merge_by_ranking``'s position computation.  Each
    element is searched into exactly its ``k-1`` sibling runs (the
    Python loop over runs unrolls at trace time; every probe is a
    vectorised ``searchsorted``).  Positions of padded elements
    (``t >= lengths[r]``) are meaningless; callers mask them before
    scattering.
    """
    k, w = runs.shape
    search = partial(jnp.searchsorted, method=_search_method(w))
    if lengths is None:
        # Hot path (uniform runs): element (r, t) is searched into each
        # sibling rp once — runs after rp count ties into rp (SIDE_TIES),
        # runs before it count strictly (SIDE_STRICT): Lemma 1 applied
        # pairwise, sides from the engine's one tie-break definition.
        cnt = jnp.zeros((k, w), jnp.int32)
        for rp in range(k):
            row = runs[rp]
            if rp + 1 < k:
                cr = search(row, runs[rp + 1 :], side=SIDE_TIES)
                cnt = cnt.at[rp + 1 :].add(cr.astype(jnp.int32))
            if rp > 0:
                cl = search(row, runs[:rp], side=SIDE_STRICT)
                cnt = cnt.at[:rp].add(cl.astype(jnp.int32))
    else:
        # Ragged runs: same incremental loop, with each source row's
        # counts clipped at its real length.  Exact because padding is
        # >= every real element (the row contract): a query can only
        # tie with padding when it equals the row's maximal real value,
        # and the clip restores exactly the real count there; padding
        # is never strictly below any query, so the left side needs no
        # correction at all (clipped anyway for symmetry).
        lengths = jnp.asarray(lengths, jnp.int32)
        cnt = jnp.zeros((k, w), jnp.int32)
        for rp in range(k):
            row = runs[rp]
            cap = lengths[rp]
            if rp + 1 < k:
                cr = search(row, runs[rp + 1 :], side=SIDE_TIES)
                cnt = cnt.at[rp + 1 :].add(
                    jnp.minimum(cr.astype(jnp.int32), cap)
                )
            if rp > 0:
                cl = search(row, runs[:rp], side=SIDE_STRICT)
                cnt = cnt.at[:rp].add(
                    jnp.minimum(cl.astype(jnp.int32), cap)
                )
    return jnp.arange(w, dtype=jnp.int32)[None, :] + cnt


def merge_kway_ranked(
    runs: jax.Array,
    vals: jax.Array | None = None,
    lengths: jax.Array | None = None,
    out_len: int | None = None,
):
    """Stable k-way merge, data-parallel scatter formulation.

    ``runs``: ``(k, w)`` sorted rows (+ optional ``vals`` payload of the
    same shape, carried through).  Returns the merged ``(total,)`` keys
    (and payload), ``total = out_len or k*w``; with ``lengths`` given,
    padded elements are dropped and the tail of the output (positions
    ``>= sum(lengths)``) is zero.
    """
    k, w = runs.shape
    total = k * w if out_len is None else out_len
    pos = kway_positions(runs, lengths)
    if lengths is not None:
        invalid = jnp.arange(w, dtype=jnp.int32)[None, :] >= jnp.asarray(
            lengths, jnp.int32
        )[:, None]
        pos = jnp.where(invalid, total, pos)  # scatter-dropped
    flat_pos = pos.reshape(-1)
    out = jnp.zeros((total,), runs.dtype)
    out = out.at[flat_pos].set(runs.reshape(-1), mode="drop")
    if vals is None:
        return out
    out_v = jnp.zeros((total,), vals.dtype)
    out_v = out_v.at[flat_pos].set(vals.reshape(-1), mode="drop")
    return out, out_v


def _kfinger_segment(
    runs: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    seg_len: int,
) -> jax.Array:
    """Sequential k-finger stable merge of ``runs[r][lo_r:hi_r]`` into a
    static ``(seg_len,)`` buffer (the per-PE "optimal sequential merge");
    ``sum(hi - lo) <= seg_len``.  ``fori_loop`` body so it vmaps across
    processing elements.
    """
    k, w = runs.shape
    rows = jnp.arange(k, dtype=jnp.int32)

    def step(t, state):
        cur, out = state
        vals = runs[rows, jnp.clip(cur, 0, w - 1)]
        avail = cur < hi
        # Fold min with availability flags: the engine's k-finger rule
        # (strict '<') keeps the earliest run on ties — the run-index
        # stability rule — and avoids any sentinel that could collide
        # with real dtype-max values.
        best_val, best_q, best_ok = vals[0], jnp.int32(0), avail[0]
        for q in range(1, k):
            better = engine.kfinger_better(vals[q], best_val, avail[q], best_ok)
            best_val = jnp.where(better, vals[q], best_val)
            best_q = jnp.where(better, jnp.int32(q), best_q)
            best_ok = best_ok | avail[q]
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(best_ok, best_val, out[t]), t, 0
        )
        cur = cur + ((rows == best_q) & best_ok)
        return cur, out

    out = jnp.zeros((seg_len,), runs.dtype)
    _, out = lax.fori_loop(0, seg_len, step, (lo, out))
    return out


@partial(jax.jit, static_argnames=("p",))
def merge_kway(runs: jax.Array, p: int = 8) -> jax.Array:
    """Perfectly load-balanced stable merge of ``k`` sorted runs.

    Algorithm 2 with the pairwise co-rank replaced by the multi-way cut:
    each of ``p`` processing elements co-ranks both endpoints of its
    output block (sizes differ by at most one, Proposition 2) and merges
    exactly its ``k`` input segments with a sequential k-finger merge.
    One partitioning step for any ``k`` — no ``log2(k)`` pairwise tree.
    """
    k, w = runs.shape
    total = k * w
    with obs.span("repro.merge_kway"):
        bounds = partition_bounds(total, p)  # (p+1,)
        cuts = co_rank_kway_batch(bounds, runs)  # (p+1, k)
        seg_len = -(-total // p)

        if obs.enabled():
            # Proposition 2 at runtime: per-PE output block sizes differ
            # by at most one (and the cut rows sum to the block bounds).
            sizes = bounds[1:] - bounds[:-1]
            obs.gauge("kway.partition_sizes", sizes, k=k, w=w, p=p)
            obs.gauge(
                "kway.partition_imbalance", sizes.max() - sizes.min(), p=p
            )

        segs = jax.vmap(
            lambda lo, hi: _kfinger_segment(runs, lo, hi, seg_len)
        )(cuts[:-1], cuts[1:])  # (p, seg_len)

        idx = (
            bounds[:-1, None] + jnp.arange(seg_len, dtype=jnp.int32)[None, :]
        )
        valid = idx < bounds[1:, None]
        out = jnp.zeros((total,), runs.dtype)
        out = out.at[jnp.where(valid, idx, total)].set(segs, mode="drop")
        return out
