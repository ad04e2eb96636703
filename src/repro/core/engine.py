"""One co-rank engine: the paper's search, defined exactly once.

Every tier of this repo runs the *same* algorithm — the stable co-rank
search of Siebert & Träff (2013) — against a different way of reading
the runs:

======================  =====================================  ==========
tier                    probe / reads                          loop
======================  =====================================  ==========
``core.corank``         local array indexing                   dynamic ``lax.while_loop`` (Prop.-1 counted)
``core.kway``           row searches of b key midpoints        static ``lax.fori_loop`` over the key bits
``distributed``         ``all_gather`` + masked ``psum``       static ``lax.fori_loop`` (lock-step collective rounds)
``external.planner``    ``np.searchsorted`` over mmap'd runs   plain Python loop
``kernels.merge``       tier-1 cuts, scalar-prefetched         rotate + bitonic :func:`precedes` network per tile
======================  =====================================  ==========

This module is the single definition site for everything those tiers
must agree on bit-for-bit:

* the **Lemma-1 predicates** and the stability tie-break — the
  ``<=`` / ``<`` asymmetry lives *only* here (:func:`count_below` and
  the helpers built on it); every other module selects a side through
  :func:`lemma1_counts` or the ``SIDE_*`` constants, or takes a merge
  decision through :func:`take_first` / :func:`kfinger_better` /
  :func:`precedes`;
* the **lock-step k-way bisection loop** (:func:`co_rank_search`),
  parameterized by a :class:`Probe`, and its **value-space** form over
  keys mapped to int32 (:func:`co_rank_value_search`, which ends in the
  run-order split of ties, :func:`tie_split_cuts`);
* the **pairwise Algorithm 1** double-ended search
  (:func:`co_rank_pairwise`), parameterized by two read functions;
* the **padding/length clamp** (padded tail positions are never
  counted — the ``owner_length`` clip in :func:`lemma1_counts`);
* the **round bounds** (:func:`prop1_bound`, :func:`kway_round_bound`,
  :func:`pairwise_lockstep_rounds`) and the one obs recording site for
  them.

Paper mapping
-------------

* **Lemma 1** — rank ``i`` of the stable merge of A and B cuts them at
  the unique ``(j, k)``, ``j + k = i``, with ``A[j-1] <= B[k]`` and
  ``B[k-1] < A[j]``.  Here: :func:`first_condition_holds` /
  :func:`second_condition_violated`; generalised to ``k`` runs the two
  conditions become "runs **before** mine count ties against my
  element, runs **after** count strictly" (:func:`lemma1_counts` — the
  run-index tie-break of the k-way stable order
  ``(value, run, offset)``).
* **Algorithm 1** — the double-ended binary search for ``(j, k)``:
  :func:`co_rank_pairwise` (its four boundary reads per round go
  through the caller's ``read_a`` / ``read_b``, so the same body runs
  on a local array or over collectives).  The k-way form replaces the
  double-ended search with one monotone bisection per run
  (``j_r(i) = |{t : rank(r, t) < i}|``): :func:`co_rank_search`.
  On a device the same cut is found by bisecting the *key* of the
  i-th merged element instead (one search per run a round, not one
  merged rank per run): :func:`co_rank_value_search`; its ties are
  split across the runs in run order — Lemma 1's tie-break once more
  (:func:`tie_split_cuts`).
* **Proposition 1** — the iteration bound
  ``ceil(log2 min(m, n)) + 1``: :func:`prop1_bound` checks the dynamic
  while-loop count; :func:`kway_round_bound` is the static lock-step
  schedule (``ceil(log2(w + 1)) + 1`` rounds over the ``w + 1``
  candidate cuts).

``Probe`` protocol
------------------

A probe tells the engine how to read its runs; the engine owns the
search semantics.  Required attributes/methods::

    xp             array namespace (jnp on device, np on host)
    width          static max candidate index (run width w)
    lengths        per-run real lengths, broadcastable to the cut shape
    owner_ids      run-id array aligned with counts(): who owns each count
    query_ids      run-id array aligned with counts(): whose query it serves
    owner_lengths  lengths aligned with counts() (the padding clamp)
    init_bounds(i) -> (lo, hi) initial bisection bounds, cut-shaped
    values(t)      candidate run elements at per-run indices t (read())
    counts(x)      -> (count_le, count_lt): per-run occupancy of the
                   candidate values, both Lemma-1 sides
    reduce(cnt)    fold sibling contributions into the cut shape
                   (sum(axis=0) locally, psum + own-row slice on a mesh)
    run_loop(rounds, body, state)  loop runner (fori / Python / psum'd)

``values``/``counts`` are where the tiers differ (local gather vs
``all_gather``+``psum`` vs mmap page faults); the predicate that
combines them is :func:`lemma1_counts`, here, once.
"""

from __future__ import annotations

from typing import Any, Callable, Protocol

import jax.numpy as jnp
from jax import lax

from repro import obs

__all__ = [
    "SIDE_TIES",
    "SIDE_STRICT",
    "count_below",
    "precedes",
    "first_condition_holds",
    "first_condition_violated",
    "second_condition_violated",
    "take_first",
    "kfinger_better",
    "lemma1_counts",
    "value_cut_counts",
    "tie_split_cuts",
    "prop1_bound",
    "kway_round_bound",
    "pairwise_lockstep_rounds",
    "run_fori",
    "run_host",
    "Probe",
    "co_rank_search",
    "co_rank_value_search",
    "co_rank_pairwise",
]


# ---------------------------------------------------------------------------
# §1  Stability: the Lemma-1 predicates and the <= / < tie-break pair.
#
# The stable k-way order is lexicographic on (value, run, offset): ties
# resolve to the earlier run.  Equivalently, when run ``rp`` counts its
# elements against a query element from run ``r``, it counts ties (<=)
# iff rp < r and strictly (<) iff rp > r — Lemma 1's two conditions,
# applied pairwise.  Everything below is a view of that one rule.
# ---------------------------------------------------------------------------

#: ``searchsorted`` sides implementing the pair: an owner run that
#: *precedes* the query's run counts ties (``<=`` -> ``side='right'``);
#: one that *follows* counts strictly (``<`` -> ``side='left'``).
SIDE_TIES = "right"
SIDE_STRICT = "left"


def count_below(v, x, ties: bool):
    """``v <= x`` (ties) or ``v < x`` (strict) — THE comparison pair.

    This is the only place the ``<=`` / ``<`` asymmetry of Lemma 1 is
    written down; every search, merge decision and count in the repo
    routes through it (or through the ``SIDE_*`` constants, its
    ``searchsorted`` spelling).
    """
    return (v <= x) if ties else (v < x)


def precedes(a, b, a_first):
    """Stable order of two elements: does ``a`` come before ``b``?

    ``a_first`` (traced bool) says whether ``a``'s origin — its
    ``(run, offset)`` — comes first; it then counts ties (``a <= b``),
    otherwise only strictly (``a < b``).  Boolean algebra, not a select
    of booleans, so Mosaic lowers it inside the Pallas kernels.
    """
    return count_below(a, b, ties=False) | (
        a_first & count_below(a, b, ties=True)
    )


def first_condition_holds(a_prev, b_val):
    """Lemma 1, first condition: ``A[j-1] <= B[k]`` (ties to A)."""
    return count_below(a_prev, b_val, ties=True)


def first_condition_violated(a_prev, b_val):
    """``A[j-1] > B[k]`` — j must decrease (Algorithm 1, lines 6-10)."""
    return ~first_condition_holds(a_prev, b_val)


def second_condition_violated(b_prev, a_val):
    """``B[k-1] >= A[j]`` — k must decrease (Algorithm 1, lines 11-15)."""
    return ~count_below(b_prev, a_val, ties=False)


def take_first(first_val, second_val, first_avail, second_avail):
    """Two-finger merge decision: take from the *earlier* input?

    Yes iff it has elements left and (the later input is exhausted or
    ``first <= second``) — ties always emit the earlier input first.
    """
    return first_avail & (
        ~second_avail | count_below(first_val, second_val, ties=True)
    )


def kfinger_better(val, best_val, avail, best_ok):
    """k-finger merge decision: does a *later* run's head beat the best?

    Only strictly (``<``): on ties the earlier run (already in
    ``best``) wins — the run-index tie-break.  Fold runs in index order
    with this and stability is run-index order by construction.
    """
    return avail & (~best_ok | count_below(val, best_val, ties=False))


def lemma1_counts(count_le, count_lt, owner, query, owner_length, xp=jnp):
    """Select each run pair's Lemma-1 side and clamp away padding.

    ``count_le`` / ``count_lt`` are both-side occupancy counts of the
    candidate values in the owner run(s); ``owner`` / ``query`` are
    broadcast-aligned run-id arrays.  Owners before the query's run
    contribute their tie count, owners after their strict count, a run
    contributes nothing to its own queries, and no run ever counts its
    padded tail (the ``owner_length`` clip — valid because padding is
    required to be >= every real element).
    """
    cnt = xp.where(owner < query, count_le, count_lt)
    cnt = xp.where(owner == query, xp.zeros_like(cnt), cnt)
    return xp.minimum(cnt, owner_length)


def value_cut_counts(run, boundary_values, length=None, xp=jnp):
    """Degenerate Lemma-1 search when the boundary *values* are known.

    The cut of a known boundary value ``v`` is the strictly-below count
    (``SIDE_STRICT``): every element equal to ``v`` sorts *after* the
    boundary, so value cuts and rank cuts coincide and the ``O(log w)``
    bisection collapses to one ``searchsorted`` per boundary (the MoE
    segment-cut fast path).  ``length`` clamps away padded tails.
    """
    local = xp.searchsorted(run, boundary_values, side=SIDE_STRICT).astype(
        xp.int32
    )
    if length is not None:
        local = xp.minimum(local, length)
    return local


def tie_split_cuts(i, le, lt):
    """Cut of rank ``i`` once the key ``v`` of its merged element is known.

    ``le`` / ``lt`` are each run's counts of its real elements ``<= v`` /
    ``< v`` (Lemma 1's two sides, clamped to the run's length), shaped
    ``(k, ...)`` with the runs first.  Every element below ``v`` sorts
    before rank ``i``; the ``i - L`` places left (``L`` the elements
    below ``v``) go to the ties of ``v`` in run order: earlier runs
    count ties, later ones strictly, as in :func:`lemma1_counts`.  Exact
    for any ``v`` with ``L <= i``: when ``i`` is past every element the
    cut is the lengths.
    """
    eq = le - lt
    ties_before = jnp.cumsum(eq, axis=0) - eq
    return lt + jnp.clip(i - lt.sum(axis=0) - ties_before, 0, eq)


# ---------------------------------------------------------------------------
# §2  Round bounds (Proposition 1 and its lock-step paddings).
# ---------------------------------------------------------------------------


def prop1_bound(m: int, n: int) -> int:
    """Proposition 1's iteration bound ``ceil(log2 min(m, n)) + 1``.

    Bounds the *dynamic* double-ended search of Algorithm 1; the
    runtime invariant counter (``corank.iterations``) and the property
    tests check recorded iteration counts against this.
    """
    mn = min(m, n)
    if mn <= 0:
        return 0
    return (mn - 1).bit_length() + 1


def kway_round_bound(w: int) -> int:
    """Static lock-step schedule for one run of width ``w``.

    ``ceil(log2(w + 1)) + 1`` rounds: Proposition 1's bound over the
    ``w + 1`` candidate cuts ``0..w``, plus the one convergence round a
    static schedule pays over the dynamic search.  Every tier's k-way
    bisection (device, collective, host planner) runs exactly this many
    rounds.
    """
    return max(1, w).bit_length() + 1


def pairwise_lockstep_rounds(m: int, n: int) -> int:
    """Static schedule for the lock-step pairwise search (Algorithm 1
    run to a fixed count so ``p`` devices' searches can share collective
    rounds): Proposition 1's range is ``min(m, n)`` wide, plus one
    safety round over the per-device dynamic searches."""
    return kway_round_bound(min(m, n)) + 1


# ---------------------------------------------------------------------------
# §3  Loop runners — how the one body executes on each tier.
# ---------------------------------------------------------------------------


def run_fori(rounds: int, body: Callable, state):
    """Device runner: a static ``lax.fori_loop`` (jit/vmap/shard_map
    safe; collective-bearing bodies stay lock-step across the mesh)."""
    return lax.fori_loop(0, rounds, lambda _, s: body(s), state)


def run_host(rounds: int, body: Callable, state):
    """Host runner: a plain Python loop (numpy / mmap probes)."""
    for _ in range(rounds):
        state = body(state)
    return state


# ---------------------------------------------------------------------------
# §4  The k-way lock-step bisection (Algorithm 1 generalised to k runs),
#     probe-parameterized.
# ---------------------------------------------------------------------------


class Probe(Protocol):
    """How a tier reads its runs (see the module docstring table)."""

    xp: Any
    width: int
    lengths: Any
    owner_ids: Any
    query_ids: Any
    owner_lengths: Any

    def init_bounds(self, i):
        ...

    def values(self, t):
        ...

    def counts(self, x):
        ...

    def reduce(self, cnt):
        ...

    def run_loop(self, rounds: int, body: Callable, state):
        ...


def merged_rank(probe: Probe, t):
    """Stable merged rank of candidate elements ``(r, t_r)``.

    ``rank(r, t) = t + sum_{rp != r} |{u : runs[rp][u] (<= | <) runs[r][t]}|``
    with the side chosen by the run-index tie-break — Lemma 1 applied
    pairwise to every sibling run.  The probe supplies the reads; the
    side selection and padding clamp happen here.
    """
    x = probe.values(t)
    count_le, count_lt = probe.counts(x)
    cnt = lemma1_counts(
        count_le,
        count_lt,
        probe.owner_ids,
        probe.query_ids,
        probe.owner_lengths,
        xp=probe.xp,
    )
    return t + probe.reduce(cnt)


def co_rank_search(
    i,
    probe: Probe,
    *,
    metric: str | None = None,
    labels: dict | None = None,
):
    """Cut vector of output rank(s) ``i``: the k-way Lemma-1 bisection.

    One monotone binary search per run, all runs in lock-step:
    ``j_r(i) = |{t : rank(r, t) < i}|`` over the strictly increasing
    :func:`merged_rank`.  ``sum_r j_r(i) == i`` holds exactly because
    the stable rank is a bijection onto ``0..total-1``.  The schedule
    is the static :func:`kway_round_bound` of the probe's width, so the
    loop lowers identically under jit, as collective rounds under
    ``shard_map``, and as a Python loop on host.

    ``i`` must be broadcast-compatible with the probe's cut shape
    (batched callers pass ``i[:, None]``).  ``metric`` names the one
    obs recording site for the round count.
    """
    xp = probe.xp
    rounds = kway_round_bound(probe.width)
    lengths = probe.lengths

    def body(lo_hi):
        lo, hi = lo_hi
        mid = (lo + hi) // 2
        pred = (mid < lengths) & (merged_rank(probe, mid) < i)
        return xp.where(pred, mid + 1, lo), xp.where(pred, hi, mid)

    lo, hi = probe.init_bounds(i)
    lo, _ = probe.run_loop(rounds, body, (lo, hi))
    if metric is not None and obs.enabled():
        obs.gauge(metric, rounds, bound=rounds, **(labels or {}))
    return lo


def co_rank_value_search(
    i,
    count_le,
    lengths,
    key_range: tuple[int, int],
    *,
    metric: str | None = None,
    labels: dict | None = None,
):
    """Cut vectors of ranks ``i`` by bisecting the merged order's *keys*.

    Over int32 keys in ``key_range = (lo, hi)`` (every key, padding
    included, inside it; ``hi - lo + 1`` a power of two): each rank ``i``
    (shape ``(b,)``) looks for ``v`` = the smallest key with
    ``sum_r min(count_le_r(v), len_r) > i``, the key of the merged
    order's element ``i``; then :func:`tie_split_cuts` splits its ties.
    One static round per bit of the range, each one ``count_le`` over all
    ``b`` midpoints at once: ``k`` searches a round, where
    :func:`co_rank_search` needs a merged rank (``k`` searches) per run.

    The loop carries both sides of the tie split: the ``<= hi`` counts,
    and the ``<= lo - 1`` counts, which over integers are the ``< lo``
    counts.  The range halves exactly every round, so at the end
    ``lo == hi == v`` and the split needs no search of its own.

    ``count_le(v)`` returns each run's raw count of elements ``<= v``
    (``SIDE_TIES``) as ``(k, b)``; ``lengths`` is ``(k, 1)``.  Returns
    ``(k, b)`` cuts with ``j.sum(0) == clip(i, 0, total)``.
    """
    lo0, hi0 = key_range
    rounds = (hi0 - lo0).bit_length()

    def body(state):
        lo, hi, le, lt = state
        # floor((lo + hi) / 2) without leaving int32.
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        le_mid = jnp.minimum(count_le(mid), lengths)
        past = le_mid.sum(axis=0) > i
        return (
            jnp.where(past, lo, mid + 1),
            jnp.where(past, mid, hi),
            jnp.where(past, le_mid, le),
            jnp.where(past, lt, le_mid),
        )

    shape = (lengths.shape[0], *i.shape)
    state = (
        jnp.full(i.shape, lo0, jnp.int32),
        # ``hi`` has every element at or below it: past the last rank it
        # stays the top of the range, where the cut is the lengths.
        jnp.full(i.shape, hi0, jnp.int32),
        jnp.broadcast_to(lengths, shape),
        jnp.zeros(shape, jnp.int32),  # nothing lies below the range
    )
    _, _, le, lt = run_fori(rounds, body, state)
    if metric is not None and obs.enabled():
        obs.gauge(metric, rounds, bound=rounds, **(labels or {}))
    return tie_split_cuts(i, le, lt)


# ---------------------------------------------------------------------------
# §5  The pairwise Algorithm 1 (double-ended search), read-parameterized.
# ---------------------------------------------------------------------------


def _violations(state, reads, m: int, n: int):
    """Evaluate both Lemma-1 conditions at the current search state
    (four boundary reads; the guards make out-of-range reads moot)."""
    j, k = state[0], state[1]
    a_jm1, b_k, b_km1, a_j = reads(j, k)
    fv = (j > 0) & (k < n) & first_condition_violated(a_jm1, b_k)
    sv = (k > 0) & (j < m) & second_condition_violated(b_km1, a_j)
    return fv, sv


def _algorithm1_step(state, reads, m: int, n: int):
    """One Algorithm-1 refinement step from the four boundary reads.

    Three-way: first condition violated -> decrease ``j`` (lines 6-10);
    else second violated -> decrease ``k`` (lines 11-15); else hold
    (the no-op branch lets converged searches idle inside a lock-step
    schedule).
    """
    j, k, j_low, k_low = state
    fv, sv = _violations(state, reads, m, n)

    delta_j = (j - j_low + 1) // 2  # ceil((j - j_low)/2)
    delta_k = (k - k_low + 1) // 2  # ceil((k - k_low)/2)
    new_k_low = jnp.where(fv, k, k_low)
    new_j_low = jnp.where(fv | ~sv, j_low, j)
    new_j = jnp.where(fv, j - delta_j, jnp.where(sv, j + delta_k, j))
    new_k = jnp.where(fv, k + delta_j, jnp.where(sv, k - delta_k, k))
    return new_j, new_k, new_j_low, new_k_low


def co_rank_pairwise(
    i,
    m: int,
    n: int,
    read_a: Callable,
    read_b: Callable,
    *,
    rounds: int | None = None,
    metric: str | None = None,
    labels: dict | None = None,
):
    """Algorithm 1: co-ranks ``(j, k)`` of output rank ``i``.

    The double-ended binary search, parameterized by how A and B are
    read — ``read_a(idx)`` / ``read_b(idx)`` receive already-clamped
    indices and may be a local gather or a collective remote read.

    ``rounds=None`` runs the dynamic ``lax.while_loop`` and counts
    iterations (Proposition 1 bounds them by :func:`prop1_bound`);
    an integer runs a static lock-step schedule of that many rounds
    (converged searches no-op), which is what collective reads need.

    Returns ``(j, k, iterations)``.  ``metric`` names the one obs
    recording site (histogram of dynamic iterations against the Prop-1
    bound, or gauge of the static round count).
    """
    i = jnp.asarray(i, jnp.int32)

    # Extreme initial assumption — as many of the i elements as possible
    # come from A.  k_low/iters derive from i (``i * 0``) so their
    # shard_map varying-axes types match the loop body's outputs.
    j = jnp.minimum(i, m)
    k = i - j
    j_low = jnp.maximum(i * 0, i - n)
    k_low = i * 0

    # Degenerate sides: Prop. 1's bound is 0 and the extreme initial
    # guess is already the answer — never read the empty array.
    if m == 0 or n == 0:
        if metric is not None and obs.enabled() and rounds is None:
            obs.histogram(
                metric, i * 0, bound=0, m=m, n=n, **(labels or {})
            )
        return j, k, i * 0

    def reads(j, k):
        a_jm1 = read_a(jnp.clip(j - 1, 0, m - 1))
        b_k = read_b(jnp.clip(k, 0, n - 1))
        b_km1 = read_b(jnp.clip(k - 1, 0, n - 1))
        a_j = read_a(jnp.clip(j, 0, m - 1))
        return a_jm1, b_k, b_km1, a_j

    state = (j, k, j_low, k_low)
    if rounds is None:

        def cond(carry):
            fv, sv = _violations(carry[0], reads, m, n)
            return fv | sv

        def body(carry):
            s, iters = carry
            return _algorithm1_step(s, reads, m, n), iters + 1

        state, iters = lax.while_loop(cond, body, (state, i * 0))
    else:
        state = run_fori(
            rounds, lambda s: _algorithm1_step(s, reads, m, n), state
        )
        iters = jnp.full_like(i, rounds)

    j, k = state[0], state[1]
    if metric is not None and obs.enabled():
        if rounds is None:
            obs.histogram(
                metric,
                iters,
                bound=prop1_bound(m, n),
                m=m,
                n=n,
                **(labels or {}),
            )
        else:
            obs.gauge(
                metric,
                rounds,
                bound=rounds,
                prop1_bound=prop1_bound(m, n),
                m=m,
                n=n,
                **(labels or {}),
            )
    return j, k, iters
