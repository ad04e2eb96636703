"""Tests for the multi-way co-rank and k-way merge subsystem."""

import numpy as np
import pytest
import jax.numpy as jnp

from _prop import given, settings, st
from repro.core import (
    co_rank_kway,
    co_rank_kway_batch,
    merge_by_ranking,
    merge_kway,
    merge_kway_ranked,
    merge_sort,
    merge_argsort,
    merge_topk,
    sort_key_val,
)
from repro.kernels.merge import merge_kway_pallas


def oracle_cuts(runs, i):
    """Reference cut vector: stably merge with (value, run, pos) keys and
    count per-run contributions to the first ``i`` outputs."""
    k, w = runs.shape
    tagged = sorted((runs[r, t], r, t) for r in range(k) for t in range(w))
    j = np.zeros(k, np.int64)
    for _, r, _ in tagged[:i]:
        j[r] += 1
    return j


def pairwise_lemma_holds(runs, j):
    """The k-way cut must satisfy Lemma 1 for every ordered run pair:
    for q < r, no kept element of r may precede a dropped one of q and
    vice versa (ties resolve toward the lower run index)."""
    k, w = runs.shape
    for q in range(k):
        for r in range(q + 1, k):
            # kept-prefix of q ends before dropped-suffix of r starts
            if j[q] > 0 and j[r] < w and not runs[q][j[q] - 1] <= runs[r][j[r]]:
                return False
            if j[r] > 0 and j[q] < w and not runs[r][j[r] - 1] < runs[q][j[q]]:
                return False
    return True


def rand_runs(rng, k, w, lo=0, hi=10, dtype=np.int32):
    return np.sort(rng.integers(lo, hi, (k, w)), axis=1).astype(dtype)


#: Key dtypes of the co-rank tests: small integers are exact in float32,
#: whose keys the co-rank maps to int32 before its search.
KEY_DTYPES = pytest.mark.parametrize("dtype", [np.int32, np.float32])


@KEY_DTYPES
@pytest.mark.parametrize("k,w", [(2, 8), (3, 5), (4, 16), (8, 7), (16, 3)])
def test_co_rank_kway_matches_oracle(k, w, dtype):
    rng = np.random.default_rng(k * 100 + w)
    runs = rand_runs(rng, k, w, dtype=dtype)
    ranks = jnp.arange(k * w + 1, dtype=jnp.int32)
    cuts = np.asarray(co_rank_kway_batch(ranks, jnp.asarray(runs)))
    for i in range(k * w + 1):
        j = cuts[i]
        np.testing.assert_array_equal(j, oracle_cuts(runs, i)), (k, w, i)
        assert j.sum() == i
        assert pairwise_lemma_holds(runs, j), (k, w, i, j)


@KEY_DTYPES
def test_co_rank_kway_cut_sum_invariant(dtype):
    """sum(j_r) == i for every rank, heavy-duplicate input."""
    rng = np.random.default_rng(0)
    runs = rand_runs(rng, 8, 32, lo=0, hi=3, dtype=dtype)  # duplicates
    ranks = jnp.arange(8 * 32 + 1, dtype=jnp.int32)
    cuts = np.asarray(co_rank_kway_batch(ranks, jnp.asarray(runs)))
    np.testing.assert_array_equal(cuts.sum(axis=1), np.asarray(ranks))


@KEY_DTYPES
def test_co_rank_kway_all_equal_stability(dtype):
    """All-equal keys: cuts must drain runs strictly in run order."""
    runs = jnp.zeros((4, 8), dtype)
    cuts = np.asarray(
        co_rank_kway_batch(jnp.arange(33, dtype=jnp.int32), runs)
    )
    for i in range(33):
        want = np.clip([i, i - 8, i - 16, i - 24], 0, 8)
        np.testing.assert_array_equal(cuts[i], want)


@KEY_DTYPES
def test_co_rank_kway_ragged_lengths(dtype):
    rng = np.random.default_rng(5)
    k, w = 4, 10
    lengths = np.array([10, 3, 7, 1], np.int32)
    runs = np.full((k, w), np.iinfo(np.int32).max, dtype)
    for r in range(k):
        runs[r, : lengths[r]] = np.sort(rng.integers(0, 6, lengths[r]))
    total = int(lengths.sum())
    cuts = np.asarray(
        co_rank_kway_batch(
            jnp.arange(total + 1, dtype=jnp.int32),
            jnp.asarray(runs),
            jnp.asarray(lengths),
        )
    )
    for i in range(total + 1):
        assert cuts[i].sum() == i
        assert (cuts[i] <= lengths).all()


@pytest.mark.parametrize("k,w", [(2, 64), (4, 33), (8, 17), (16, 9)])
@pytest.mark.parametrize("p", [1, 3, 8, 16])
def test_merge_kway_values(k, w, p):
    rng = np.random.default_rng(k * w + p)
    runs = rand_runs(rng, k, w, hi=50)
    got = np.asarray(merge_kway(jnp.asarray(runs), p=p))
    np.testing.assert_array_equal(
        got, np.sort(runs.reshape(-1), kind="stable")
    )


def test_merge_kway_stability_duplicates():
    """Duplicate-heavy keys with an index payload: payload order must be
    the global stable order (run-major, then position)."""
    rng = np.random.default_rng(9)
    k, w = 6, 40
    runs = rand_runs(rng, k, w, hi=4)  # only 4 distinct keys
    ids = np.arange(k * w, dtype=np.int32).reshape(k, w)
    keys, got_ids = merge_kway_ranked(jnp.asarray(runs), jnp.asarray(ids))
    want_order = np.argsort(runs.reshape(-1), kind="stable")
    np.testing.assert_array_equal(np.asarray(got_ids), want_order)
    np.testing.assert_array_equal(
        np.asarray(keys), np.sort(runs.reshape(-1), kind="stable")
    )


def test_merge_kway_agrees_with_pairwise_folds():
    """k-way merge == fold of the paper's pairwise merge_by_ranking."""
    rng = np.random.default_rng(11)
    k, w = 8, 25
    runs = rand_runs(rng, k, w, hi=12)
    folded = jnp.asarray(runs[0])
    for r in range(1, k):
        folded = merge_by_ranking(folded, jnp.asarray(runs[r]))
    got = np.asarray(merge_kway(jnp.asarray(runs), p=5))
    np.testing.assert_array_equal(got, np.asarray(folded))


def test_merge_kway_ranked_ragged():
    rng = np.random.default_rng(13)
    k, w = 3, 8
    lengths = np.array([8, 2, 5], np.int32)
    runs = np.full((k, w), np.iinfo(np.int32).max, np.int32)
    parts = []
    for r in range(k):
        runs[r, : lengths[r]] = np.sort(rng.integers(0, 5, lengths[r]))
        parts.append(runs[r, : lengths[r]])
    total = int(lengths.sum())
    got = np.asarray(
        merge_kway_ranked(
            jnp.asarray(runs), lengths=jnp.asarray(lengths), out_len=total
        )
    )
    np.testing.assert_array_equal(
        got, np.sort(np.concatenate(parts), kind="stable")
    )


@pytest.mark.parametrize("fanout", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [1, 2, 37, 64, 257, 1000])
def test_sort_fanout_sweep(fanout, n):
    rng = np.random.default_rng(fanout * 10000 + n)
    x = rng.integers(-100, 100, n).astype(np.int32)
    got = np.asarray(merge_sort(jnp.asarray(x), fanout))
    np.testing.assert_array_equal(got, np.sort(x, kind="stable"))


@pytest.mark.parametrize("fanout", [2, 4, 8, 16])
def test_argsort_fanout_stable(fanout):
    rng = np.random.default_rng(fanout)
    x = rng.integers(0, 4, 333).astype(np.int32)  # heavy duplicates
    got = np.asarray(merge_argsort(jnp.asarray(x), fanout))
    np.testing.assert_array_equal(got, np.argsort(x, kind="stable"))


def test_sort_fanout_agreement_across_fanouts():
    """Every fanout must produce the identical (stable) permutation."""
    rng = np.random.default_rng(21)
    keys = rng.integers(0, 8, 500).astype(np.int32)
    vals = np.arange(500, dtype=np.int32)
    outs = []
    for fanout in (2, 4, 8, 16):
        k, v = sort_key_val(jnp.asarray(keys), jnp.asarray(vals), fanout)
        outs.append((np.asarray(k), np.asarray(v)))
    for k, v in outs[1:]:
        np.testing.assert_array_equal(k, outs[0][0])
        np.testing.assert_array_equal(v, outs[0][1])


@pytest.mark.parametrize("fanout", [2, 4, 16])
def test_topk_tournament_fanout(fanout):
    rng = np.random.default_rng(fanout + 40)
    x = rng.standard_normal(3000).astype(np.float32)
    vals, idx = merge_topk(jnp.asarray(x), 17, block=128, fanout=fanout)
    order = np.argsort(-x, kind="stable")[:17]
    np.testing.assert_allclose(np.asarray(vals), x[order], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), order)


# ---------------------------------------------------------------------------
# Pallas kernel: interpret-mode sweep of shapes x dtypes x fanouts
# ---------------------------------------------------------------------------


@pytest.mark.slow  # 24-cell interpret-mode sweep: minutes of tracing
@pytest.mark.parametrize("dtype", [np.int32, np.float32, "bfloat16"])
@pytest.mark.parametrize("k,w", [(2, 1000), (4, 513), (8, 300), (16, 65)])
@pytest.mark.parametrize("tile", [128, 256])
def test_merge_kway_pallas_sweep(dtype, k, w, tile):
    rng = np.random.default_rng(abs(hash((str(dtype), k, w, tile))) % 2**32)
    if dtype == "bfloat16":
        # small integer-valued floats: exact in bf16, avoids rounding
        # reorders vs the float oracle
        base = np.sort(rng.integers(-250, 250, (k, w)), axis=1).astype(
            np.float32
        )
        runs = jnp.asarray(base, jnp.bfloat16)
        got = np.asarray(merge_kway_pallas(runs, tile=tile)).astype(np.float32)
        want = np.sort(base.reshape(-1), kind="stable")
    else:
        base = np.sort(rng.integers(-1000, 1000, (k, w)), axis=1).astype(dtype)
        got = np.asarray(merge_kway_pallas(jnp.asarray(base), tile=tile))
        want = np.sort(base.reshape(-1), kind="stable")
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_merge_kway_pallas_stability_tagged():
    """Ties across runs resolve by run index: parity-style tag check."""
    rng = np.random.default_rng(17)
    k, w = 4, 700
    base = np.sort(rng.integers(0, 6, (k, w)), axis=1)
    runs = (base * 8 + np.arange(k)[:, None]).astype(np.int32)
    got = np.asarray(merge_kway_pallas(jnp.asarray(runs), tile=128))
    vals, origin = got // 8, got % 8
    np.testing.assert_array_equal(np.sort(vals, kind="stable"), vals)
    for v in np.unique(vals):
        seg = origin[vals == v]
        assert not np.any(np.diff(seg) < 0), f"instability at key {v}"


def test_merge_kway_pallas_adversarial_skew():
    """Run r entirely below run r+1 — worst case for equidistant
    partitions, exactly balanced for the multi-way co-rank."""
    k, w = 4, 512
    runs = jnp.arange(k * w, dtype=jnp.int32).reshape(k, w)
    got = np.asarray(merge_kway_pallas(runs, tile=256))
    np.testing.assert_array_equal(got, np.arange(k * w, dtype=np.int32))


def test_merge_kway_pallas_matches_xla_path():
    rng = np.random.default_rng(23)
    runs = np.sort(rng.standard_normal((8, 400)), axis=1).astype(np.float32)
    got = merge_kway_pallas(jnp.asarray(runs), tile=128)
    want = merge_kway_ranked(jnp.asarray(runs))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Proposition 1 at runtime: the recorded iteration counters
# ---------------------------------------------------------------------------


def test_prop1_runtime_iteration_counters():
    """The obs-layer ``corank.iterations`` records must respect Prop 1's
    ``ceil(log2 min(m, n)) + 1`` bound on live searches — the counter the
    paper's complexity claim is audited with in production runs."""
    from repro import obs
    from repro.core.corank import co_rank, prop1_bound

    rng = np.random.default_rng(31)
    cases = [(1, 1), (2, 7), (8, 8), (33, 7), (128, 128), (5, 1000)]
    with obs.capture() as recs:
        for m, n in cases:
            a = jnp.asarray(np.sort(rng.integers(-50, 50, m)), np.int32)
            b = jnp.asarray(np.sort(rng.integers(-50, 50, n)), np.int32)
            for i in (0, 1, (m + n) // 2, m + n - 1, m + n):
                co_rank(i, a, b)
        obs.flush()
        its = [r for r in recs if r["metric"] == "corank.iterations"]
        assert len(its) == 5 * len(cases)
        for r in its:
            m, n = r["labels"]["m"], r["labels"]["n"]
            assert r["labels"]["bound"] == prop1_bound(m, n)
            assert r["max"] <= r["labels"]["bound"], (
                f"Prop 1 violated for (m={m}, n={n}): "
                f"{r['max']} > {r['labels']['bound']}"
            )


def test_kway_corank_search_by_dtype():
    """Phase 1 of the k-way merge runs the value search for every key
    dtype, one round per bit of the dtype's range: 32 for int32 and for
    floats (mapped to int32), 8 for int8."""
    from repro import obs

    rng = np.random.default_rng(41)
    k, w = 4, 32
    runs = np.sort(rng.integers(0, 5, (k, w)), axis=1)
    bounds = {np.int32: 32, np.float32: 32, np.int8: 8}
    with obs.capture() as recs:
        for dtype in bounds:
            ids = np.arange(k * w, dtype=np.int32).reshape(k, w)
            keys, vals = merge_kway_pallas(
                jnp.asarray(runs.astype(dtype)),
                jnp.asarray(ids),
                tile=16,
                interpret=True,
            )
            order = np.argsort(runs.ravel(), kind="stable")
            np.testing.assert_array_equal(np.asarray(vals), ids.ravel()[order])
        obs.flush()
    rounds = [r for r in recs if r["metric"] == "kway.corank_rounds"]
    assert [r["labels"]["search"] for r in rounds] == ["value"] * 3
    assert [r["labels"]["bound"] for r in rounds] == list(bounds.values())
    assert [r["value"] for r in rounds] == list(bounds.values())


# ---------------------------------------------------------------------------
# properties (hypothesis when installed, seeded fallback offline)
# ---------------------------------------------------------------------------


@KEY_DTYPES
@settings(max_examples=50, deadline=None)
@given(
    st.integers(2, 8),
    st.integers(1, 40),
    st.integers(0, 2**31 - 1),
    st.data(),
)
def test_co_rank_kway_property(dtype, k, w, seed, data):
    rng = np.random.default_rng(seed)
    runs = rand_runs(rng, k, w, lo=-9, hi=9, dtype=dtype)
    i = data.draw(st.integers(0, k * w))
    j = np.asarray(co_rank_kway(i, jnp.asarray(runs)))
    np.testing.assert_array_equal(j, oracle_cuts(runs, i))
    assert pairwise_lemma_holds(runs, j)


@settings(max_examples=30, deadline=None)
@given(
    st.integers(2, 8),
    st.integers(1, 30),
    st.integers(1, 12),
    st.integers(0, 2**31 - 1),
)
def test_merge_kway_property(k, w, p, seed):
    rng = np.random.default_rng(seed)
    runs = rand_runs(rng, k, w, lo=-20, hi=20)
    got = np.asarray(merge_kway(jnp.asarray(runs), p=p))
    np.testing.assert_array_equal(
        got, np.sort(runs.reshape(-1), kind="stable")
    )


@pytest.mark.slow  # every example re-traces the interpret-mode kernel
@settings(max_examples=20, deadline=None)
@given(
    st.sampled_from([2, 4, 8]),
    st.integers(1, 60),
    st.integers(0, 2**31 - 1),
)
def test_merge_kway_pallas_property(k, w, seed):
    rng = np.random.default_rng(seed)
    runs = rand_runs(rng, k, w, lo=-20, hi=20)
    got = np.asarray(merge_kway_pallas(jnp.asarray(runs), tile=128))
    np.testing.assert_array_equal(
        got, np.sort(runs.reshape(-1), kind="stable")
    )


@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_kway_positions_search_methods_agree(monkeypatch, dtype, ragged):
    """Dense compares (short rows) and binary search (wide rows) count
    with the same order: identical positions on ties, signed zeros and
    infinities."""
    from repro.core import kway

    rng = np.random.default_rng(7)
    k, w = 4, 48
    if dtype == np.int32:
        runs = rng.integers(-3, 3, (k, w)).astype(dtype)
    else:
        levels = np.array([-np.inf, -1.5, -0.0, 0.0, 2.0, np.inf], dtype)
        runs = levels[rng.integers(0, levels.size, (k, w))]
    runs = np.sort(runs, axis=1)
    lengths = jnp.asarray([w, w // 2, 0, w - 1], jnp.int32) if ragged else None
    got = {}
    for width in (0, w):  # 0: binary search always; w: compare-all here
        monkeypatch.setattr(kway, "COMPARE_ALL_WIDTH", width)
        got[width] = np.asarray(kway.kway_positions(jnp.asarray(runs), lengths))
    np.testing.assert_array_equal(got[0], got[w])


def test_kway_tile_bounds_the_tile_count():
    from repro.kernels.merge import KWAY_MAX_TILES, kway_tile

    assert kway_tile(4, 1024) == 512
    assert kway_tile(16, 1 << 20) == 4096
    for k, w in [(4, 1 << 22), (16, 1 << 20), (3, 1_000_003)]:
        s = kway_tile(k, w)
        assert k * w <= s * KWAY_MAX_TILES
        assert s == 512 or k * w > s // 2 * KWAY_MAX_TILES
