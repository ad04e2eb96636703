"""Shared oracle cases for the cross-layer co-rank equivalence sweep.

Every instantiation of the one co-rank engine (``repro.core.engine``) —
device (``core.kway`` / ``core.corank``), distributed
(``distributed.splitters``, 8 fake devices in a subprocess), host
planner (``external.planner``) and the Pallas kernel
(``kernels.merge``, interpret mode) — must return bit-identical cuts on
these cases.  The cases deliberately stress the places where the five
former transcriptions used to drift:

* duplicate-heavy keys (the stability tie-break carries the answer);
* ±inf floats (comparison edge values);
* real int32 dtype-max elements coexisting with dtype-max padding;
* pre-sorted inputs (degenerate cuts: whole runs taken in order);
* ragged / zero-length runs behind the ``lengths`` sideband;
* the value search of ``core.kway``, over keys mapped to int32: real
  int32 keys at both ends of the range next to int32-max padding
  (midpoint overflow, the length clamp), uint32 keys above 2^31, int8
  and int16 keys, one key shared by every run with the swept ranks
  inside its ties, float32 signed zeros (tied, as ``<`` ties them)
  among negatives and infinities on ragged rows, and float16 keys.

The oracle is engine-independent: a numpy stable ``lexsort`` over
``(value, run, offset)`` — the paper's definition of the stable k-way
merge order, computed by brute force.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "kway_cases",
    "oracle_cuts",
    "oracle_pairwise",
    "pairwise_cases",
    "rank_sweep",
]


def _pad_rows(rows, w, fill):
    """Stack ragged sorted rows into a sorted-over-full-width (k, w)."""
    k = len(rows)
    out = np.full((k, w), fill, dtype=np.result_type(fill, *rows))
    for r, row in enumerate(rows):
        out[r, : len(row)] = row
    return out


def kway_cases(k: int):
    """List of ``(name, runs, lengths)``: ``runs`` is ``(k, w)`` with every
    row sorted over its full width; ``lengths`` is int32 ``(k,)`` real
    lengths (always explicit, so every tier exercises its sideband)."""
    rng = np.random.default_rng(1234 + k)
    cases = []

    # Duplicate-heavy: tiny key universe, every cut decided by ties.
    w = 32
    runs = np.sort(rng.integers(0, 4, (k, w)), axis=1).astype(np.int32)
    cases.append(("dup_heavy", runs, np.full(k, w, np.int32)))

    # ±inf floats: infinities as *real* elements, +inf also the padding.
    w = 24
    rows = []
    lens = []
    for r in range(k):
        n = int(rng.integers(8, w + 1))
        body = rng.normal(size=n - 2).astype(np.float32)
        row = np.sort(
            np.concatenate([[-np.inf], body, [np.inf]]).astype(np.float32)
        )
        rows.append(row)
        lens.append(n)
    cases.append(
        ("pm_inf", _pad_rows(rows, w, np.float32(np.inf)),
         np.asarray(lens, np.int32))
    )

    # Real int32 dtype-max elements + dtype-max padding on ragged rows.
    w = 20
    imax = np.iinfo(np.int32).max
    rows = []
    lens = []
    for r in range(k):
        n = int(rng.integers(4, w + 1))
        row = np.sort(rng.integers(imax - 3, imax + 1, n)).astype(np.int32)
        rows.append(row)
        lens.append(n)
    cases.append(
        ("dtype_max", _pad_rows(rows, w, np.int32(imax)),
         np.asarray(lens, np.int32))
    )

    # Pre-sorted: the concatenation is already globally sorted.
    w = 16
    flat = np.sort(rng.integers(-100, 100, k * w)).astype(np.int32)
    cases.append(
        ("pre_sorted", flat.reshape(k, w), np.full(k, w, np.int32))
    )

    # Ragged with a zero-length run and heavy duplicates.
    w = 28
    rows = []
    lens = []
    for r in range(k):
        n = 0 if r == k // 2 else int(rng.integers(1, w + 1))
        rows.append(np.sort(rng.integers(0, 6, n)).astype(np.int32))
        lens.append(n)
    cases.append(
        ("ragged_zero", _pad_rows(rows, w, np.int32(np.iinfo(np.int32).max)),
         np.asarray(lens, np.int32))
    )

    # Real keys at both ends of int32, next to int32-max padding.
    w = 20
    edges = np.array([imax - 1, imax, -imax - 1, -imax, -1, 0, 1], np.int64)
    rows = [np.sort(rng.choice(edges, int(rng.integers(0, w + 1))))
            .astype(np.int32) for _ in range(k)]
    cases.append(
        ("int32_extremes", _pad_rows(rows, w, np.int32(imax)),
         np.asarray([len(r) for r in rows], np.int32))
    )

    # uint32 keys above 2^31 (and a few below), uint32-max padding.
    w = 20
    umax = np.iinfo(np.uint32).max
    edges = np.array(
        [0, 5, 2**31 - 1, 2**31, 2**31 + 1, umax - 1, umax], np.uint64
    )
    rows = [np.sort(rng.choice(edges, int(rng.integers(1, w + 1))))
            .astype(np.uint32) for _ in range(k)]
    cases.append(
        ("uint32_high", _pad_rows(rows, w, np.uint32(umax)),
         np.asarray([len(r) for r in rows], np.int32))
    )

    # Narrow integers, widened to int32 by the value search.
    for dtype, lo, hi in ((np.int8, -128, 128), (np.int16, -40, 40)):
        w = 24
        top = np.iinfo(dtype).max
        rows = [np.sort(rng.integers(lo, hi, int(rng.integers(1, w + 1))))
                .astype(dtype) for _ in range(k)]
        cases.append(
            (np.dtype(dtype).name, _pad_rows(rows, w, dtype(top)),
             np.asarray([len(r) for r in rows], np.int32))
        )

    # One key (7) in every run, most of each run: the swept ranks fall
    # inside its ties, which the cut splits across the runs in run order.
    w = 32
    runs = np.full((k, w), 7, np.int32)
    for r in range(k):
        below, above = rng.integers(0, 5, 2)
        runs[r, :below] = np.sort(rng.integers(-5, 7, below))
        runs[r, w - above :] = np.sort(rng.integers(8, 20, above))
    cases.append(("shared_tie", runs, np.full(k, w, np.int32)))

    # Float keys: -0.0 and +0.0 mixed in one tie, negatives, infinities
    # real and as padding, ragged rows.
    w = 24
    pool = np.array(
        [-np.inf, -2.5, -1.0, -0.0, 0.0, 0.5, 1.0, np.inf], np.float32
    )
    rows = [np.sort(rng.choice(pool, int(rng.integers(1, w + 1))))
            for _ in range(k)]
    cases.append(
        ("signed_zero", _pad_rows(rows, w, np.float32(np.inf)),
         np.asarray([len(r) for r in rows], np.int32))
    )

    # float16 keys, widened to float32 before the map.
    w = 24
    pool = np.array([-65504.0, -3.5, -0.0, 0.0, 0.25, 2.0, 65504.0])
    runs = np.sort(rng.choice(pool, (k, w)), axis=1).astype(np.float16)
    cases.append(("float16", runs, np.full(k, w, np.int32)))

    return cases


def oracle_cuts(runs: np.ndarray, lengths: np.ndarray, i: int) -> np.ndarray:
    """Brute-force stable cut vector J(i): int64 (k,).

    Stable k-way merge order is lexicographic on (value, run, offset);
    J(i)_r counts run r's elements among the first i merged.
    """
    k, w = runs.shape
    run_ids = np.repeat(np.arange(k), w)
    offs = np.tile(np.arange(w), k)
    real = offs < np.asarray(lengths)[run_ids]
    vals, run_ids, offs = runs.ravel()[real], run_ids[real], offs[real]
    order = np.lexsort((offs, run_ids, vals))
    i = min(max(int(i), 0), len(order))
    return np.bincount(run_ids[order[:i]], minlength=k).astype(np.int64)


def rank_sweep(total: int, n: int = 13) -> list[int]:
    """Deterministic output ranks covering [0, total] incl. both ends."""
    if total <= 0:
        return [0]
    pts = set(np.linspace(0, total, n, dtype=np.int64).tolist())
    pts.update([1, total - 1, total // 2])
    return sorted(p for p in pts if 0 <= p <= total)


def pairwise_cases():
    """List of ``(name, a, b)`` sorted 1-D arrays for Algorithm 1."""
    rng = np.random.default_rng(99)
    imax = np.iinfo(np.int32).max
    return [
        (
            "dup_heavy",
            np.sort(rng.integers(0, 4, 57)).astype(np.int32),
            np.sort(rng.integers(0, 4, 43)).astype(np.int32),
        ),
        (
            "pm_inf",
            np.sort(
                np.concatenate(
                    [[-np.inf, np.inf], rng.normal(size=30)]
                ).astype(np.float32)
            ),
            np.sort(
                np.concatenate(
                    [[-np.inf, -np.inf, np.inf], rng.normal(size=20)]
                ).astype(np.float32)
            ),
        ),
        (
            "dtype_max",
            np.sort(rng.integers(imax - 2, imax + 1, 17)).astype(np.int32),
            np.sort(rng.integers(imax - 2, imax + 1, 23)).astype(np.int32),
        ),
        (
            "pre_sorted",
            np.arange(0, 40, 2, dtype=np.int32),
            np.arange(40, 70, dtype=np.int32),
        ),
        (
            "empty_side",
            np.sort(rng.integers(0, 9, 12)).astype(np.int32),
            np.empty(0, np.int32),
        ),
    ]


def oracle_pairwise(a: np.ndarray, b: np.ndarray, i: int):
    """Two-finger stable co-rank oracle: unique (j, k), j + k = i."""
    j = k = 0
    while j + k < i:
        if j < len(a) and (k >= len(b) or a[j] <= b[k]):
            j += 1
        else:
            k += 1
    return j, k
