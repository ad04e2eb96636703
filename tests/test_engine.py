"""Cross-layer equivalence sweep: one engine, identical cuts everywhere.

Every instantiation of ``repro.core.engine`` — device ``co_rank`` /
``co_rank_kway``, host-planner ``co_rank_kway_host``, Pallas
``merge_kway_pallas`` (interpret), and the 8-device collective searches
(subprocess lane) — must agree bit-for-bit with the engine-independent
brute-force oracle on the shared cases in ``_engine_cases``.
"""

import os
import pathlib
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from _engine_cases import (
    kway_cases,
    oracle_cuts,
    oracle_pairwise,
    pairwise_cases,
    rank_sweep,
)
from repro.core.corank import co_rank
from repro.core.kway import co_rank_kway, co_rank_kway_batch
from repro.external.planner import co_rank_kway_host

REPO = pathlib.Path(__file__).resolve().parents[1]

KWAY_CASES = kway_cases(4)
CASE_IDS = [name for name, _, _ in KWAY_CASES]


@pytest.mark.parametrize("name,a,b", pairwise_cases(),
                         ids=[c[0] for c in pairwise_cases()])
def test_pairwise_matches_oracle(name, a, b):
    m, n = len(a), len(b)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    for i in rank_sweep(m + n):
        res = co_rank(i, aj, bj)
        assert (int(res.j), int(res.k)) == oracle_pairwise(a, b, i), (
            name, i, int(res.j), int(res.k))


@pytest.mark.parametrize("name,runs,lengths", KWAY_CASES, ids=CASE_IDS)
def test_kway_device_matches_oracle(name, runs, lengths):
    total = int(lengths.sum())
    sweep = rank_sweep(total)
    cuts = np.asarray(
        co_rank_kway_batch(
            jnp.asarray(sweep, jnp.int32),
            jnp.asarray(runs),
            jnp.asarray(lengths),
        )
    )
    for row, i in zip(cuts, sweep):
        np.testing.assert_array_equal(
            row, oracle_cuts(runs, lengths, i), err_msg=f"{name} i={i}"
        )


@pytest.mark.parametrize("name,runs,lengths", KWAY_CASES, ids=CASE_IDS)
def test_kway_single_rank_matches_oracle(name, runs, lengths):
    """One rank at a time, at both ends and past the end of the merge:
    ``j.sum() == min(i, total)``."""
    total = int(lengths.sum())
    for i in (0, total, total + 3):
        j = np.asarray(
            co_rank_kway(i, jnp.asarray(runs), jnp.asarray(lengths))
        )
        np.testing.assert_array_equal(
            j, oracle_cuts(runs, lengths, i), err_msg=f"{name} i={i}"
        )


@pytest.mark.parametrize("name,runs,lengths", KWAY_CASES, ids=CASE_IDS)
def test_kway_host_planner_matches_device(name, runs, lengths):
    total = int(lengths.sum())
    ragged = [runs[r, : lengths[r]] for r in range(runs.shape[0])]
    device = np.asarray(
        co_rank_kway_batch(
            jnp.asarray(rank_sweep(total), jnp.int32),
            jnp.asarray(runs),
            jnp.asarray(lengths),
        )
    )
    for row, i in zip(device, rank_sweep(total)):
        host = co_rank_kway_host(i, ragged)
        np.testing.assert_array_equal(host, row, err_msg=f"{name} i={i}")
        np.testing.assert_array_equal(
            host, oracle_cuts(runs, lengths, i), err_msg=f"{name} i={i}"
        )


def stable_reference(runs, vals, lengths):
    """The real elements of ``runs`` (and ``vals``) in NumPy's stable
    argsort order of the runs laid end to end."""
    k, w = runs.shape
    real = (np.arange(w)[None, :] < np.asarray(lengths)[:, None]).ravel()
    keys = runs.ravel()[real]
    order = np.argsort(keys, kind="stable")
    return keys[order], vals.ravel()[real][order]


def assert_stable_merge(name, got_keys, got_vals, runs, vals, lengths):
    total = int(np.sum(lengths))
    want_keys, want_vals = stable_reference(runs, vals, lengths)
    np.testing.assert_array_equal(
        np.asarray(got_keys)[:total], want_keys, err_msg=name
    )
    np.testing.assert_array_equal(
        np.asarray(got_vals)[:total],
        want_vals,
        err_msg=f"{name}: payload permutation (tie order) drifted",
    )


@pytest.mark.parametrize("name,runs,lengths", KWAY_CASES, ids=CASE_IDS)
def test_pallas_interpret_bitexact(name, runs, lengths):
    """Interpret-mode kernel merge == brute-force stable order, payload
    permutation included (the payload pins the tie order exactly)."""
    from repro.kernels.merge import merge_kway_pallas

    k, w = runs.shape
    ids = (np.arange(k * w, dtype=np.int32)).reshape(k, w)
    keys, vals = merge_kway_pallas(
        jnp.asarray(runs),
        jnp.asarray(ids),
        lengths=jnp.asarray(lengths),
        tile=16,
        interpret=True,
    )
    assert_stable_merge(name, keys, vals, runs, ids, lengths)


def test_pallas_k16_ties_cut_by_tiles():
    """k = 16 runs of three keys: the tile boundaries fall inside ties
    held by several runs, which the cuts split in run order."""
    from repro.kernels.merge import merge_kway_pallas

    rng = np.random.default_rng(16)
    k, w, tile = 16, 64, 32
    runs = np.sort(rng.integers(0, 3, (k, w)), axis=1).astype(np.int32)
    ids = np.arange(k * w, dtype=np.int32).reshape(k, w)
    lengths = np.full(k, w)
    want_keys, want_ids = stable_reference(runs, ids, lengths)
    bounds = np.arange(tile, k * w, tile)
    runs_holding = [len(np.unique(want_ids[want_keys == v] // w))
                    for v in want_keys[bounds]]
    inside = (want_keys[bounds - 1] == want_keys[bounds]) & (
        np.asarray(runs_holding) > 1
    )
    assert inside.sum() >= 3, "no tile boundary falls in a multi-run tie"
    keys, vals = merge_kway_pallas(
        jnp.asarray(runs), jnp.asarray(ids), tile=tile, interpret=True
    )
    assert_stable_merge("k16_ties", keys, vals, runs, ids, lengths)


def test_merge_window_ragged_payload():
    """A ragged k = 8 window with a payload through ``ops.merge_window``'s
    Pallas path: empty and full rows, real int32-max keys beside the
    padding, and a width the tile does not divide."""
    from repro.kernels.ops import merge_window

    rng = np.random.default_rng(8)
    k, w = 8, 40
    imax = np.iinfo(np.int32).max
    lengths = rng.integers(1, w, k).astype(np.int32)
    lengths[3], lengths[5] = 0, w
    runs = np.full((k, w), imax, np.int32)
    for r in range(k):
        row = rng.integers(-30, 30, lengths[r])
        row[: lengths[r] // 8] = imax  # real dtype-max keys
        runs[r, : lengths[r]] = np.sort(row)
    vals = rng.permutation(k * w).astype(np.int32).reshape(k, w)
    keys, got_vals = merge_window(
        jnp.asarray(runs),
        jnp.asarray(vals),
        jnp.asarray(lengths),
        backend="pallas",
        tile=16,
        interpret=True,
    )
    assert_stable_merge("window", keys, got_vals, runs, vals, lengths)


@pytest.mark.slow
def test_distributed_cuts_eight_devices():
    """Subprocess lane: the collective searches on 8 fake devices return
    the same cuts as the device tier on the shared cases (k = p = 8)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "_engine_check.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "ALL OK" in proc.stdout
