"""merge-2p24-int32: the plain reference of a stable k-way merge.

Imports nothing of the program under test.  The reference is NumPy's
stable argsort of the real elements in input order (row by row, each
row up to its length): equal keys keep that order, and each payload
value travels with its key.

The controls break one guarantee that ``merge-2p24-int32.json`` states,
as a faster merge could be tempted to: ``unstable`` sorts without
keeping the input order of equal keys; ``approximate_cuts`` cuts every
run at fixed proportional positions instead of co-ranking each tile
boundary, so the output is a permutation of the input that is sorted
within each tile only.
"""

from __future__ import annotations

import math

import numpy as np

#: output tiles of the ``approximate_cuts`` control (fewer where a run's
#: width is not a multiple)
CONTROL_TILES = 4096


def real_elements(runs: np.ndarray, vals, lengths):
    """Keys (and payload) of the real elements, in input order."""
    k, w = runs.shape
    if lengths is None:
        lengths = np.full(k, w)
    real = np.arange(w)[None, :] < np.asarray(lengths)[:, None]
    return runs[real], (None if vals is None else vals[real])


def reference(runs, vals=None, lengths=None, control: str = ""):
    """Merged keys and payload (``None`` without one)."""
    keys, payload = real_elements(runs, vals, lengths)
    if control == "approximate_cuts":
        k, w = runs.shape
        if keys.size != k * w:
            raise ValueError("approximate_cuts needs full rows")
        # tile t takes positions [t*w/T, (t+1)*w/T) of every run
        tiles = math.gcd(CONTROL_TILES, w)
        idx = np.arange(k * w).reshape(k, tiles, -1)
        idx = idx.transpose(1, 0, 2).reshape(tiles, -1)
        inner = np.argsort(keys[idx], axis=1, kind="stable")
        order = np.take_along_axis(idx, inner, 1).reshape(-1)
    elif control == "unstable":
        # equal keys in reverse input order: a sort that keeps no order
        # among equals is free to produce exactly this
        rev = np.argsort(keys[::-1], kind="stable")
        order = keys.size - 1 - rev
    elif control:
        raise ValueError(f"unknown control {control!r}")
    else:
        order = np.argsort(keys, kind="stable")
    return keys[order], (None if payload is None else payload[order])
