"""Pallas TPU kernels for the co-rank stable merge.

TPU adaptation of the paper:

* Phase 1 (plain JAX, tiny): co-rank all ``G+1`` tile boundaries — the
  pairwise Algorithm 1 (``co_rank_batch``) or its k-way generalisation
  (``co_rank_kway_batch``).  Output tile ``r`` of ``S`` elements then
  owns exact input segments ``run_q[c_q(r) : c_q(r+1))`` whose lengths
  sum to ``S``.  *Perfect* load balance makes the Pallas grid uniform
  and every block shape static — the property that makes this algorithm
  TPU-native (a factor-2-imbalanced partition would force 2x tile
  padding).

* Phase 2 (``pl.pallas_call``): grid cell ``r`` = the paper's processing
  element ``r``.  The cuts come in through **scalar prefetch**
  (``pltpu.PrefetchScalarGridSpec``): each input's ``index_map`` reads
  them to stage the two consecutive S-blocks that cover its segment.
  Every array is laid out as ``(blocks, S/128, 128)``, so a block is a
  whole ``(S/128, 128)`` slab — legal for Mosaic at any tile that is a
  power of two and at least 128.

* The per-cell merge uses only what the TPU's vector unit lowers: lane
  and sublane rotations (``pltpu.roll``), compares and selects — no
  gathers.  Each run's segment is rotated into its place in the tile
  (run order, so the position is the stable ``(run, offset)`` tag), then
  a bitonic network of compare-exchanges orders ``(key, tag)``
  lexicographically.  Keys compare through the engine's ``<=`` / ``<``
  pair: the earlier tag counts ties — Lemma 1's tie-break — so the
  result is exactly the stable merge.  No scalar loop over elements runs.

Sub-32-bit keys and payloads are widened to 32 bits around the call (the
round trip is exact) so the kernel body only sees 32-bit vregs.

Interpret mode is the platform's choice, made once in
:func:`default_interpret`: interpret off-TPU, compile on a TPU.
Everything is validated against numpy's stable sort in interpret mode
(``tests/test_kernels.py``, ``tests/test_kway.py``) and compiled for a
described v5e by ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import engine
from repro.core.corank import co_rank_batch
from repro.core.kway import co_rank_kway_batch
from repro.core.mergesort import sentinel_max as _sentinel

__all__ = [
    "default_interpret",
    "merge_pallas",
    "merge_tile_kernel",
    "merge_kway_pallas",
    "kway_tile",
]

LANES = 128


def default_interpret() -> bool:
    """Interpret the kernels off-TPU, compile them on a TPU.

    The one place the platform decides; every entry point that takes
    ``interpret=None`` resolves through here.
    """
    return jax.default_backend() != "tpu"


def _tile_layout(s: int) -> tuple[int, int]:
    """``(rows, lanes)`` of one S-element slab: full 128-lane rows."""
    if s < 1 or s & (s - 1):
        raise ValueError(f"tile must be a power of two, got {s}")
    lanes = min(s, LANES)
    return s // lanes, lanes


def _wide(x: jax.Array) -> jax.Array:
    """Widen a sub-32-bit array to 32 bits (exact, order-preserving)."""
    if x.dtype.itemsize >= 4:
        return x
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.float32)
    return x.astype(jnp.int32)


def _roll(x, shift, axis: int):
    """``jnp.roll`` along ``axis`` (``0 <= shift < size``)."""
    if x.shape[axis] == 1:
        return x
    return pltpu.roll(x, shift, axis)


def _roll_flat(x, shift):
    """``jnp.roll`` of the row-major flattening of a 2-D slab.

    One lane rotation, then two row rotations selected per lane: lanes
    at or past the lane shift stayed in their row, the rest wrapped into
    the next one.  ``0 <= shift < x.size`` (traced).
    """
    rows, lanes = x.shape
    lane_shift = shift % lanes
    y = _roll(x, lane_shift, 1)
    if rows == 1:
        return y
    row_shift = shift // lanes
    z0 = _roll(y, row_shift, 0)
    z1 = _roll(y, (row_shift + 1) % rows, 0)
    c = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(c >= lane_shift, z0, z1)


def _partner(x, d: int, upper):
    """Value at flat index ``i ^ d`` (``d`` a power of two)."""
    rows, lanes = x.shape
    axis, size, step = (1, lanes, d) if d < lanes else (0, rows, d // lanes)
    below = _roll(x, step, axis)  # x[i - d]
    above = _roll(x, size - step, axis)  # x[i + d]
    return jnp.where(upper, below, above)


def _precedes(ka, ta, kb, tb, n_valid):
    """Stable order: does ``(ka, ta)`` come before ``(kb, tb)``?

    Keys compare through the engine's stable order, the tag being the
    element's ``(run, offset)`` rank.  Tags ``>= n_valid`` are filler
    and go last, in tag order.  Boolean algebra throughout: Mosaic
    cannot narrow the byte masks a select of booleans produces.
    """
    tag_first = ta < tb
    filler = (ta >= n_valid) | (tb >= n_valid)
    return (filler & tag_first) | (
        ~filler & engine.precedes(ka, kb, tag_first)
    )


def _bitonic_sort(keys, tags, vals, n_valid):
    """Order ``(key, tag)`` pairs with a bitonic compare-exchange network.

    ``tags`` are unique, so the order is total and the network's result
    is the unique sorted sequence.  ``vals`` (or None) rides along.
    """
    rows, lanes = keys.shape
    i = tags  # the initial tags are the flat positions
    size = 2
    while size <= rows * lanes:
        descending = (i & size) != 0
        d = size // 2
        while d:
            upper = (i & d) != 0
            pk = _partner(keys, d, upper)
            pt = _partner(tags, d, upper)
            first = _precedes(keys, tags, pk, pt, n_valid)
            keep = first ^ upper ^ descending  # first == (upper == desc)
            keys = jnp.where(keep, keys, pk)
            if vals is not None:
                vals = jnp.where(keep, vals, _partner(vals, d, upper))
            tags = jnp.where(keep, tags, pt)
            d //= 2
        size *= 2
    return keys, vals


def merge_tile_kernel(
    cb_ref,  # ((G+1)*k,) scalar prefetch: cut c_q(r) at [r*k + q]
    *refs,  # 2k key slabs (+ 2k payload slabs), then the output slab(s)
    k: int,
    tile: int,
    nblocks: tuple[int, ...],
    has_vals: bool = False,
):
    """Merge one output tile of ``k`` co-ranked segments.

    Each run ``q`` is staged as two consecutive ``(S/128, 128)`` slabs
    starting at block ``min(c_q(r) // S, nblocks[q] - 1)`` (the clamp
    only bites when the segment is empty or ends in the first slab).
    Its segment is rotated into positions ``[P_q, P_q + len_q)`` of the
    tile, ``P_q`` the lengths of the runs before it, so a position is
    the element's stable ``(run, offset)`` tag; the bitonic network then
    sorts ``(key, tag)``.
    """
    s = tile
    r = pl.program_id(0)
    n_in = 4 * k if has_vals else 2 * k
    out_ref = refs[n_in]
    shape = out_ref.shape
    rows = shape[0]
    pos = (
        lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
        + lax.broadcasted_iota(jnp.int32, shape, 1)
    )

    keys = jnp.zeros(shape, out_ref.dtype)
    vals = jnp.zeros(shape, refs[n_in + 1].dtype) if has_vals else None
    start = jnp.int32(0)
    for q in range(k):
        lo = cb_ref[r * k + q]
        seg = cb_ref[(r + 1) * k + q] - lo
        off = lo - jnp.minimum(lo // s, nblocks[q] - 1) * s
        shift = (start - off + 2 * s) % (2 * s)
        take = (pos >= start) & (pos < start + seg)

        def place(first, second, acc):
            win = jnp.concatenate([first[...], second[...]], axis=0)
            return jnp.where(take, _roll_flat(win, shift)[:rows], acc)

        keys = place(refs[2 * q], refs[2 * q + 1], keys)
        if has_vals:
            vals = place(refs[2 * k + 2 * q], refs[2 * k + 2 * q + 1], vals)
        start = start + seg

    keys, vals = _bitonic_sort(keys, pos, vals, start)
    out_ref[...] = keys
    if has_vals:
        refs[n_in + 1][...] = vals


def _slabs(x: jax.Array, s: int) -> jax.Array:
    """``(..., n*S)`` -> ``(blocks, S/128, 128)`` slabs."""
    return x.reshape((-1,) + _tile_layout(s))


#: Cut entries prefetched per ``pallas_call``.  They live in SMEM (1 MiB
#: on a v5e, shared with the kernel's own scalars), so a merge with more
#: tiles than fit runs as several calls over consecutive tile ranges.
SMEM_CUTS = 1 << 16


def _merge_call(cb, sources, *, k, s, g, nblocks, interpret, semantics,
                name):
    """Merge ``g`` tiles, ``SMEM_CUTS // k - 1`` tiles per call."""
    per_call = max(1, SMEM_CUTS // k - 1)
    outs = [
        _merge_tiles(
            cb[c0 * k : (min(c0 + per_call, g) + 1) * k], sources,
            k=k, s=s, g=min(per_call, g - c0), nblocks=nblocks,
            interpret=interpret, semantics=semantics, name=name,
        )
        for c0 in range(0, g, per_call)
    ]
    if len(outs) == 1:
        return outs[0]
    return [jnp.concatenate(parts, axis=0) for parts in zip(*outs)]


def _merge_tiles(cb, sources, *, k, s, g, nblocks, interpret, semantics,
                 name):
    """One ``pallas_call`` over ``g`` tiles.

    ``sources``: per run ``q`` a ``(slabs, base)`` pair — the run's
    blocks sit at ``slabs[base : base + nblocks[q]]``; a second list of
    pairs, if present, is the payload with the same placement.  ``name``
    names the custom call (``%<name>.N``), whatever scope it runs in: a
    device trace finds the kernel by it.
    """
    key_sources, val_sources = sources
    block = (None,) + _tile_layout(s)

    def spec(q: int, plus: int, base: int):
        def index_map(r, cb):
            blk = jnp.minimum(cb[r * k + q] // s + plus, nblocks[q] - 1)
            return (base + blk, 0, 0)

        return pl.BlockSpec(block, index_map)

    in_specs, operands = [], []
    for group in (key_sources, val_sources or []):
        for q, (slabs, base) in enumerate(group):
            for plus in (0, 1):
                in_specs.append(spec(q, plus, base))
                operands.append(slabs)
    out_spec = pl.BlockSpec(block, lambda r, cb: (r, 0, 0))
    shape = (g,) + _tile_layout(s)
    out_shape = [jax.ShapeDtypeStruct(shape, key_sources[0][0].dtype)]
    if val_sources:
        out_shape.append(jax.ShapeDtypeStruct(shape, val_sources[0][0].dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=in_specs,
        out_specs=[out_spec] * len(out_shape),
    )
    return pl.pallas_call(
        functools.partial(
            merge_tile_kernel,
            k=k,
            tile=s,
            nblocks=tuple(nblocks),
            has_vals=bool(val_sources),
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(semantics,),
        ),
    )(cb, *operands)


#: Most output tiles a k-way merge takes by default.  Phase 1 co-ranks
#: every tile boundary with batched searches (``k`` a round), whose
#: gathers a TPU pays dearly for: at tile 512 a 2^24-element k=16 merge
#: spent most of its 87 s on a v5e there, when a round took ``k**2``.
KWAY_MAX_TILES = 4096


def kway_tile(k: int, w: int) -> int:
    """Default tile of a ``(k, w)`` merge: 512, doubled until the merge
    has at most :data:`KWAY_MAX_TILES` tiles."""
    s = 512
    while k * w > s * KWAY_MAX_TILES:
        s *= 2
    return s


def _pad_to(x: jax.Array, length: int) -> jax.Array:
    pad = length - x.shape[-1]
    fill = jnp.full(x.shape[:-1] + (pad,), _sentinel(x.dtype))
    return jnp.concatenate([x, fill], axis=-1)


@functools.partial(
    jax.jit, static_argnames=("tile", "interpret", "dimension_semantics")
)
def merge_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: int = 512,
    interpret: bool | None = None,
    dimension_semantics: str = "arbitrary",
) -> jax.Array:
    """Stable merge of two ordered 1-D arrays with a Pallas TPU kernel.

    Args:
      a, b: ordered arrays (any length; padded internally to tile multiples
        with order-preserving max sentinels).
      tile: output elements per grid cell (S); a power of two, at least
        128 when compiled for a TPU.
      interpret: run the kernel body in interpret mode; None lets the
        platform decide (:func:`default_interpret`).
      dimension_semantics: 'arbitrary' or 'parallel' for the grid axis —
        tiles are independent (paper's synchronization-freeness), so
        'parallel' is sound; kept switchable for the perf study.
    """
    if interpret is None:
        interpret = default_interpret()
    m, n = a.shape[0], b.shape[0]
    dtype = jnp.result_type(a, b)
    s = tile

    with obs.span("repro.slab_relayout"):
        # Logical padding to S-multiples (sentinels merge stably to the tail).
        m2 = -(-max(m, 1) // s) * s
        n2 = -(-max(n, 1) // s) * s
        a_log = _pad_to(a.astype(dtype), m2)
        b_log = _pad_to(b.astype(dtype), n2)
        g = (m2 + n2) // s

    with obs.span("repro.corank"):
        # Phase 1: co-rank the G+1 tile boundaries (the paper's Algorithm 1).
        bounds = jnp.arange(g + 1, dtype=jnp.int32) * s
        cr = co_rank_batch(bounds, a_log, b_log)
        cb = jnp.stack([cr.j, cr.k], axis=1).reshape(-1)

    with obs.span("repro.slab_relayout"):
        sources = ([(_slabs(_wide(a_log), s), 0),
                    (_slabs(_wide(b_log), s), 0)], None)
    with obs.span("repro.tile_merge"):
        (out,) = _merge_call(
            cb, sources, k=2, s=s, g=g, nblocks=(m2 // s, n2 // s),
            interpret=interpret, semantics=dimension_semantics,
            name="merge_pallas",
        )
    with obs.span("repro.slab_relayout"):
        return out.reshape(-1)[: m + n].astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("tile", "interpret", "dimension_semantics")
)
def merge_kway_pallas(
    runs: jax.Array,
    vals: jax.Array | None = None,
    *,
    lengths: jax.Array | None = None,
    tile: int | None = None,
    interpret: bool | None = None,
    dimension_semantics: str = "arbitrary",
):
    """Stable merge of ``k`` sorted runs with one Pallas pass.

    Args:
      runs: ``(k, w)`` array, rows sorted ascending (pad ragged runs
        with dtype-max sentinels upstream; sentinels merge to the tail).
      vals: optional ``(k, w)`` payload carried through the merge
        permutation (the external sort's window path); doubles the
        staged blocks and rides the same compare-exchanges.
      lengths: optional ``(k,)`` real row lengths.  Rows must stay
        sorted over their full width (sentinel padding).  The tile
        boundaries are then co-ranked against the *real* elements only,
        so padding never interleaves with real dtype-max keys; output
        positions ``>= lengths.sum()`` are unspecified — callers slice.
      tile: output elements per grid cell (S); a power of two, at least
        128 when compiled for a TPU; None picks :func:`kway_tile`.
      interpret: run the kernel body in interpret mode; None lets the
        platform decide (:func:`default_interpret`).
      dimension_semantics: grid axis annotation; tiles are independent
        so 'parallel' is sound.

    Returns the merged ``(k*w,)`` keys, or ``(keys, vals)`` with a
    payload.

    The k-way generalisation of ``merge_pallas``: phase 1 cuts all
    ``G+1`` tile boundaries into every run at once (multi-way co-rank),
    phase 2 stages two S-blocks per run per tile via scalar-prefetched
    index maps and merges each tile in one network.  ``log2(k)``
    pairwise passes collapse into one.
    """
    if interpret is None:
        interpret = default_interpret()
    k, w = runs.shape
    dtype = runs.dtype
    s = tile or kway_tile(k, w)

    with obs.span("repro.slab_relayout"):
        w2 = -(-max(w, 1) // s) * s
        runs_log = _pad_to(runs, w2)
    g = k * w2 // s
    nb = w2 // s

    with obs.span("repro.corank"):
        if lengths is None and w2 != w:
            # Co-rank against the real width, so the tail padding never
            # displaces a real dtype-max key (or its payload).
            lengths = jnp.full((k,), w, jnp.int32)
        # Phase 1: multi-way co-rank of the G+1 tile boundaries (ragged
        # rows clamp at their real lengths, exactly as in core.kway).
        lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)
        bounds = jnp.arange(g + 1, dtype=jnp.int32) * s
        cb = co_rank_kway_batch(bounds, runs_log, lengths).reshape(-1)

    with obs.span("repro.slab_relayout"):
        key_slabs = _slabs(_wide(runs_log), s)
        val_sources = None
        if vals is not None:
            vals_log = jnp.concatenate(
                [vals, jnp.zeros((k, w2 - w), vals.dtype)], axis=1
            )
            val_slabs = _slabs(_wide(vals_log), s)
            val_sources = [(val_slabs, q * nb) for q in range(k)]
        sources = ([(key_slabs, q * nb) for q in range(k)], val_sources)
    with obs.span("repro.tile_merge"):
        out = _merge_call(
            cb, sources, k=k, s=s, g=g, nblocks=(nb,) * k,
            interpret=interpret, semantics=dimension_semantics,
            name="merge_kway_pallas",
        )
    with obs.span("repro.slab_relayout"):
        keys = out[0].reshape(-1)[: k * w].astype(dtype)
        if vals is None:
            return keys
        return keys, out[1].reshape(-1)[: k * w].astype(vals.dtype)
