"""Sampling on merge-sorted logits — the serving-side use of the paper.

top-k uses the merge-based tournament top-k; top-p (nucleus) keeps the
merge-sorted prefix whose boundary is found with the engine's
value-keyed cut, so equal logits resolve toward the lower token id —
deterministic tie-breaking across compilations, which lexicographic
float sorts do not guarantee.

Two call shapes:

* the per-request references (:func:`sample_topk` / :func:`sample_topp`)
  vmap a single-row tournament per request — the semantics oracle;
* the batched serving forms (:func:`sample_topk_batched` /
  :func:`sample_topp_batched`) push the whole decode batch through
  ``merge_topk_batch``: every active request's per-block candidate runs
  are concatenated into one ``(b * r, k)`` run matrix and cut with **one
  ``merge_kway_ranked`` call per tournament round** — the round count
  depends only on the vocab/fanout geometry, never on the batch size,
  which is where the sub-linear decode-step scaling in
  ``BENCH_serve.json`` comes from.  Per-request results are bit-identical
  to the references (asserted in ``tests/test_serving.py`` on
  duplicate-heavy, ±inf and dtype-max logits), so the serving engine can
  use either interchangeably.

The top-p nucleus boundary is the degenerate Lemma-1 search of
``repro.core.engine.value_cut_counts`` — the cumulative-probability run
is sorted, so the cut at boundary value ``p`` is one ``searchsorted``
per request, the same machinery the dropless-MoE segment cuts use.

``fanout`` (candidate lists merged per tournament round) threads down
from ``ModelConfig.fanout`` so serving sweeps can tune the fan-out>2
path end-to-end; 0 picks the library default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine
from repro.core.topk import (
    candidate_blocks,
    merge_topk,
    merge_topk_batch,
    tournament_rounds,
)

__all__ = [
    "sample_greedy",
    "sample_topk",
    "sample_topp",
    "sample_topk_batched",
    "sample_topp_batched",
    "batched_topk",
]


# ---------------------------------------------------------------------------
# per-request references (the semantics oracle)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def sample_topk(key, logits, k: int = 50, temperature: float = 1.0,
                fanout: int = 0):
    """logits: (b, vocab) -> token ids (b,) sampled from the top-k set."""

    def one(key_i, row):
        vals, idx = merge_topk(row, k, fanout=fanout)
        probs = jax.nn.softmax(vals.astype(jnp.float32) / temperature)
        choice = jax.random.categorical(key_i, jnp.log(probs + 1e-20))
        return idx[choice]

    keys = jax.random.split(key, logits.shape[0])
    return jax.vmap(one)(keys, logits)


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def sample_topp(key, logits, p: float = 0.9, k: int = 256,
                temperature: float = 1.0, fanout: int = 0):
    """Nucleus sampling over merge-sorted top-k candidates."""

    def one(key_i, row):
        # descending, stable
        vals, idx = merge_topk(row, k, fanout=fanout)
        probs = jax.nn.softmax(vals.astype(jnp.float32) / temperature)
        cum = jnp.cumsum(probs)
        keep = cum - probs < p  # first token always kept
        probs = jnp.where(keep, probs, 0.0)
        choice = jax.random.categorical(key_i, jnp.log(probs + 1e-20))
        return idx[choice]

    keys = jax.random.split(key, logits.shape[0])
    return jax.vmap(one)(keys, logits)


@jax.jit
def sample_greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# batched serving forms: one merge cut per round for the whole batch
# ---------------------------------------------------------------------------


def _record_topk_metrics(b: int, n: int, k: int, fanout: int) -> None:
    """Static tournament geometry -> the ``serve.topk_*`` evidence: the
    number of merge cuts a step costs (batch-size independent) and the
    candidate count entering the final cut."""
    if not obs.enabled():
        return
    _, nb = candidate_blocks(n, k)
    rounds = tournament_rounds(nb, fanout)
    final_runs = rounds[-1] if rounds else 1
    obs.gauge("serve.topk_merge_rounds", len(rounds),
              batch=b, blocks=nb, fanout=fanout or 0)
    obs.counter("serve.topk_candidates", b * final_runs * k,
                batch=b, k=k)


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def batched_topk(logits, k: int = 50, fanout: int = 0):
    """Row-wise ``(values, indices)`` top-k of a ``(b, vocab)`` batch via
    one ``merge_kway_ranked`` cut per tournament round (see module
    docstring).  Bit-identical per row to ``merge_topk(logits[i], k)``.
    """
    b, n = logits.shape
    _record_topk_metrics(b, n, k, fanout)
    return merge_topk_batch(logits, k, fanout=fanout)


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def sample_topk_batched(keys, logits, k: int = 50,
                        temperature: float = 1.0, fanout: int = 0):
    """Batched top-k sampling with explicit per-request keys.

    ``keys``: (b,) PRNG keys, one per request — the serving engine
    derives them from (request id, token index) so a request's stream
    never depends on which slot or step it lands in.  Token draws are
    bit-identical to ``sample_topk``'s per-request path given the same
    per-row key.
    """
    vals, idx = batched_topk(logits, k, fanout=fanout)
    with obs.span("repro.topk_draw"):
        probs = jax.nn.softmax(
            vals.astype(jnp.float32) / temperature, axis=-1)
        choice = jax.vmap(
            lambda kk, pp: jax.random.categorical(kk, jnp.log(pp + 1e-20))
        )(keys, probs)
        return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]


@functools.partial(jax.jit, static_argnames=("k", "fanout"))
def sample_topp_batched(keys, logits, p: float = 0.9, k: int = 256,
                        temperature: float = 1.0, fanout: int = 0):
    """Batched nucleus sampling; the nucleus boundary per request is the
    engine's value-keyed cut into the sorted cumulative-probability run
    (``value_cut_counts`` — one ``searchsorted`` per request, exactly the
    MoE segment-cut machinery), equivalent to the reference's
    ``cum - probs < p`` prefix because that run is nondecreasing.
    """
    vals, idx = batched_topk(logits, k, fanout=fanout)
    with obs.span("repro.topk_draw"):
        probs = jax.nn.softmax(
            vals.astype(jnp.float32) / temperature, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        n_keep = jax.vmap(
            lambda row: engine.value_cut_counts(row, jnp.float32(p))
        )(cum - probs)
        keep = jnp.arange(k, dtype=jnp.int32)[None, :] < n_keep[:, None]
        probs = jnp.where(keep, probs, 0.0)
        choice = jax.vmap(
            lambda kk, pp: jax.random.categorical(kk, jnp.log(pp + 1e-20))
        )(keys, probs)
        return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
