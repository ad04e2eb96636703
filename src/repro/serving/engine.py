"""Continuous-batching decode engine: admission -> ragged step -> sample.

One :class:`DecodeEngine` owns a :class:`~repro.serving.scheduler.Scheduler`
(FIFO queue + per-slot progress), a :class:`~repro.serving.kv_pool.KVPool`
(fixed-capacity recyclable cache slots) and two jitted device functions
that are compiled **once** for the pool shape, no matter how occupancy
churns:

* the ragged decode step (``decode_step_ragged``): every slot advances
  one token at its own position; inactive slots ride along masked (their
  lengths are held back, so their writes are never readable history);
* the batched sampler: the whole batch's candidate runs cut by one
  ``merge_kway_ranked`` call per tournament round
  (``repro.serving.sampling``).

Prompt tokens are fed through the same decode path as generated ones
(iteration-level scheduling), so a request admitted at step ``t`` starts
contributing to the batch immediately — no separate prefill entrypoint,
no recompilation, no barrier on the other slots.

Determinism contract: a request's token stream is a pure function of
``(engine seed, request id, prompt, sampler settings)`` — sampling keys
are derived by folding ``(rid, token index)`` into the seed, never the
slot or step index — so streams are byte-identical across runs,
compilations, and any admission interleaving.  ``tests/test_serving.py``
pins this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.models.transformer import Cache, decode_step_ragged
from repro.serving.kv_pool import KVPool
from repro.serving.sampling import (
    sample_greedy,
    sample_topk_batched,
    sample_topp_batched,
)
from repro.serving.scheduler import Request, Scheduler

__all__ = ["DecodeEngine"]


@functools.lru_cache(maxsize=None)
def _ragged_step_fn(cfg: ModelConfig):
    """The jitted ragged decode step of ``cfg`` (one per config)."""

    def ragged_step(params, cache, tokens, active):
        logits, new_cache = decode_step_ragged(
            cfg, params, cache, tokens, cache.length
        )
        # only active slots bank their position; inactive ones
        # re-write the same masked cell next step
        lengths = jnp.where(active, cache.length + 1, cache.length)
        return logits, Cache(new_cache.kind, new_cache.data, lengths)

    return jax.jit(ragged_step)


@jax.jit
def _sample_keys(base_key, rids, gens):
    """Per-slot sampling keys: ``(rid, token index)`` folded into the seed."""
    return jax.vmap(
        lambda r, g: jax.random.fold_in(jax.random.fold_in(base_key, r), g)
    )(rids, gens)


class DecodeEngine:
    """Serve decode requests with per-step admission over a slot pool."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 max_batch: int = 0, queue_depth: int = 0,
                 sampler: str = "topk", top_k: int = 50, top_p: float = 0.9,
                 temperature: float = 1.0, seed: int = 42,
                 cache_dtype=jnp.bfloat16):
        if sampler not in ("greedy", "topk", "topp"):
            raise ValueError(f"unknown sampler {sampler!r}")
        max_batch = max_batch or cfg.max_batch
        queue_depth = queue_depth or cfg.queue_depth
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.sampler = sampler
        self.top_k = min(top_k, cfg.vocab)
        self.top_p = top_p
        self.temperature = temperature
        self.pool = KVPool(cfg, max_batch, max_len, cache_dtype)
        self.scheduler = Scheduler(max_batch, queue_depth)
        self.results: dict[int, list[int]] = {}
        self.steps = 0
        self._base_key = jax.random.key(seed)
        # Shared by every engine of this config: a second engine over the
        # same pool shape reuses the compiled step.
        self._step_fn = _ragged_step_fn(cfg)

    # -- intake ------------------------------------------------------------

    def submit(self, request: Request) -> bool:
        """Queue a request; rejects (False) on a full queue or a request
        that cannot fit the pool's per-slot sequence capacity."""
        need = request.prompt.size + request.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request {request.rid}: prompt + max_new_tokens = {need} "
                f"exceeds pool max_len {self.max_len}"
            )
        return self.scheduler.submit(request)

    # -- one engine step ---------------------------------------------------

    def _sample(self, keys, logits):
        if self.sampler == "greedy":
            return sample_greedy(logits)
        if self.sampler == "topk":
            return sample_topk_batched(
                keys, logits, k=self.top_k, temperature=self.temperature,
                fanout=self.cfg.fanout,
            )
        return sample_topp_batched(
            keys, logits, p=self.top_p, k=min(self.top_k, self.cfg.vocab),
            temperature=self.temperature, fanout=self.cfg.fanout,
        )

    def step(self) -> dict:
        """Admit, advance every active slot one token, sample, retire.

        Returns ``{"admitted": [rids], "sampled": {rid: token},
        "completed": [rids], "active": int}`` for the caller's loop.

        Each phase runs inside a host span (``serve.step`` around
        ``serve.admit``, ``serve.build_inputs``, ``serve.dispatch``,
        ``serve.fetch_tokens``, ``serve.retire``), so a profiler trace
        puts each device-idle gap down to the phase the chip waited on.
        """
        with obs.host_span("serve.step"):
            sched, pool = self.scheduler, self.pool

            with obs.host_span("serve.admit"):
                n_free = min(pool.free_slots, sched.queued)
                placed = sched.admit([pool.alloc() for _ in range(n_free)])
                if obs.enabled():
                    obs.gauge("serve.active_slots", sched.active_slots,
                              capacity=pool.capacity)
                occupied = sched.occupied()
            if not occupied:
                return {"admitted": [], "sampled": {}, "completed": [],
                        "active": 0}

            with obs.host_span("serve.build_inputs"):
                b = pool.capacity
                tokens = np.zeros((b, 1), np.int32)
                active = np.zeros((b,), bool)
                due = np.zeros((b,), bool)
                rids = np.zeros((b,), np.uint32)
                gens = np.zeros((b,), np.uint32)
                for slot, st in occupied:
                    tokens[slot, 0] = st.next_feed
                    active[slot] = True
                    due[slot] = st.samples_this_step
                    rids[slot] = st.request.rid
                    gens[slot] = st.generated
                tokens, active = jnp.asarray(tokens), jnp.asarray(active)

            with obs.host_span("serve.dispatch"):
                logits, cache = self._step_fn(
                    self.params, pool.cache, tokens, active)
                pool.set_cache(cache.data, cache.length)
                # the keys' inputs go over while the device runs the step
                keys = _sample_keys(
                    self._base_key, jnp.asarray(rids), jnp.asarray(gens)
                )
                nxt = self._sample(keys, logits)

            with obs.host_span("serve.fetch_tokens"):
                nxt = np.asarray(nxt)  # blocks: step done

            with obs.host_span("serve.retire"):
                sampled: dict[int, int] = {}
                completed: list[int] = []
                for slot, st in occupied:
                    if st.fed < st.request.prompt.size:
                        st.fed += 1
                    if due[slot]:
                        tok = int(nxt[slot])
                        st.tokens.append(tok)
                        st.generated += 1
                        sampled[st.request.rid] = tok
                    if st.done:
                        req = sched.complete(slot)
                        pool.free(slot)
                        self.results[req.rid] = list(st.tokens)
                        completed.append(req.rid)
                self.steps += 1
            return {"admitted": [r.rid for _, r in placed], "sampled": sampled,
                    "completed": completed, "active": len(occupied)}

    # -- drive -------------------------------------------------------------

    @property
    def pending(self) -> int:
        return self.scheduler.pending

    def run(self, max_steps: int = 100_000,
            arrivals=None) -> dict[int, list[int]]:
        """Step until every submitted request retires.

        ``arrivals``: optional iterable of ``(step, Request)`` injected
        when the engine reaches that step — the staggered-arrival test
        harness.  Returns ``{rid: generated tokens}``.
        """
        schedule = sorted(arrivals or [], key=lambda a: a[0])
        i = 0
        while True:
            while i < len(schedule) and schedule[i][0] <= self.steps:
                if not self.submit(schedule[i][1]):
                    break  # queue full: retry next step
                i += 1
            if self.pending == 0 and i == len(schedule):
                return dict(self.results)
            if self.steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain in {max_steps} steps "
                    f"({self.pending} pending)"
                )
            self.step()
