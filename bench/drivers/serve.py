"""Serving cells: a closed loop of clients over ``DecodeEngine``.

The one generator of every serving mix.  Its parameters come from
``traffic/<mix>.json``:

* ``clients``: requests in flight; each client sends its next request
  when the last one finishes (vLLM's ``--max-concurrency`` mode);
* ``prompt_len`` / ``output_len``: lognormal ``median`` and ``sigma``,
  clipped to ``[min, max]``.  The lengths are the distribution's
  quantiles at ``(i + 0.5) / requests``, dealt in blocks of ``clients``
  requests that each hold one length of every stratum of the quantiles:
  every seed serves the same multiset of sizes, any stretch of the
  window sees nearly the same mix, and the seed only deals them out in
  another order and draws the prompt tokens;
* ``sampler``, ``top_k``, ``temperature``: the engine's sampler;
* ``warm_steps``: engine steps run in set-up, so the window starts with
  every slot busy and every program compiled;
* ``trace_seconds``: length of the traced window of a ``--trace 1`` run;
* ``check_requests``: requests finished in the window whose served
  tokens are compared with the reference, the longest among them;
* ``check_steps``: steps of the window, drawn from the seed, whose
  sampled tokens are compared with the reference sampler.

The window drives ``DecodeEngine.step`` (admission, the jitted ragged
decode step, the sampler) and nothing else.  Its rate counts tokens by
their content, from the scheduler's counters: the prompt tokens fed and
the tokens generated in the window.  ``correct`` compares

* the model: each served token's logit in the float32 reference run
  over the request's prompt and served tokens (``logit_gap``: the widest
  gap by which it lies below the reference's best, greedy, or below its
  ``top_k``-th best, top-k), and the keys and values the window wrote
  into the cache (``kv_rel_err``);
* the sampler: at ``check_steps`` steps, the token served to every slot
  that sampled, against the token the stated rule (argmax, or a draw
  among ``lax.top_k`` of the logits at ``temperature`` with the
  request's own key) takes from the logits the program gave the sampler
  at that step (``sampler_mismatch``, exact).
"""

from __future__ import annotations

import functools
import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.harness import (ROOT, Cell, device_record, frozen_setup, ok,
                           percentile, seeds)

sys.path.insert(0, str(ROOT / "src"))

#: sequences per reference call; one compiled program serves every block
REF_BLOCK = 4
#: fewest served tokens a run compares before it may read correct
MIN_CHECKED = 64


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """Lognormal quantiles at ``(i + 0.5) / n``, rounded and clipped."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def dealt_lengths(spec: dict, n: int, block: int, rng) -> np.ndarray:
    """The ``n`` quantiles in blocks of ``block``: block ``j`` holds one
    length of each of ``block`` strata of consecutive quantiles, in an
    order and choice drawn from ``rng``."""
    if n % block:
        raise ValueError(f"requests {n} are not whole blocks of {block}")
    strata = quantile_lengths(spec, n).reshape(block, n // block)
    blocks = rng.permuted(strata, axis=1).T
    return rng.permuted(blocks, axis=1).reshape(-1)


def model_config(cfg: dict, traffic: dict):
    """The program's ``ModelConfig`` for this configuration file; refuses
    a file that states something the dense GQA path does not compute."""
    from repro.configs.base import ModelConfig

    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    runs_as = {
        "hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
        "rms_norm_eps": 1e-6, "attention_multiplier": 1 / math.sqrt(hd),
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
        "logits_scaling": 1.0,
    }
    for key, want in runs_as.items():
        if cfg[key] != want:
            raise ValueError(f"{key}={cfg[key]!r}: the dense path runs {want!r}")
    return ModelConfig(
        name=cfg["model_type"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=hd, rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["activation_dtype"], param_dtype=cfg["torch_dtype"],
        max_batch=cfg["serve"]["slots"], queue_depth=traffic["clients"],
    )


def attended_positions(engine) -> int:
    """Cache positions the next engine step attends over, summed over its
    slots, from the scheduler's host state (no device read): a slot that
    has fed ``m`` tokens attends over ``m + 1``; an admission over 1."""
    sched = engine.scheduler
    total = 0
    for _, st in sched.occupied():
        total += st.fed + max(st.generated - 1, 0) + 1
    return total + min(engine.pool.free_slots, sched.queued)


class ClosedLoop:
    """``clients`` requests in flight over one engine, with token times."""

    def __init__(self, engine, prompts, outputs, clients: int):
        from repro.serving.scheduler import Request

        self._request = Request
        self.engine = engine
        self.prompts, self.outputs = prompts, outputs
        self.next_rid = 0
        self.served: dict[int, list[int]] = {}
        self.stamps: dict[int, list[float]] = {}
        self.finished: list[tuple[int, float]] = []
        self.prompt_done = 0  # prompt tokens of the finished requests
        for _ in range(clients):
            self._send()

    def _send(self) -> None:
        rid = self.next_rid
        i = rid % len(self.prompts)
        ok = self.engine.submit(
            self._request(rid, self.prompts[i], int(self.outputs[i])))
        if not ok:
            raise RuntimeError(f"engine refused request {rid}: queue full")
        self.served[rid], self.stamps[rid] = [], []
        self.next_rid += 1

    def step(self) -> dict:
        out = self.engine.step()
        t = time.perf_counter()
        for rid, tok in out["sampled"].items():
            self.served[rid].append(tok)
            self.stamps[rid].append(t)
        for rid in out["completed"]:
            self.finished.append((rid, t))
            self.prompt_done += self.prompt(rid).size
            self._send()
        return out

    def prompt(self, rid: int) -> np.ndarray:
        return self.prompts[rid % len(self.prompts)]

    def prompt_fed(self) -> int:
        """Prompt tokens fed so far, by the scheduler's ``fed`` counters."""
        return self.prompt_done + sum(
            st.fed for _, st in self.engine.scheduler.occupied())


def record_sampler(engine, rng, keep: int) -> list:
    """Keep, for ``keep`` of the engine's sampler calls from now on (a
    reservoir drawn from ``rng``), the logits the sampler was given and
    ``(slot, rid, token index)`` of each slot that samples: references
    to the device arrays, no copy and no wait."""
    kept: list = []
    inner = engine._sample
    calls = [0]

    def sample(keys, logits):
        calls[0] += 1
        at = len(kept) if len(kept) < keep else int(rng.integers(0, calls[0]))
        if at < keep:
            due = [(slot, st.request.rid, st.generated)
                   for slot, st in engine.scheduler.occupied()
                   if st.samples_this_step]
            if at == len(kept):
                kept.append(None)
            kept[at] = (logits, due)
        return inner(keys, logits)

    engine._sample = sample
    return kept


@functools.partial(jax.jit, static_argnames=("k",))
def _gaps(ref, targets, *, k: int):
    """How far each target's logit lies below the ``k``-th best logit of
    its row, 0 inside the top ``k``."""
    kth = jax.lax.top_k(ref, k)[0][..., -1]
    got = jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]
    return jnp.maximum(kth - got, 0.0)


def token_gaps(module, cfg: dict, params, seqs, *, k: int,
               precision: str = "float32", chooser=None) -> list:
    """Gap of every served token of each ``(prompt, served)`` in ``seqs``:
    how far its reference logit lies below the reference's ``k``-th best
    (``k = 1``: the best) at its position, 0 inside that set.

    With ``chooser``, the tokens are not the served ones but those
    ``chooser(first, logits)`` picks at every position of the block of
    sequences that starts at ``seqs[first]``, from the ``(REF_BLOCK, L,
    vocab)`` logits of the ``precision`` forward (the control)."""
    max_len = cfg["serve"]["max_len"]
    gaps = []
    for b0 in range(0, len(seqs), REF_BLOCK):
        block = seqs[b0:b0 + REF_BLOCK]
        feed = np.zeros((REF_BLOCK, max_len + 1), np.int32)
        for i, (prompt, served) in enumerate(block):
            row = np.concatenate([prompt, np.asarray(served, np.int32)])
            feed[i, :row.size] = row
        ref = module.reference_logits(cfg, params, feed[:, :-1])
        if chooser is None:
            targets = jnp.asarray(feed[:, 1:])
        else:
            other = module.reference_logits(cfg, params, feed[:, :-1],
                                            precision)
            targets = chooser(b0, other)
            del other
        block_gaps = np.asarray(_gaps(ref, targets, k=k))
        del ref
        for i, (prompt, served) in enumerate(block):
            gaps.append(block_gaps[i, prompt.size - 1:
                                   prompt.size - 1 + len(served)])
    return gaps


def fed_tokens(st) -> np.ndarray:
    """Tokens a slot has fed so far, whose keys and values its cache holds:
    its prompt, then every sampled token but the newest."""
    prompt = st.request.prompt[:st.fed]
    if st.fed < st.request.prompt.size:
        return prompt
    return np.concatenate(
        [prompt, np.asarray(st.tokens[:max(st.generated - 1, 0)], np.int32)])


def kv_rel_err(module, cfg: dict, params, seqs, cached,
               precision: str = "float32") -> float:
    """Worst layer's ``|K - K_ref| / |K_ref|`` (or of V) over the positions
    each sequence of ``seqs`` filled; ``cached`` holds the compared keys
    and values ``(layers, n, L, kv_heads, head_dim)``, or ``None`` to
    compare the ``precision`` forward instead (the control)."""
    max_len = cfg["serve"]["max_len"]
    feed = np.zeros((REF_BLOCK, max_len), np.int32)
    valid = np.zeros((REF_BLOCK, max_len), bool)
    for i, toks in enumerate(seqs):
        feed[i, :toks.size] = toks
        valid[i, :toks.size] = True
    ref = module.reference_kv(cfg, params, feed)
    got = cached if cached is not None else module.reference_kv(
        cfg, params, feed, precision)
    worst = 0.0
    for want, have in zip(ref, got):
        want = np.asarray(want)[:, :len(seqs)][:, valid[:len(seqs)]]
        have = np.asarray(have, np.float32)[:, :len(seqs)][:, valid[:len(seqs)]]
        num = np.sum((have - want) ** 2, axis=tuple(range(1, want.ndim)))
        den = np.sum(want ** 2, axis=tuple(range(1, want.ndim)))
        worst = max(worst, float(np.sqrt(num / den).max()))
    return worst


@functools.partial(jax.jit, static_argnames=("k", "temperature"))
def reference_draw(base, logits, rids, gens, *, k: int, temperature: float):
    """The token the stated sampler takes from each row of ``logits (n,
    vocab)``: the first largest (``k = 1``), else a draw among the ``k``
    largest (``lax.top_k``) at ``temperature`` with the row's key, ``(rid,
    token index)`` folded into ``base``."""
    if k == 1:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    keys = jax.vmap(lambda r, g: jax.random.fold_in(
        jax.random.fold_in(base, r), g))(rids, gens)
    vals, idx = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(vals.astype(jnp.float32) / temperature, axis=-1)
    choice = jax.vmap(
        lambda kk, pp: jax.random.categorical(kk, jnp.log(pp + 1e-20)))(
            keys, probs)
    return jnp.take_along_axis(idx, choice[:, None], 1)[:, 0]


def draw_rule(traffic: dict, engine_word: int):
    """``reference_draw`` with the mix's sampler and the engine's seed."""
    base = jax.random.key(engine_word)
    k = sampler_k(traffic)
    temp = float(traffic["temperature"])
    return lambda logits, rids, gens: reference_draw(
        base, logits, jnp.asarray(rids, jnp.uint32),
        jnp.asarray(gens, jnp.uint32), k=k, temperature=temp)


def sampler_mismatch(kept: list, served: dict, draw) -> tuple[int, int]:
    """Of the slots that sampled at the kept steps, how many were served
    another token than ``draw`` takes from the step's logits; and how
    many were compared."""
    wrong = checked = 0
    for logits, due in kept:
        b = logits.shape[0]
        rids, gens = np.zeros(b, np.uint32), np.zeros(b, np.uint32)
        for slot, rid, gen in due:
            rids[slot], gens[slot] = rid, gen
        want = np.asarray(draw(logits, rids, gens))
        for slot, rid, gen in due:
            wrong += int(served[rid][gen] != want[slot])
            checked += 1
    return wrong, checked


def sampler_chooser(traffic: dict, engine_word: int, rids, prompt_lens):
    """The engine's own sampling rule, applied to other logits: at every
    position of a block of sequences, the token its sampler would draw
    (the same per-request keys: ``(rid, token index)`` folded into the
    engine's seed)."""
    draw = draw_rule(traffic, engine_word)
    rids = np.asarray(rids, np.uint32)
    starts = np.asarray(prompt_lens, np.int64) - 1

    def choose(first, logits):
        b, length = logits.shape[:2]
        r = np.zeros((b, length), np.uint32)
        gens = np.zeros((b, length), np.uint32)
        n = min(b, len(rids) - first)
        r[:n] = rids[first:first + n, None]
        pos = np.arange(length)[None, :]
        gens[:n] = np.clip(pos - starts[first:first + n, None], 0, None)
        flat = draw(logits.reshape(b * length, -1), r.reshape(-1),
                    gens.reshape(-1))
        return flat.reshape(b, length)

    return choose


def sampler_k(traffic: dict) -> int:
    """Size of the set a served token may come from."""
    return traffic["top_k"] if traffic["sampler"] == "topk" else 1


def judge(checks: list) -> bool:
    return bool(checks) and all(ok(c) for c in checks)


def model_checks(cell: Cell, gaps_per: list, kv_err: float) -> list:
    """The model's numbers beside their limits: the widest logit gap over
    the compared tokens, how many were compared, and the cache's error."""
    return [
        {"name": "logit_gap", "value": max(float(g.max()) for g in gaps_per),
         "limit": cell.limits["logit_gap"]["limit"]},
        {"name": "tokens_checked", "value": sum(g.size for g in gaps_per),
         "limit": MIN_CHECKED, "at_least": True},
        {"name": "kv_rel_err", "value": kv_err,
         "limit": cell.limits["kv_rel_err"]["limit"]},
    ]


def run(cell: Cell, seed: int, seconds: float, tracer,
        control: str = "") -> dict:
    """One run.  ``control`` (``int8``; only ``bench/control.py`` and the
    tests set it) also judges the control by the same comparison: the
    reference in that precision in the program's place, its tokens drawn
    by the engine's own rule at every position of the compared requests,
    its keys and values those of the same tokens."""
    from repro.serving.engine import DecodeEngine

    cfg, traffic = cell.config, cell.traffic
    w_weights, w_engine, w_order = seeds(seed, 3)
    rng = np.random.default_rng(w_order)
    n, block = traffic["requests"], traffic["clients"]
    prompt_lens = dealt_lengths(traffic["prompt_len"], n, block, rng)
    outputs = dealt_lengths(traffic["output_len"], n, block, rng)
    prompts = [rng.integers(0, cfg["vocab_size"], p, dtype=np.int32)
               for p in prompt_lens]

    module = cell.reference()
    params = module.make_weights(cfg, w_weights)
    engine = DecodeEngine(
        model_config(cfg, traffic), params, max_len=cfg["serve"]["max_len"],
        sampler=traffic["sampler"], top_k=traffic["top_k"],
        temperature=traffic["temperature"], seed=w_engine,
    )
    loop = ClosedLoop(engine, prompts, outputs, traffic["clients"])
    for _ in range(traffic["warm_steps"]):
        loop.step()
    kept = record_sampler(engine, np.random.default_rng(w_order + 2),
                          traffic["check_steps"])

    # -- the window -------------------------------------------------------
    length = min(seconds, traffic["trace_seconds"]) if tracer.on else seconds
    steps = []  # (start, end, active slots, sum of attended positions)
    generated = 0
    with frozen_setup():
        fed0 = loop.prompt_fed()
        tracer.start()
        t0 = time.perf_counter()
        t1 = t0
        while t1 - t0 < length:
            ctx = attended_positions(engine) if tracer.on else 0
            with tracer.span("bench.engine_step"):
                out = loop.step()
            start, t1 = t1, time.perf_counter()
            steps.append((start, t1, out["active"], ctx))
            generated += len(out["sampled"])
        tracer.stop()
        fed = loop.prompt_fed() - fed0
    device = device_record(jax.devices())

    gaps = [b - a for st in loop.stamps.values()
            for a, b in zip(st, st[1:]) if t0 < b <= t1]
    done = [rid for rid, t in loop.finished if t0 < t <= t1]
    metrics = {"tokens_per_s": (fed + generated) / (t1 - t0)}
    if gaps:
        metrics["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    facts = {"flops": sum(s[2] * 2 * flops.dense_matmul_params(cfg)
                          + flops.attention_flops(cfg, 1) * s[3]
                          for s in steps)}

    # -- the sampler, on the logits it was given ----------------------------
    draw = draw_rule(traffic, w_engine)
    wrong, drawn = sampler_mismatch(kept, loop.served, draw)
    del kept
    checks = [{"name": "sampler_mismatch", "value": wrong,
               "limit": cell.limits["sampler_mismatch"]["limit"]},
              {"name": "sampler_checked", "value": drawn,
               "limit": traffic["check_steps"], "at_least": True}]

    # -- the model, once the program's state is freed ------------------------
    pick = np.random.default_rng(w_order + 1)
    flight = engine.scheduler.occupied()
    flight = [flight[i] for i in sorted(
        pick.permutation(len(flight))[:REF_BLOCK])]
    slots = np.array([slot for slot, _ in flight])
    kv_seqs = [fed_tokens(st) for _, st in flight]
    held = np.asarray(engine.pool.cache.length)[slots]
    if [t.size for t in kv_seqs] != held.tolist():
        raise RuntimeError(f"cache lengths {held} != tokens fed "
                           f"{[t.size for t in kv_seqs]}")
    cached = [np.asarray(c[:, slots]) for c in engine.pool.cache.data]
    by_len = sorted(done, key=lambda r: (-(loop.prompt(r).size
                                           + len(loop.served[r])), r))
    chosen = by_len[:1] + list(pick.permutation(by_len[1:])[
        : max(traffic["check_requests"] - 1, 0)])
    seqs = [(loop.prompt(r), list(loop.served[r])) for r in chosen]
    del engine, loop, params
    gc.collect()

    failed, control_line = 0, None
    if seqs:
        params = module.make_weights(cfg, w_weights)
        k = sampler_k(traffic)
        gaps_per = token_gaps(module, cfg, params, seqs, k=k)
        failed = sum(float(g.max()) > cell.limits["logit_gap"]["limit"]
                     for g in gaps_per)
        checks += model_checks(
            cell, gaps_per, kv_rel_err(module, cfg, params, kv_seqs, cached))
        if control:
            chooser = sampler_chooser(traffic, w_engine, chosen,
                                      [p.size for p, _ in seqs])
            ctl_checks = model_checks(
                cell,
                token_gaps(module, cfg, params, seqs, k=k, precision=control,
                           chooser=chooser),
                kv_rel_err(module, cfg, params, kv_seqs, None, control))
            control_line = {"control": control, "correct": judge(ctl_checks),
                            "checks": ctl_checks}
        del params
    correct = judge(checks) and failed == 0 and bool(seqs)
    result = {
        "setup_end": t0, "metrics": metrics, "correct": correct,
        "attempted": len(done), "failed": failed, "checks": checks,
        "device": device, "facts": facts,
    }
    if control_line:
        result["control"] = control_line
    return result
