"""Merge cells: back-to-back calls of one ``ops`` merge entry point.

The one generator of every merge mix.  Its parameters come from
``traffic/<mix>.json``:

* ``entry``: ``stable_merge_kway`` (runs only) or ``merge_window`` (runs,
  optional payload and ragged ``lengths``);
* ``k``: sorted runs per call, each ``n / k`` wide;
* ``distinct``: keys are drawn from this many values spread over the
  int32 range, so equal keys repeat across every run;
* ``payload``: carry the flat input index as an int32 payload;
* ``min_fill``: each row holds between ``min_fill`` and all of its width
  in real keys, the rest sentinel.  The fills are fixed fractions, the
  same for every seed, which only deals them to other rows;
* ``inputs``: distinct inputs made in set-up and called in turn;
* ``trace_seconds``: length of the traced window of a ``--trace 1`` run.

The window calls the compiled entry point, as ``jax.jit`` would, on
inputs made on the device from the seed, each call waited for.
``correct`` compares the output of a sample of the window's calls, drawn
from the seed, with the reference bit for bit, and asks the compiled
program to hold the Pallas kernel (``tpu_custom_call``) on a TPU.
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.harness import (ROOT, Cell, device_record, frozen_setup, ok,
                           seeds)

sys.path.insert(0, str(ROOT / "src"))

#: window calls whose output is compared
SAMPLE_CALLS = 3


def fills(traffic: dict, n_inputs: int) -> np.ndarray:
    """Fill fraction of each row of each input: evenly spaced in
    ``[min_fill, 1]`` over all the rows."""
    k, lo = traffic["k"], traffic["min_fill"]
    q = (np.arange(n_inputs * k) + 0.5) / (n_inputs * k)
    return (lo + (1.0 - lo) * q).reshape(n_inputs, k)


@functools.partial(jax.jit, static_argnames=("k", "w", "distinct", "payload"))
def make_input(key, lengths, *, k: int, w: int, distinct: int, payload: bool):
    """Sorted ``(k, w)`` int32 rows with sentinel tails past ``lengths``."""
    stride = (2**32 - 2) // distinct
    draw = jax.random.randint(key, (k, w), 0, distinct, jnp.int32)
    keys = jnp.sort(jnp.int32(-(2**31) + 1) + draw * jnp.int32(stride), axis=1)
    pad = jnp.arange(w, dtype=jnp.int32)[None, :] >= lengths[:, None]
    keys = jnp.where(pad, jnp.iinfo(jnp.int32).max, keys)
    vals = jnp.arange(k * w, dtype=jnp.int32).reshape(k, w) if payload else None
    return keys, vals


def entry_args(traffic: dict, keys, vals, lengths) -> tuple:
    if traffic["entry"] == "stable_merge_kway":
        return (keys,)
    if traffic["entry"] == "merge_window":
        return (keys, vals, lengths)
    raise ValueError(f"unknown merge entry {traffic['entry']!r}")


def run(cell: Cell, seed: int, seconds: float, tracer,
        control: str = "") -> dict:
    """One run.  ``control`` (a control of the reference module; only
    ``bench/control.py`` and the tests set it) also judges that control,
    put in the program's place on the sampled calls' inputs, by the same
    comparison."""
    from repro.kernels import ops

    cfg, traffic = cell.config, cell.traffic
    k, n_inputs = traffic["k"], traffic["inputs"]
    w = cfg["n"] // k
    if cfg["key_dtype"] != "int32" or cfg["n"] % k:
        raise ValueError("merge cells take int32 keys in k equal rows")
    w_data, w_order, w_pick = seeds(seed, 3)
    row_fill = np.random.default_rng(w_order).permuted(fills(traffic, n_inputs))
    lengths = [np.floor(f * w).astype(np.int32) for f in row_fill]
    inputs = []
    for j in range(n_inputs):
        keys, vals = make_input(
            jax.random.fold_in(jax.random.key(w_data), j),
            jnp.asarray(lengths[j]), k=k, w=w, distinct=traffic["distinct"],
            payload=traffic["payload"])
        inputs.append(entry_args(traffic, keys, vals, jnp.asarray(lengths[j])))
    entry = getattr(ops, traffic["entry"])
    compiled = entry.lower(*inputs[0]).compile()
    kernel = "tpu_custom_call" in compiled.as_text()
    jax.block_until_ready(compiled(*inputs[0]))

    # -- the window -------------------------------------------------------
    length = min(seconds, traffic["trace_seconds"]) if tracer.on else seconds
    pick = np.random.default_rng(w_pick)
    kept: list[tuple[int, object]] = []  # reservoir of (input, output)
    calls = []  # (start, end, real elements)
    with frozen_setup():
        tracer.start()
        t0 = time.perf_counter()
        t1 = t0
        while t1 - t0 < length:
            j = len(calls) % n_inputs
            with tracer.span("bench.merge_call"):
                out = jax.block_until_ready(compiled(*inputs[j]))
            start, t1 = t1, time.perf_counter()
            calls.append((start, t1, int(lengths[j].sum())))
            if len(kept) < SAMPLE_CALLS:
                kept.append((j, out))
            else:
                slot = pick.integers(0, len(calls))
                if slot < SAMPLE_CALLS:
                    kept[slot] = (j, out)
            del out
        tracer.stop()
    device = device_record(jax.devices())

    real = sum(c[2] for c in calls)
    payload_bytes = 4 if traffic["payload"] else 0
    metrics = {"elements_per_s": real / (t1 - t0)}
    facts = {
        "calls": len(calls),
        "program": f"jit_{traffic['entry']}",
        "min_bytes": sum(flops.merge_min_bytes(c[2], 4, payload_bytes)
                         for c in calls),
    }

    # -- correctness: the sampled calls against the reference --------------
    module = cell.reference()
    host_out = [(j, jax.tree.map(np.asarray, out)) for j, out in kept]
    by_input = {j: jax.tree.map(np.asarray, inputs[j]) for j, _ in kept}
    del kept, inputs, compiled
    wrong = control_wrong = failed_calls = 0
    for j, out in host_out:
        args = by_input[j]
        runs = args[0]
        vals = args[1] if len(args) > 1 else None
        want_k, want_v = module.reference(runs, vals, lengths[j])
        bad = mismatches(out, want_k, want_v)
        wrong += bad
        failed_calls += bad > 0
        if control:
            ctl = module.reference(runs, vals, lengths[j], control=control)
            control_wrong += mismatches(ctl if vals is not None else ctl[0],
                                        want_k, want_v)
    checks = judged(cell, wrong, len(host_out))
    if device["platform"] == "tpu":
        checks.append({"name": "kernel_missing", "value": int(not kernel),
                       "limit": 0})
    result = {
        "setup_end": t0, "metrics": metrics,
        "correct": all(ok(c) for c in checks),
        "attempted": len(calls), "failed": failed_calls, "checks": checks,
        "device": device, "facts": facts,
    }
    if control:
        ctl_checks = judged(cell, control_wrong, len(host_out))
        result["control"] = {"control": control, "checks": ctl_checks,
                             "correct": all(ok(c) for c in ctl_checks)}
    return result


def judged(cell: Cell, wrong: int, calls: int) -> list:
    """The numbers compared beside their limits: elements that differ
    from the reference over the compared calls, and how many calls."""
    return [{"name": "mismatched_elements", "value": wrong,
             "limit": cell.limits["mismatched_elements"]["limit"]},
            {"name": "calls_checked", "value": calls, "limit": 1,
             "at_least": True}]


def mismatches(out, want_k, want_v) -> int:
    """Output positions whose key or payload differs from the reference,
    bit for bit, over the real elements."""
    got_k, got_v = (out, None) if want_v is None else out
    n = want_k.size
    bad = np.asarray(got_k)[:n].view(np.uint32) != want_k.view(np.uint32)
    if want_v is not None:
        bad |= np.asarray(got_v)[:n] != want_v
    return int(bad.sum())
