"""The float32 reference of ``granite-3-2b`` against the program's decode
path on the CPU at a small size, and ``bench/run.py`` without a chip."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: Widest |program - reference| logit, over the rms of the reference
#: logits, that the program may show.  It computes in bfloat16, whose
#: relative step is 2^-8: its logits stray by 1.1% rms, 5.3% at worst
#: over these 131,072; the int8 control strays by 4% rms, 21% at worst.
PROGRAM_TOL = 0.1


@pytest.fixture(scope="module")
def setup():
    import dataclasses

    from benchtiny import TINY_LM

    cell = harness.load_cell("granite-3-2b.chat-topk")
    cfg = dict(cell.config, **TINY_LM)
    cell = dataclasses.replace(cell, config=cfg, traffic=dict(cell.traffic))
    return cell, cfg, cell.reference()


def _program_logits(cfg, traffic, params, tokens):
    """Feed ``tokens (b, L)`` one position a step through the engine's
    jitted ragged decode step; logits of every position."""
    from repro.models.transformer import Cache
    from repro.serving.engine import _ragged_step_fn
    from repro.serving.kv_pool import KVPool

    sys.path.insert(0, str(ROOT / "bench" / "drivers"))
    drivers = harness.load_module(ROOT / "bench" / "drivers" / "serve.py")
    mcfg = drivers.model_config(cfg, traffic)
    b, length = tokens.shape
    pool = KVPool(mcfg, b, cfg["serve"]["max_len"])
    cache = Cache(pool.cache.kind, pool.cache.data, jnp.zeros((b,), jnp.int32))
    step = _ragged_step_fn(mcfg)
    out = []
    for t in range(length):
        logits, cache = step(params, cache, jnp.asarray(tokens[:, t:t + 1]),
                             jnp.ones((b,), bool))
        out.append(np.asarray(logits))
    return np.stack(out, axis=1)


def test_reference_agrees_with_prefill_then_decode(setup):
    cell, cfg, ref = setup
    params = ref.make_weights(cfg, 1234)
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (4, cfg["serve"]["max_len"]), dtype=np.int32)
    want = np.asarray(ref.reference_logits(cfg, params, tokens))
    got = _program_logits(cfg, cell.traffic, params, tokens)
    scale = np.sqrt(np.mean(want**2))
    err = np.max(np.abs(got - want)) / scale
    assert err < PROGRAM_TOL, err
    # computed in int8, the comparison fails
    ctl = np.asarray(ref.reference_logits(cfg, params, tokens, "int8"))
    assert np.max(np.abs(ctl - want)) / scale > PROGRAM_TOL


def test_weights_are_made_from_the_seed(setup):
    _, cfg, ref = setup
    a = jax.tree.leaves(ref.make_weights(cfg, 7))
    b = jax.tree.leaves(ref.make_weights(cfg, 7))
    c = jax.tree.leaves(ref.make_weights(cfg, 8))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert {str(x.dtype) for x in a} == {"bfloat16", "float32"}


def test_seed_words_take_large_seeds():
    words = harness.seeds(2**33 + 5, 3)
    assert words != harness.seeds(5, 3)
    assert all(0 <= w < 2**32 for w in words)


def test_run_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-2b.chat-topk", "--seed", "0", "--seconds", "10",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
