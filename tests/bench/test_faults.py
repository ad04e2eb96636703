"""Whole runs of each cell at a CPU size, past the harness's look for a
chip: sound runs read ``correct``; runs with the timed path broken
underneath do not, nor does each control put in the program's place."""

import argparse

import jax
import numpy as np
import pytest

from bench import harness

CELLS = ["granite-3-2b.chat-topk", "granite-3-2b.chat-greedy",
         "merge-2p24-int32.k16-dup", "merge-2p24-int32.k8-window"]


def run(cell, seed=2**31 + 11, trace=0):
    from bench import run as bench_run

    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=1.0,
                              trace=trace, keep_trace="")
    return bench_run.measure(args, cell=cell, require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_cell, name):
    line = run(tiny_cell(name))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = harness.load_cell(name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", CELLS[:1] + CELLS[3:])
def test_traced_run_is_correct(tiny_cell, name):
    line = run(tiny_cell(name), trace=1)
    assert line["correct"], line["checks"]
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", CELLS[:2])
def test_altered_token_fails(tiny_cell, monkeypatch, name):
    from repro.serving.engine import DecodeEngine

    sample = DecodeEngine._sample

    def altered(self, keys, logits):
        tok = sample(self, keys, logits)
        return (tok + 1) % self.cfg.vocab

    monkeypatch.setattr(DecodeEngine, "_sample", altered)
    line = run(tiny_cell(name))
    assert not line["correct"]
    assert not line["checks"]["logit_gap"]["ok"]
    assert not line["checks"]["sampler_mismatch"]["ok"]


@pytest.mark.parametrize("setting,value", [("temperature", 0.1),
                                           ("top_k", 100)])
def test_misconfigured_sampler_fails(tiny_cell, monkeypatch, setting, value):
    """A sampler that draws at another temperature or from another top-k
    serves tokens the logit gap cannot tell from sound ones."""
    from repro.serving.engine import DecodeEngine

    sample = DecodeEngine._sample

    def misconfigured(self, keys, logits):
        setattr(self, setting, value)
        return sample(self, keys, logits)

    monkeypatch.setattr(DecodeEngine, "_sample", misconfigured)
    line = run(tiny_cell("granite-3-2b.chat-topk"))
    assert not line["correct"]
    assert not line["checks"]["sampler_mismatch"]["ok"]
    assert line["checks"]["sampler_mismatch"]["value"] > 0


CONTROLS = [("granite-3-2b.chat-greedy", "int8", "kv_rel_err"),
            ("granite-3-2b.chat-topk", "int8", "kv_rel_err"),
            ("merge-2p24-int32.k16-dup", "approximate_cuts",
             "mismatched_elements"),
            ("merge-2p24-int32.k8-window", "unstable", "mismatched_elements")]


@pytest.mark.parametrize("name,control,number", CONTROLS)
def test_control_is_not_correct(tiny_cell, name, control, number):
    """The control in the program's place, judged by the cell's own
    comparison and limits, reads not correct, while the program does."""
    from bench.run import Tracer

    cell = tiny_cell(name)
    result = cell.driver().run(cell, 2**31 + 13, 1.0, Tracer(False),
                               control=control)
    assert result["correct"], result["checks"]
    ctl = result["control"]
    assert ctl["control"] == control
    assert not ctl["correct"]
    checks = {c["name"]: c for c in ctl["checks"]}
    assert not harness.ok(checks[number]), checks


@pytest.mark.parametrize("name", CELLS[2:])
def test_altered_answer_fails(tiny_cell, monkeypatch, name):
    import repro.core.kway as kway

    ranked = kway.merge_kway_ranked

    def altered(*args, **kwargs):
        out = ranked(*args, **kwargs)
        if isinstance(out, tuple):
            return out[0].at[0].add(1), out[1]
        return out.at[0].add(1)

    monkeypatch.setattr(kway, "merge_kway_ranked", altered)
    jax.clear_caches()
    try:
        line = run(tiny_cell(name))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"]
    assert line["checks"]["mismatched_elements"]["value"] > 0


@pytest.fixture
def merge_reference():
    cell = harness.load_cell("merge-2p24-int32.k8-window")
    return cell.reference()


def _sorted_rows(k, w, distinct, seed):
    rng = np.random.default_rng(seed)
    return np.sort(rng.integers(0, distinct, (k, w)), axis=1).astype(np.int32)


def test_unstable_control_fails(merge_reference):
    from bench.drivers import merge as driver

    runs = _sorted_rows(8, 256, 16, 0)
    vals = np.arange(runs.size, dtype=np.int32).reshape(runs.shape)
    lengths = np.array([256, 200, 192, 250, 256, 193, 220, 240])
    want = merge_reference.reference(runs, vals, lengths)
    ctl = merge_reference.reference(runs, vals, lengths, control="unstable")
    assert np.array_equal(want[0], ctl[0])  # keys alike: only the order breaks
    assert driver.mismatches(ctl, *want) > 0


def test_approximate_cuts_control_fails(merge_reference, monkeypatch):
    from bench.drivers import merge as driver

    monkeypatch.setattr(merge_reference, "CONTROL_TILES", 16)
    runs = _sorted_rows(16, 256, 1024, 1)
    want = merge_reference.reference(runs)
    ctl = merge_reference.reference(runs, control="approximate_cuts")
    assert np.array_equal(np.sort(ctl[0]), want[0])  # a permutation
    assert driver.mismatches(ctl[0], want[0], None) > 0


def test_unwritten_cache_fails(tiny_cell, monkeypatch):
    import repro.serving.engine as engine
    from repro.models.transformer import Cache

    real = engine._ragged_step_fn

    def stale(cfg):
        step = real(cfg)

        def returns_old_cache(params, cache, tokens, active):
            logits, new = step(params, cache, tokens, active)
            return logits, Cache(new.kind, cache.data, new.length)

        return returns_old_cache

    monkeypatch.setattr(engine, "_ragged_step_fn", stale)
    line = run(tiny_cell("granite-3-2b.chat-greedy"))
    assert not line["correct"]
    assert not line["checks"]["kv_rel_err"]["ok"]
