"""Observability-layer tests.

Fast lane (runs in the main single-device pytest process): the
zero-overhead-off guarantee — instrumented functions traced while obs is
disabled produce HLO byte-identical to a never-enabled trace, with no
callback custom-calls — plus registry/sink unit behaviour and the kernel
dispatch validation.

Slow lane: the 8-device acceptance run (``tests/_obs_check.py``) in a
subprocess, mirroring tests/test_exchange.py — the main process must keep
a single device.
"""

import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.kway import merge_kway

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# zero overhead when disabled
# ---------------------------------------------------------------------------


def _lower_merge_kway():
    fn = jax.jit(lambda runs: merge_kway(runs))
    return (
        fn.lower(jax.ShapeDtypeStruct((4, 32), jnp.int32))
        .compile()
        .as_text()
    )


# Debug metadata (op_name scopes, source_file/source_line) is not part of
# the compiled program: line attribution shifts with jax's trace-cache
# state (e.g. whose frame first traced jnp.where's inner jit), so the
# identity check compares the HLO with metadata stripped — both the
# per-instruction ``metadata={...}`` and the module's source-location
# tables (FileNames / FunctionNames / FileLocations / StackFrames, one
# numbered entry per line, each table closed by a blank line).
_HLO_METADATA_RE = re.compile(r", metadata=\{[^}]*\}")
_HLO_LOCATION_TABLE_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:\d+ .*\n)*\n?",
    re.M,
)


def _canon(hlo: str) -> str:
    return _HLO_LOCATION_TABLE_RE.sub("", _HLO_METADATA_RE.sub("", hlo))


def test_disabled_hlo_identical_and_callback_free():
    """Tier-1 guard: instrumentation must not change the compiled program
    while disabled — not after an enable/disable cycle either."""
    assert not obs.enabled()
    before = _lower_merge_kway()
    assert "custom-call" not in before

    with obs.capture():
        enabled_txt = _lower_merge_kway()
        assert "custom-call" in enabled_txt  # record points really trace

    after = _lower_merge_kway()
    assert _canon(after) == _canon(before), (
        "HLO of the disabled trace changed across an enable/disable cycle"
    )


def test_disabled_record_adds_no_jaxpr_ops():
    assert not obs.enabled()

    def f(x):
        obs.gauge("t.noop", x.sum())
        obs.counter("t.noop_c", 1)
        obs.histogram("t.noop_h", x)
        return x * 2

    jaxpr = str(jax.make_jaxpr(f)(jnp.arange(4)))
    assert "callback" not in jaxpr


# ---------------------------------------------------------------------------
# always-on scopes (metadata only) and host spans
# ---------------------------------------------------------------------------


def _sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _hlo_merge_kway():
    from repro.kernels.merge import merge_kway_pallas

    runs = _sds((4, 200))  # padded to 2 tiles a run, sliced back
    return merge_kway_pallas.lower(
        runs, runs, lengths=_sds((4,)), tile=128, interpret=True
    ).compile().as_text()


def _hlo_merge_pair():
    from repro.kernels.merge import merge_pallas

    return merge_pallas.lower(
        _sds((300,)), _sds((200,)), tile=128, interpret=True
    ).compile().as_text()


def _hlo_sample_topk():
    from repro.serving.sampling import sample_topk_batched

    keys = jax.random.split(jax.random.key(0), 3)
    return sample_topk_batched.lower(
        keys, _sds((3, 1000), jnp.float32), k=8, fanout=4
    ).compile().as_text()


def _hlo_ragged_step():
    from repro.configs.registry import ARCHS, smoke_config
    from repro.models.transformer import init_params
    from repro.serving.engine import _ragged_step_fn
    from repro.serving.kv_pool import KVPool

    cfg = smoke_config(ARCHS["qwen3-0.6b"])
    params, _ = init_params(cfg, jax.random.key(0))
    cache = KVPool(cfg, 2, 32, jnp.bfloat16).cache
    return _ragged_step_fn(cfg).lower(
        params, cache, _sds((2, 1)), _sds((2,), jnp.bool_)
    ).compile().as_text()


_SCOPED = {
    "merge_kway_pallas": (
        _hlo_merge_kway,
        ("repro.corank", "repro.slab_relayout", "repro.tile_merge")),
    "merge_pallas": (
        _hlo_merge_pair,
        ("repro.corank", "repro.slab_relayout", "repro.tile_merge")),
    "sample_topk_batched": (
        _hlo_sample_topk,
        ("repro.topk_block_sort", "repro.topk_tournament",
         "repro.topk_draw")),
    "ragged_step": (
        _hlo_ragged_step,
        ("repro.decoder_layers", "repro.attention", "repro.ffn")),
}


def _scope_paths(hlo: str) -> list[list[str]]:
    return [p.split("/") for p in re.findall(r'op_name="([^"]*)"', hlo)]


@pytest.mark.parametrize("fn", sorted(_SCOPED))
def test_scopes_in_compiled_hlo_while_disabled(fn):
    """With obs off, every op of a scoped region carries its stable
    ``repro.*`` scope in its ``op_name`` path."""
    assert not obs.enabled()
    lower, scopes = _SCOPED[fn]
    paths = _scope_paths(lower())
    for scope in scopes:
        assert any(scope in p for p in paths), (fn, scope)
    if fn == "ragged_step":  # the layer scopes sit inside the scan's
        for scope in ("repro.attention", "repro.ffn"):
            assert any("repro.decoder_layers" in p and scope in p
                       for p in paths), scope


@pytest.mark.parametrize("fn", ["merge_kway_pallas", "ragged_step"])
def test_scopes_are_metadata_only(fn, monkeypatch):
    """The compiled program with scopes equals the one with ``obs.span``
    a null context, once metadata is stripped."""
    import contextlib

    lower, scopes = _SCOPED[fn]
    jax.clear_caches()
    scoped = lower()
    monkeypatch.setattr(obs, "span", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = lower()
    jax.clear_caches()
    assert not any(scopes[0] in p for p in _scope_paths(plain))
    assert any(scopes[0] in p for p in _scope_paths(scoped))
    assert _canon(scoped) == _canon(plain)


def test_engine_step_opens_its_host_spans(monkeypatch):
    """One engine step opens the six ``serve.*`` spans, the five phases
    in order inside ``serve.step``."""
    from repro.configs.registry import ARCHS, smoke_config
    from repro.models.transformer import init_params
    from repro.serving import DecodeEngine, Request

    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    cfg = smoke_config(ARCHS["qwen3-0.6b"])
    params, _ = init_params(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, max_len=16, max_batch=2,
                          queue_depth=2, sampler="topk", top_k=4)
    engine.submit(Request(0, np.asarray([3, 5], np.int32), 2))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    engine.step()
    phases = ["serve.admit", "serve.build_inputs", "serve.dispatch",
              "serve.fetch_tokens", "serve.retire"]
    want = [("enter", "serve.step")]
    for name in phases:
        want += [("enter", name), ("exit", name)]
    want.append(("exit", "serve.step"))
    assert [e for e in events if e[1].startswith("serve.")] == want


def test_host_record_binds_no_callback(monkeypatch):
    """A record point in host code goes straight to the sink, with the
    record a traced one makes; inside a trace the callback stays."""
    with obs.capture() as recs:
        jax.jit(lambda x: (obs.gauge("t.host", 7, capacity=4), x)[1])(1)
        obs.flush()
    (traced,) = [r for r in recs if r["metric"] == "t.host"]

    def refuse(*args, **kwargs):
        raise AssertionError("host record bound a debug callback")

    monkeypatch.setattr(jax.debug, "callback", refuse)
    with obs.capture() as recs:
        obs.gauge("t.host", 7, capacity=4)
        obs.counter("t.host_c", np.int64(3))
        obs.flush()
        assert obs.totals()["t.host_c"] == 3
    (host,) = [r for r in recs if r["metric"] == "t.host"]
    assert {k: v for k, v in host.items() if k != "ts"} == {
        k: v for k, v in traced.items() if k != "ts"}
    assert host == {"ts": host["ts"], "metric": "t.host", "kind": "gauge",
                    "value": 7, "labels": {"capacity": 4}}


# ---------------------------------------------------------------------------
# registry / sink behaviour
# ---------------------------------------------------------------------------


def test_counter_totals_accumulate():
    with obs.capture() as recs:
        obs.counter("t.hits", 5, tag="a")
        obs.counter("t.hits", jnp.arange(3))  # vector counter: summed
        obs.flush()
        assert obs.totals()["t.hits"] == 5 + (0 + 1 + 2)
        assert len([r for r in recs if r["metric"] == "t.hits"]) == 2


def test_histogram_summary_fields():
    with obs.capture() as recs:
        obs.histogram("t.dist", jnp.asarray([1.0, 2.0, 3.0, 4.0]))
        obs.flush()
        (r,) = [x for x in recs if x["metric"] == "t.dist"]
        assert r["kind"] == "histogram"
        assert r["count"] == 4
        assert r["min"] == 1.0 and r["max"] == 4.0 and r["sum"] == 10.0
        assert "p50" in r and "p90" in r


def test_traced_labels_forwarded_through_callback():
    with obs.capture() as recs:
        jax.jit(
            lambda x: (obs.gauge("t.lbl", x, device=jnp.int32(3)), x)[1]
        )(jnp.int32(7))
        obs.flush()
        (r,) = [x for x in recs if x["metric"] == "t.lbl"]
        assert r["value"] == 7
        assert r["labels"]["device"] == 3


def test_step_label_stamped():
    with obs.capture() as recs:
        obs.set_step(42)
        obs.gauge("t.stepped", 1.0)
        obs.flush()
        (r,) = [x for x in recs if x["metric"] == "t.stepped"]
        assert r["step"] == 42
    obs.set_step(None)


def test_enable_argument_validation():
    with pytest.raises(ValueError):
        obs.enable()
    with pytest.raises(ValueError):
        from repro.obs.sink import ListSink

        obs.enable(metrics_dir="/tmp/x", sink=ListSink())
    assert not obs.enabled()


def test_capture_nests_without_cross_talk():
    with obs.capture() as outer:
        obs.gauge("t.outer", 1)
        with obs.capture() as inner:
            obs.gauge("t.inner", 2)
            obs.flush()
        obs.gauge("t.outer", 3)
        obs.flush()
        assert [r["metric"] for r in inner] == ["t.inner"]
        outer_names = [r["metric"] for r in outer]
        assert outer_names.count("t.outer") == 2
        assert "t.inner" not in outer_names
    assert not obs.enabled()


def test_jsonl_sink_roundtrip(tmp_path):
    import json

    obs.enable(metrics_dir=str(tmp_path))
    try:
        obs.gauge("t.file", jnp.float32(1.5), tag="x")
        obs.log_event("t.event", detail="hello")
        obs.flush()
    finally:
        obs.disable()
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    recs = [json.loads(line) for line in lines]
    metrics = {r["metric"] for r in recs}
    assert {"t.file", "t.event"} <= metrics


def test_log_event_safe_while_disabled(caplog):
    assert not obs.enabled()
    obs.log_event("t.disabled_event", reason="nothing should raise")


# ---------------------------------------------------------------------------
# kernel dispatch validation (satellite: no silent backend fall-through)
# ---------------------------------------------------------------------------


def test_invalid_backend_raises():
    from repro.kernels.ops import stable_merge, stable_sort

    a = jnp.asarray([1, 3], jnp.int32)
    b = jnp.asarray([2, 4], jnp.int32)
    with pytest.raises(ValueError, match="backend must be one of"):
        stable_merge(a, b, backend="palas")  # the typo must fail loudly
    with pytest.raises(ValueError, match="backend must be one of"):
        stable_sort(a, backend="PALLAS")


def test_invalid_backend_env_raises(monkeypatch):
    from repro.kernels.ops import BACKEND_ENV_VAR, default_backend

    monkeypatch.setenv(BACKEND_ENV_VAR, "cuda")
    with pytest.raises(ValueError, match=BACKEND_ENV_VAR):
        default_backend()


def test_dispatch_counter_and_one_time_log():
    from repro.kernels import ops

    a = jnp.asarray([1, 3], jnp.int32)
    b = jnp.asarray([2, 4], jnp.int32)
    ops._LOGGED_CHOICES.discard(("stable_merge", "xla", "arg"))
    with obs.capture() as recs:
        stable = np.asarray(ops.stable_merge(a, b, backend="xla"))
        np.testing.assert_array_equal(stable, [1, 2, 3, 4])
        ops.stable_merge(a, b, backend="xla")  # cached: no re-trace
        obs.flush()
        chosen = [
            r for r in recs if r["metric"] == "kernels.backend_selected"
        ]
        assert len(chosen) == 1  # announced once per distinct choice
        assert chosen[0]["labels"]["backend"] == "xla"
        assert chosen[0]["labels"]["source"] == "arg"
        assert obs.totals().get("kernels.dispatch_calls", 0) >= 1


# ---------------------------------------------------------------------------
# 8-device acceptance run (subprocess)
# ---------------------------------------------------------------------------


def _run(script: str, *args: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / script), *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return proc.stdout


@pytest.mark.slow  # subprocess run on 8 fake devices
def test_obs_eight_devices():
    out = _run("_obs_check.py")
    assert "ALL OK" in out
    assert "Prop-1 iteration counters within bound: OK" in out
    assert "HLO reconciliation" in out
