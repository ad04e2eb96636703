"""The program's own scopes and host spans in a profiler trace
(``bench/scopes.py``) and the readers of the engine's phases: on events
laid out by hand, and on small traces recorded on the chip by ``--trace
1`` runs, against values worked out from those files."""

import pathlib

import pytest

from bench import harness, scopes
from bench import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _read(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _trace():
    """One engine step of 100 ns: a scoped ``while`` holding a body op,
    and the engine's phases on the host."""
    E = tr.Event
    ops = [E("while.1", 10, 50, DEV), E("fusion.2", 20, 30, DEV),
           E("copy.3", 30, 40, DEV), E("fusion.4", 60, 80, DEV)]
    spans = [E("bench.engine_step", 0, 100, "python")]
    host = [E("serve.step", 2, 98, "python"),
            E("serve.admit", 2, 5, "python"),
            E("serve.build_inputs", 5, 8, "python"),
            E("serve.dispatch", 8, 12, "python"),
            E("serve.fetch_tokens", 12, 85, "python"),
            E("serve.retire", 85, 98, "python")]
    loop = "jit(f)/repro.decoder_layers/while"
    names = {"while.1": loop,
             "fusion.2": loop + "/body/repro.ffn/dot",
             "copy.3": loop + "/body/dynamic_slice",
             "fusion.4": "jit(f)/repro.topk_draw/exp"}
    return (tr.Trace(ops, [], spans, host, devices=1),
            {(DEV, k): v for k, v in names.items()})


def test_scoped_time_is_exclusive():
    t, names = _trace()
    # the while's own 20 ns, its body's 10 + 10
    assert scopes.scoped_ns(t, names, "repro.decoder_layers") == 40
    assert scopes.scoped_ns(t, names, "repro.decoder_layers",
                            exclude=scopes.LAYER_SCOPES) == 30
    assert scopes.scoped_ns(t, names, "repro.ffn") == 10
    assert scopes.scoped_ns(t, names, "repro.topk_draw") == 20
    # a scope is a whole path component, not a prefix of one
    assert scopes.scoped_ns(t, names, "repro.topk") == 0


def test_idle_inside_the_engine_phases():
    t, _ = _trace()
    # busy 10-50 and 60-80: the step is dispatched at 10, the fetch
    # waits from 12 to 85
    assert scopes.idle_in(t, scopes.PREP_SPANS) == 3 + 3 + 2
    assert scopes.idle_in(t, scopes.RETIRE_SPANS) == (73 - 38 - 20) + 13
    assert scopes.idle_in(t, ["serve.step"]) == tr.idle_in_spans(
        t, "bench.engine_step") - 2 - 2
    assert _read("engine_prep_ms.serve")(t, {}, None) == pytest.approx(8e-6)
    assert _read("engine_retire_ms.serve")(t, {}, None) == pytest.approx(
        28e-6)


def test_idle_in_agrees_with_busy():
    """``idle_in`` from one cover equals ``busy`` per event."""
    t, _ = _trace()
    for h in t.host:
        want = (h.end - h.start) - tr.busy(t, h.start, h.end)
        assert scopes.idle_in(t, [h.name]) == want, h.name


# A --trace 1 run of granite-3-2b.chat-greedy on one v5e, recorded at the
# parent of the change that scoped the program: op_name paths without any
# repro.* scope, and no serve.* spans.
GREEDY = DATA / "greedy_small.xplane.pb"


@pytest.fixture(scope="module")
def greedy():
    return tr.load(str(GREEDY)), scopes.op_scopes(str(GREEDY))


def test_op_scopes_read_from_the_wire_format(greedy):
    t, names = greedy
    assert len({e.name for e in t.ops}) == 69
    assert len(names) == 54
    assert sum((e.where, e.name) in names for e in t.ops) == 4374
    copy57 = [v for (plane, op), v in names.items()
              if op.startswith("%copy.57 = ")]
    assert copy57 == ["jit(ragged_step)/while/body/dynamic_slice"]
    fusion181 = [v for (plane, op), v in names.items()
                 if op.startswith("%fusion.181 = ")]
    assert fusion181 == [
        "jit(ragged_step)/while/body/closed_call/bsd,df->bsf/dot_general"]


def test_existing_readers_unchanged_on_the_greedy_trace(greedy):
    """Every reader the benchmark had reads what it read before."""
    t, _ = greedy
    facts = {"flops": 1e12, "program": "jit_ragged_step", "calls": 3,
             "min_bytes": 1e9}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    want = {
        "sampler_ms.serve": 0.01686633333333333,
        "model_step_ms.serve": 18.63618,
        "serve_step_mfu": 8.046586675932074,
        "engine_gap_ms.serve": 2.4133106666666664,
        "device_idle_share.serve": 11.514389092773547,
        "tile_kernel_ms.merge": None,
        "merge_prep_ms.merge": None,
        "merge_hbm_roofline": 2.183926142591491,
        "device_idle_share.merge": None,
    }
    got = {name: _read(name)(t, facts, peak) for name in want}
    assert got == want


def test_engine_readers_find_nothing_without_the_spans(greedy):
    """The program before the engine's spans: nothing to read, no error."""
    t, names = greedy
    assert _read("engine_prep_ms.serve")(t, {}, None) is None
    assert _read("engine_retire_ms.serve")(t, {}, None) is None
    assert scopes.scoped_ns(t, names, "repro.decoder_layers") == 0


# --trace 1 runs on one v5e of the program with its scopes and spans:
# three chat-topk steps (a 0.25 s window, too short for a request to
# finish in it), and one merge_window call over 8 runs of 2^14 keys with
# a payload.  What nothing here reads was dropped to keep them small: the
# /host:metadata plane, the device's "Async XLA Ops" line and the
# "source" / "source_stack" stats of its op metadata.  The numbers below
# were read from the files with a plain sweep over their raw events,
# apart from bench/scopes.py.
TOPK = DATA / "topk_small.xplane.pb"
MERGE = DATA / "merge_small.xplane.pb"
MS = 1e-6


@pytest.fixture(scope="module")
def recorded():
    return {name: (tr.load(str(path)), scopes.op_scopes(str(path)))
            for name, path in (("topk", TOPK), ("merge", MERGE))}


def test_recorded_traces_carry_their_scopes(recorded):
    assert TOPK.stat().st_size < 1_000_000
    assert MERGE.stat().st_size < 1_000_000
    for name, n_names in (("topk", 390), ("merge", 88)):
        t, names = recorded[name]
        assert len(names) == n_names
        ops = scopes.scoped_ops(t, names)
        assert len(ops) == len(tr.in_window(t, t.ops))
        assert all(path for _, _, path in ops), name
    t, names = recorded["merge"]
    kernels = {v for (plane, op), v in names.items()
               if op.startswith("%merge_kway_pallas.")}
    assert kernels and all(
        scopes.in_scope(v, "repro.tile_merge") for v in kernels)


@pytest.mark.parametrize("name,scope,exclude,want_ns", [
    ("topk", "repro.topk_block_sort", (), 253_400_549),
    ("topk", "repro.topk_tournament", (), 23_861_437),
    ("topk", "repro.topk_draw", (), 5_638),
    ("topk", "repro.attention", (), 7_993_470),
    ("topk", "repro.ffn", (), 16_324_701),
    ("topk", "repro.decoder_layers", scopes.LAYER_SCOPES, 30_723_394),
    ("merge", "repro.corank", (), 63_916_138),
    ("merge", "repro.slab_relayout", (), 2_780),
    ("merge", "repro.tile_merge", (), 1_085_043),
])
def test_scoped_time_on_recorded_traces(recorded, name, scope, exclude,
                                        want_ns):
    t, names = recorded[name]
    assert scopes.scoped_ns(t, names, scope, exclude) == want_ns


@pytest.mark.parametrize("name,program,parts", [
    ("topk", "jit_sample_topk_batched", scopes.TOPK_SCOPES),
    ("topk", "jit_ragged_step", ("repro.decoder_layers",)),
    ("merge", "jit_merge_window", scopes.MERGE_SCOPES),
])
def test_scoped_parts_add_up_to_their_program(recorded, name, program,
                                              parts):
    t, names = recorded[name]
    total = sum(scopes.scoped_ns(t, names, s) for s in parts)
    assert total == pytest.approx(tr.program_ns(t, program), rel=0.02)


def test_readers_on_recorded_traces(recorded):
    t, _ = recorded["topk"]
    gap = _read("engine_gap_ms.serve")(t, {}, None)
    prep = _read("engine_prep_ms.serve")(t, {}, None)
    retire = _read("engine_retire_ms.serve")(t, {}, None)
    assert gap == pytest.approx(8_386_519 / 3 * MS)
    assert prep == pytest.approx((16_160 + 173_580 + 0) / 3 * MS)
    assert retire == pytest.approx((7_844_299 + 142_670) / 3 * MS)
    assert 0.8 * gap <= prep + retire <= gap
    t, names = recorded["merge"]
    # the kernel keeps its name inside its scope: the reader finds it
    assert _read("tile_kernel_ms.merge")(t, {}, None) == pytest.approx(
        1_085_043 * MS)
    assert _read("merge_prep_ms.merge")(
        t, {"program": "jit_merge_window"}, None) == pytest.approx(
        (65_004_241 - 1_085_043) * MS)
    assert _read("engine_prep_ms.serve")(t, {}, None) is None
