"""The reduction from a profiler trace to per-layer numbers: on events
laid out by hand, and on a small trace recorded on the chip by a
``--trace 1`` run, against values worked out from that file."""

import json
import pathlib

import pytest

from bench import harness
from bench import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def _trace():
    """Two engine steps of 100 ns; device ops overlap in the first."""
    E = tr.Event
    ops = [E("fusion.1", 10, 40, DEV), E("fusion.2", 30, 60, DEV),
           E("merge_tile_kernel", 120, 150, DEV), E("copy.3", 170, 180, DEV)]
    modules = [E("jit_ragged_step(7)", 10, 60, DEV),
               E("jit_sample_greedy(9)", 120, 150, DEV),
               E("jit__sample_keys(8)", 170, 180, DEV)]
    spans = [E("bench.engine_step", 0, 100, "python"),
             E("bench.engine_step", 100, 200, "python")]
    host = [E("PjitFunction(ragged_step)", 0, 12, "python"),
            E("np.asarray", 60, 100, "python")]
    return tr.Trace(ops, modules, spans, host, devices=1)


def test_union_and_busy():
    t = _trace()
    assert tr.union([(10, 40), (30, 60), (70, 80)]) == [(10, 60), (70, 80)]
    assert t.window == (0, 200)
    assert tr.busy(t) == 50 + 30 + 10
    assert tr.busy(t, 0, 100) == 50


def test_program_and_op_times():
    t = _trace()
    assert tr.program_ns(t, "jit_ragged_step") == 50
    assert tr.program_ns(t, "jit_sample_greedy", "jit__sample_keys") == 40
    assert tr.ops_ns(t, "merge_tile_kernel") == 30


def test_idle_inside_spans_and_gaps():
    t = _trace()
    assert tr.idle_in_spans(t, "bench.engine_step") == (100 - 50) + (100 - 40)
    gaps = tr.breakdown(t)["idle_gaps"]
    # longest first: 60..120 while the host converted the sampled tokens
    assert gaps[0][0] == "np.asarray"
    assert [g[1] for g in gaps] == pytest.approx([60e-9, 20e-9, 20e-9, 10e-9])


def test_readers_on_hand_made_events():
    t = _trace()
    read = lambda name: harness.load_module(
        ROOT / "bench" / "metrics" / f"{name}.py").read
    assert read("model_step_ms.serve")(t, {}, None) == pytest.approx(25e-6)
    assert read("sampler_ms.serve")(t, {}, None) == pytest.approx(20e-6)
    assert read("engine_gap_ms.serve")(t, {}, None) == pytest.approx(55e-6)
    assert read("device_idle_share.serve")(t, {}, None) == pytest.approx(55.0)
    peak = {"bf16_flops_per_s": 1e12}
    assert read("serve_step_mfu")(t, {"flops": 1e3}, peak) == pytest.approx(
        100 * 1e3 / 200e-9 / 1e12)
    # no merge spans in a serving trace: merge readers find nothing
    assert read("tile_kernel_ms.merge")(t, {}, None) is None


# A --trace 1 run of granite-3-2b.chat-greedy on one v5e with a 0.06 s
# window: three engine steps.  The numbers below were read from the file
# with a plain sweep over its raw events, apart from bench/trace.py.
SMALL = DATA / "greedy_small.xplane.pb"
WINDOW_NS = 63_084_415  # first bench.engine_step start to last end
BUSY_NS = 55_820_630  # union of the XLA Ops intervals in the window
RAGGED_NS = 55_908_540  # three jit_ragged_step executions
SAMPLER_NS = 42_128 + 8_471  # jit_sample_greedy + jit__sample_keys
IDLE_IN_SPANS_NS = 7_239_932


@pytest.fixture(scope="module")
def small():
    return tr.load(str(SMALL))


def test_recorded_trace_structure(small):
    assert SMALL.stat().st_size < 1_000_000
    assert small.devices == 1
    assert len(tr.spans(small, "bench.engine_step")) == 3
    assert small.window[1] - small.window[0] == WINDOW_NS


def test_recorded_trace_busy_and_programs(small):
    assert tr.busy(small) == BUSY_NS
    assert tr.program_ns(small, "jit_ragged_step") == RAGGED_NS
    assert tr.program_ns(small, "jit_sample_greedy",
                         "jit__sample_keys") == SAMPLER_NS
    assert tr.idle_in_spans(small, "bench.engine_step") == IDLE_IN_SPANS_NS


def test_recorded_trace_readers(small):
    read = lambda name: harness.load_module(
        ROOT / "bench" / "metrics" / f"{name}.py").read
    assert read("model_step_ms.serve")(small, {}, None) == pytest.approx(
        RAGGED_NS / 3 * 1e-6)
    assert read("sampler_ms.serve")(small, {}, None) == pytest.approx(
        SAMPLER_NS / 3 * 1e-6)
    assert read("engine_gap_ms.serve")(small, {}, None) == pytest.approx(
        IDLE_IN_SPANS_NS / 3 * 1e-6)
    assert read("device_idle_share.serve")(small, {}, None) == pytest.approx(
        100 * (1 - BUSY_NS / WINDOW_NS))
    bd = tr.breakdown(small)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    # exclusive times never exceed the busy time
    assert sum(s for _, s in bd["device_ops"]) <= BUSY_NS * 1e-9
