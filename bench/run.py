#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, on the machine that holds the chips: it enables
JAX's persistent compilation cache, builds the cell from its files
(``BENCHMARK.json`` and what ``bench/harness.py`` lists), warms up the
cell's own shapes, measures for ``--seconds`` and checks that what the
window produced is correct.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of a short window), ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared for ``correct`` beside its limit.  The same numbers end
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, trace as tr  # noqa: E402

NO_CHIP = 2


class Tracer:
    """A profiler trace of the window when on; spans and start/stop are
    no-ops when off."""

    def __init__(self, on: bool):
        self.on = on
        self.directory = tempfile.mkdtemp(prefix="bench_trace_") if on else None

    def start(self) -> None:
        if self.on:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device ops and spans only
            jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def close(self) -> None:
        if self.directory:
            shutil.rmtree(self.directory, ignore_errors=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default="",
                   help="copy the profiler trace into this directory")
    return p.parse_args(argv)


def per_layer(cell, trace, facts, peak) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(trace, facts, peak)
        if value is not None:
            out[m["name"]] = {"value": harness.finite(value), "unit": m["unit"]}
    return out


def measure(args, *, cell=None, require_chip: bool = True) -> dict | None:
    """The result of one run, or ``None`` where the chips are missing.
    Tests pass a ``cell`` of their own size and ``require_chip=False``."""
    cell = cell or harness.load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program the cell runs, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None

    tracer = Tracer(bool(args.trace))
    try:
        result = cell.driver().run(cell, args.seed, args.seconds, tracer)
        device = dict(result["device"], count=cell.chips)
        line = {"correct": bool(result["correct"]),
                "attempted": result["attempted"], "failed": result["failed"]}
        if args.trace:
            if args.keep_trace:
                shutil.copytree(tracer.directory, args.keep_trace,
                                dirs_exist_ok=True)
            trace = tr.load(tr.find_xplane(tracer.directory))
            peak = harness.peaks(device["kind"]) if require_chip else None
            line["metrics"] = per_layer(cell, trace, result["facts"], peak)
            device["busy_s"] = tr.busy(trace) * 1e-9
            device["window_s"] = trace.window_s
            line["device"] = device
            line["breakdown"] = tr.breakdown(trace)
        else:
            metrics = dict(result["metrics"],
                           setup_s=result["setup_end"] - T_START)
            line["metrics"] = {
                m["name"]: {"value": harness.finite(metrics[m["name"]]),
                            "unit": m["unit"]}
                for m in cell.end_to_end if m["name"] in metrics}
            line["device"] = device
        line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"],
                                      "ok": harness.ok(c)}
                          for c in result["checks"]}
        return line
    finally:
        tracer.close()


def main(argv=None) -> int:
    args = parse(argv)
    line = measure(args)
    if line is None:
        return NO_CHIP
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']}, "
              f"{'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
