#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control,
seed after seed in one process, to set each limit between them.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \\
        --control int8

Each seed is a whole run of the cell at its own size and load (as
``bench/run.py --trace 0`` makes it), whose comparison also judges the
control, put in the program's place, by the same numbers and limits:
for a served model the reference in the precision below the one served
(``int8``), its tokens drawn by the engine's own rule; for a merge a
reference that breaks a stated guarantee (``unstable``,
``approximate_cuts``).  One JSON line per seed, with the program's
``correct`` and the control's, then the lower reading of each number
(the largest the program gave) and the upper (the smallest the control
gave).  The benchmark's own runs never run the control.

``--fault altered_token`` plants a fault in the program instead (each
sampled token is replaced by the next id), so the readings are the
fault's: the upper reading of a number the control does not separate.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.run import NO_CHIP, Tracer  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", default="")
    p.add_argument("--fault", default="", choices=("", "altered_token"))
    args = p.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench: control readings are taken on a TPU", file=sys.stderr)
        return NO_CHIP
    if args.fault == "altered_token":
        from repro.serving.engine import DecodeEngine

        sample = DecodeEngine._sample
        DecodeEngine._sample = (
            lambda self, keys, logits:
            (sample(self, keys, logits) + 1) % self.cfg.vocab)
    driver = cell.driver()
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        result = driver.run(cell, seed, args.seconds, Tracer(False),
                            control=args.control)
        checks = {c["name"]: c["value"] for c in result["checks"]}
        line = {"seed": seed, "fault": args.fault,
                "correct": result["correct"], "checks": checks}
        for name, value in checks.items():
            program.setdefault(name, []).append(value)
        if "control" in result:
            ctl = result["control"]
            line["control"] = {
                "name": ctl["control"], "correct": ctl["correct"],
                "checks": {c["name"]: {"value": c["value"],
                                       "limit": c["limit"], "ok": harness.ok(c)}
                           for c in ctl["checks"]}}
            for c in ctl["checks"]:
                control.setdefault(c["name"], []).append(c["value"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "lower": {k: max(v) for k, v in program.items()},
        "upper": {k: min(v) for k, v in control.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
