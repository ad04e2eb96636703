"""What every driver and reader shares: the manifest, a cell's files, the
peaks table, the device record and the statistics of a window.

Everything a cell needs is found by name under ``bench/``:

* ``configs/<config>.json`` — the configuration as it is run, and
  ``configs/<config>.py`` — its plain reference (and whatever makes its
  inputs or weights from the seed);
* ``traffic/<traffic>.json`` — the mix's parameters; its ``driver`` key
  names ``drivers/<driver>.py``, the one generator for that kind of cell;
* ``limits/<cell>.json`` — the limit of each number compared for
  ``correct``, with the readings it was set from;
* ``metrics/<metric>.py`` — one reader per per-layer metric.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
from typing import Any

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(path: pathlib.Path):
    """Import a file by path (cell files carry names with ``-`` and ``.``)."""
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with everything its files hold."""

    name: str
    chips: int
    config_name: str
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    limits: dict  # limits/<cell>.json: {number: {"limit": x, ...}}
    end_to_end: list  # manifest entries this cell reports with --trace 0
    per_layer: list  # ... and with --trace 1

    def reference(self):
        """``configs/<config>.py``: the plain reference and the seeded
        weights or inputs."""
        return load_module(BENCH / "configs" / f"{self.config_name}.py")

    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest or load_manifest()
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=w["chips"],
        config_name=w["config"],
        config=load_json(ROOT / cfg["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name)],
    )


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json"
        )
    return table[device_kind]


def seeds(seed: int, n: int) -> list[int]:
    """``n`` 32-bit words from ``seed``, any non-negative whole number.

    ``jax.random.key`` keeps only the low 32 bits of a larger seed, so
    every key the benchmark makes comes from one of these words.
    """
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]


def device_record(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip since the process started."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def percentile(values, q: float) -> float:
    """``q``-th percentile (linear interpolation) of a non-empty sample."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, np.float64), q))


def finite(x: float) -> float:
    if not math.isfinite(x):
        raise ValueError(f"metric is not finite: {x}")
    return x


def ok(check: dict) -> bool:
    """A compared number within its limit: at most it, or with
    ``at_least``, at least it."""
    if check.get("at_least"):
        return check["value"] >= check["limit"]
    return check["value"] <= check["limit"]


@contextlib.contextmanager
def frozen_setup():
    """For the window, what set-up left alive (JAX's and the program's
    objects) is frozen out of the garbage collector's reach, as in a
    long-running server: a full collection over it takes about 70 ms
    and otherwise falls at random steps of the window."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()
