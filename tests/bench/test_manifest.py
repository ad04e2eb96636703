"""``BENCHMARK.json`` against the rules it is held to, and every cell's
files where the harness looks for them."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = ROOT / "bench"


@pytest.fixture(scope="module")
def manifest():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_plain_and_unique(manifest, section):
    names = [e["name"] for e in manifest[section]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_units_and_directions(manifest, section):
    for m in manifest[section]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_bounds(manifest):
    names = [m["name"] for m in manifest["end_to_end"]]
    assert "setup_s" in names
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        cfg = configs[w["config"]]
        assert (ROOT / cfg["file"]).is_file()
        assert (BENCH / "configs" / f"{w['config']}.py").is_file()
        traffic = BENCH / "traffic" / f"{w['traffic']}.json"
        with open(traffic) as f:
            driver = json.load(f)["driver"]
        assert (BENCH / "drivers" / f"{driver}.py").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for m in manifest["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_moves_reported_by_every_listed_cell(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert _reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_every_cell_reports_enough(manifest):
    for w in manifest["workloads"]:
        e2e = [m for m in manifest["end_to_end"] if _reports(m, w["name"])]
        assert "setup_s" in [m["name"] for m in e2e]
        assert len(e2e) >= 2
        assert any(_reports(m, w["name"]) for m in manifest["per_layer"])


def test_at_most_half_the_cells_on_four_chips(manifest):
    chips = [w["chips"] for w in manifest["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(1, len(chips) // 2)


def test_configs_used_and_files_distinct(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])


def test_a_full_check_fits_its_time(manifest):
    # 24 cells, each 2 + 14 runs of run_seconds + 60 s, 2 x 90 s of
    # compile per cell and 1200 s spare, within 43200 s
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (manifest["run_seconds"] + 60) + cells * 180 + 1200
    assert total <= 43200
