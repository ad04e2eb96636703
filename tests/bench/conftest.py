"""Shared set-up of the benchmark's own tests: the repository root on the
path (for ``import bench``), and cells at the size of a CPU test."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def tiny_cell():
    from bench import harness
    from benchtiny import tiny

    return lambda name: tiny(harness.load_cell(name))
