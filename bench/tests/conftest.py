"""CPU rehearsal of the benchmark: tiny sizes, ``JAX_PLATFORMS=cpu``.

Run from the repository root: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests``. The harness itself refuses the CPU; these tests call its
parts as functions.
"""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


@pytest.fixture(scope="session")
def manifest():
    return harness.load_json(ROOT / "BENCHMARK.json")


def tiny_cell(manifest, name, deployment=None, traffic=None):
    """The cell ``name`` with its deployment and mix shrunk for the CPU."""
    cell = copy.deepcopy(harness.load_cell(manifest, name))
    for key, value in (deployment or {}).items():
        section, field = key.split(".")
        cell.config["deployment"][section][field] = value
    cell.traffic.update(traffic or {})
    return cell


@pytest.fixture
def metro_cell(manifest):
    return tiny_cell(manifest, "metro-tick",
                     {"edges.count": 30, "users.per_tick": 3000},
                     {"populations": 3, "checked_ticks": 2})


@pytest.fixture
def sweep_cell(manifest):
    return tiny_cell(manifest, "paper-sweep-4chip",
                     {"catalog.services": 12, "catalog.max_impls": 4},
                     {"users": [30, 60, 90], "trials": 3})
