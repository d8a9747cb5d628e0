"""Shared fixtures of the benchmark's own tests: a tiny configuration and
its traffic (``data/``), run on the CPU through the same harness code the
chip runs."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

DATA = HERE / "data"
TRAFFIC = DATA / "traffic"


@pytest.fixture(scope="session")
def tiny_spec():
    spec = json.loads((DATA / "tiny-spec.json").read_text())
    full = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in full["end_to_end"]]
    spec["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in full["per_layer"]]
    return spec


@pytest.fixture(scope="session")
def tiny_cells(tiny_spec):
    """One set-up per tiny cell, shared by the tests that only read."""
    cells = {}

    def get(name):
        if name not in cells:
            cells[name] = harness.Cell.from_spec(name, tiny_spec, TRAFFIC,
                                                 log=lambda m: None)
            cells[name].warm(1, level_stats=False)
        return cells[name]
    return get
