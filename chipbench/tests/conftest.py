"""Shared helpers: cells of BENCHMARK.json cut to sizes a CPU test holds.

Run with ``python -m pytest chipbench/tests`` from the root of the
checkout; JAX runs on the CPU here."""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

# sizes at which the models run in seconds on a CPU
SMALL = {
    "izh100k.run": {"n_total": 2000, "n_conn": 100},
}


def small_cell(workload: str) -> harness.Cell:
    cell = harness.load_cell(workload)
    cell.config["model_config"].update(SMALL[workload])
    return cell


@pytest.fixture(params=sorted(SMALL))
def workload(request):
    return request.param
