"""Shared helpers: cells of BENCHMARK.json cut to sizes a CPU test holds.

Run with ``python -m pytest chipbench/tests`` from the root of the
checkout; JAX runs on the CPU here."""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

# a cell's configuration cut to a size at which it runs in seconds on a
# CPU: small/<workload>.json holds the model_config keys it changes
SMALL = Path(__file__).resolve().parent / "small"
WORKLOADS = sorted(p.name[:-len(".json")] for p in SMALL.glob("*.json"))


def small_cell(workload: str) -> harness.Cell:
    cell = harness.load_cell(workload)
    cell.config["model_config"].update(
        json.loads((SMALL / f"{workload}.json").read_text()))
    return cell


@pytest.fixture(params=WORKLOADS)
def workload(request):
    return request.param
