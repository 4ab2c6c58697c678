"""Whole runs of each cell on the CPU, past the harness's look for a chip,
with the timed path broken underneath: every fault must make ``correct``
false."""

import dataclasses
import time

import pytest

from chipbench import harness
from chipbench.tests.conftest import WORKLOADS, small_cell
from repro.core.snn import simulator as S
from repro.core.snn import spec as SP

SEED = 2 ** 31 + 29


def _run(workload):
    return harness.run_cell(small_cell(workload), SEED, 0.5, False,
                            time.perf_counter(), require_chip=False)


def state_unchanged(monkeypatch):
    """Each step returns the state it was given (its spikes still out)."""
    step = S.Simulator.step

    def stuck(self, state, *a, **k):
        return state, step(self, state, *a, **k)[1]
    monkeypatch.setattr(S.Simulator, "step", stuck)


def count_altered(monkeypatch):
    """One neuron's spike count comes back one higher than it fired."""
    run = SP.CompiledModel.run

    def bumped(self, *a, **k):
        res = run(self, *a, **k)
        pop = sorted(res.spike_counts)[0]
        counts = dict(res.spike_counts)
        counts[pop] = counts[pop].at[0].add(1)
        return dataclasses.replace(res, spike_counts=counts)
    monkeypatch.setattr(SP.CompiledModel, "run", bumped)


def spike_altered(monkeypatch):
    """One neuron spikes once where its membrane says it should not: the
    recorded raster of the first population gains a spike."""
    run = SP.CompiledModel.run

    def flipped(self, *a, **k):
        res = run(self, *a, **k)
        data = dict(res.recordings.data)
        key = sorted(k for k in data if k.endswith("_spikes"))[0]
        data[key] = data[key].at[0, 0].set(~data[key][0, 0])
        rec = dataclasses.replace(res.recordings, data=data)
        return dataclasses.replace(res, recordings=rec)
    monkeypatch.setattr(SP.CompiledModel, "run", flipped)


# by the traffic's mode: a run-mode cell has no batch and no exchange
# between chips, so a step that returns its state unchanged, and an answer
# altered where it is produced
FAULTS = {
    "run": [state_unchanged, count_altered, spike_altered],
}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in WORKLOADS
    for f in FAULTS[harness.load_cell(w).traffic["mode"]]],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_run_incorrect(workload, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(workload)
    assert res["correct"] is False, res["checks"]


def test_sound_runs_are_correct(workload):
    res = _run(workload)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"net_steps_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
