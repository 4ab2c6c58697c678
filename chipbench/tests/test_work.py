"""Necessary-work counters against a hand count, and the peaks table."""

import pytest

from chipbench import work
from chipbench.reference import izh_cortex


def test_izhikevich_counts_by_hand():
    # 8 excitatory + 2 inhibitory neurons, 3 targets each; over 4 steps
    # the excitatory ones fired 5 spikes and the inhibitory ones 2
    layout = izh_cortex.layout({"n_total": 10, "exc_frac": 0.8,
                                "n_conn": 3})
    w = work.necessary(layout, {"exc": 5, "inh": 2}, net_steps=4)
    assert w["events"] == 5 * 3 + 2 * 3
    assert w["prop_bytes"] == 21 * 16
    # V and U of 10 neurons, read and written once per step, float32
    assert w["state_bytes"] == 4 * 10 * 2 * 2 * 4
    assert w["flops"] == 4 * 10 * 28 + 21


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    w = {"prop_bytes": 4e9, "state_bytes": 1e9, "flops": 1e12}
    assert work.least_seconds(w, peak) == pytest.approx(5.0)
    assert work.least_seconds(w, peak, prop_only=True) == pytest.approx(4.0)
    w["flops"] = 1e13
    assert work.least_seconds(w, peak) == pytest.approx(10.0)


def test_peaks_of_the_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v99 imaginary")
