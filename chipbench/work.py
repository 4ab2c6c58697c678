"""Necessary work of a network step, and the chip's peaks.

The counts are what any implementation has to do, not what the simulator
happens to do, so a metric built on them does not go stale when a later
change replaces a kernel:

* a synaptic event (one spike reaching one target) reads its weight and
  target index and reads and writes the target's accumulator: 16 bytes
  and one add;
* every neuron's state and every post-synaptic conductance is read and
  written once per step;
* each neuron model costs the operations its update takes, counted by
  hand from its equations (``MODEL_COST``; a transcendental counts as one).

At the benchmark's network sizes the bytes bound the least time: the operations
would take well under a hundredth of it at the chip's peak.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

EVENT_BYTES = 16          # weight 4 + index 4 + accumulator read 4, write 4
EVENT_FLOPS = 1
WORD = 4                  # float32 state
PSM_FLOPS = 5             # decay, add, reversal term, product, accumulate

# neuron model -> state variables and operations per neuron-step:
#   izhikevich: 9 per half-step on V (x2), 5 for U, clamp, threshold,
#               reset (2 selects and an add)                      = 28
MODEL_COST = {
    "izhikevich": {"state_vars": 2, "flops": 28},
}


class UnknownDevice(KeyError):
    """The device kind has no entry in peaks.json."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def necessary(layout: dict, spikes: dict, net_steps: int) -> dict:
    """Bytes and operations that ``net_steps`` network-steps need, given
    the spikes each population fired in them (summed over networks)."""
    events = sum(int(spikes[s["pre"]]) * s["fanout"]
                 for s in layout["synapses"])
    state_words = sum(p["n"] * MODEL_COST[p["model"]]["state_vars"]
                      for p in layout["populations"].values())
    psm_words = sum(s["psm_vars"] * s["n_post"] for s in layout["synapses"])
    step_flops = (sum(p["n"] * MODEL_COST[p["model"]]["flops"]
                      for p in layout["populations"].values())
                  + PSM_FLOPS * psm_words)
    return {
        "events": events,
        "prop_bytes": events * EVENT_BYTES,
        "state_bytes": net_steps * (state_words + psm_words) * 2 * WORD,
        "flops": net_steps * step_flops + events * EVENT_FLOPS,
    }


def least_seconds(work: dict, peak: dict, prop_only: bool = False) -> float:
    """The least time the chip could take for ``work``: the larger of its
    bytes over peak bandwidth and its operations over peak rate."""
    if prop_only:
        return work["prop_bytes"] / peak["hbm_bytes_per_s"]
    nbytes = work["prop_bytes"] + work["state_bytes"]
    return max(nbytes / peak["hbm_bytes_per_s"],
               work["flops"] / peak["flops_per_s"])
