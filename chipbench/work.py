"""Necessary work of a network step, and the chip's peaks.

The counts are what any implementation has to do, not what the simulator
happens to do, so a metric built on them does not go stale when a later
change replaces a kernel:

* a synaptic event (one spike reaching one target) reads its weight and
  target index and reads and writes the target's accumulator: 16 bytes
  and one add, unless the synapse population's entry gives its own
  ``event_bytes`` (a delayed event also reads its delay);
* every neuron's state and every post-synaptic conductance is read and
  written once per step;
* each neuron costs the operations its update takes.

The counts of a network come from its plain reference's ``layout``: each
population gives ``n``, ``state_vars`` and ``flops`` (its model's
operations per neuron-step, counted by hand from its equations, a
transcendental as one), each synapse population ``pre``, ``fanout``,
``psm_vars``, ``n_post`` and optionally ``event_bytes``.  So a new neuron
model brings its counts with its reference, and nothing here names one.

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
    the spikes each population fired in them (summed over networks).
    Raises ValueError naming a population whose count the layout leaves
    out."""
    state_words = step_flops = 0
    for name, p in layout["populations"].items():
        for key in ("state_vars", "flops"):
            if key not in p:
                raise ValueError(
                    f"the layout's population {name!r} gives no {key!r}")
        state_words += p["n"] * p["state_vars"]
        step_flops += p["n"] * p["flops"]
    events = prop_bytes = 0
    for s in layout["synapses"]:
        n = int(spikes[s["pre"]]) * s["fanout"]
        events += n
        prop_bytes += n * s.get("event_bytes", EVENT_BYTES)
    psm_words = sum(s["psm_vars"] * s["n_post"] for s in layout["synapses"])
    step_flops += PSM_FLOPS * psm_words
    return {
        "events": events,
        "prop_bytes": prop_bytes,
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
