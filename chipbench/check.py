"""The numbers that decide ``correct``: the program's outputs against the
plain reference, driven by the program's own spikes.

The network is chaotic, so two free-running simulations part once a
rounding difference flips one spike.  The reference is therefore
teacher-forced: its synapses carry the spikes the program recorded, and
each step it makes its own spike decision from its own membrane.  A
rounding difference can still flip a decision, but only where the
membrane lies at the threshold; a wrong spike lies far from it.  So:

* ``flip_margin``: over the (step, neuron) pairs where the reference's
  decision differs from the program's recorded spike, the largest
  distance of the reference's membrane from threshold, in mV (the nearer
  of that step and the one before, so a crossing one step early or late
  reads small).  0 when no decision differs.
* ``spike_flips_ppm``: such pairs per million recorded spikes.
* ``count_gap``: spike counts the program returned that differ from the
  reference's decisions by more than that neuron's flips explain.  Exact.
* ``v_gap_mV``: the widest gap of a membrane potential the program
  returned against the reference's (where the program returns its state).
* ``finite_mismatch``: networks whose NaN-guard flag differs.  Exact.
"""

from __future__ import annotations

import numpy as np


class Tally:
    def __init__(self, keys):
        self.keys = tuple(keys)
        self.flips = 0
        self.spikes = 0
        self.margin = 0.0
        self.count_gap = 0
        self.v_gap = 0.0
        self.finite_mismatch = 0
        self._flips_by_neuron = {}

    def spikes_vs(self, decisions: dict, margins: dict, raster: dict):
        """The reference's decisions and margins against the recorded
        raster, each pop -> [steps, n]."""
        for pop, r in raster.items():
            r = np.asarray(r, bool)
            d = np.asarray(decisions[pop], bool)
            m = np.abs(np.asarray(margins[pop], np.float64))
            m = np.where(np.isnan(m), np.inf, m)
            prev = np.concatenate([np.full_like(m[:1], np.inf), m[:-1]])
            flip = d != r
            self.flips += int(flip.sum())
            self.spikes += int(r.sum())
            if flip.any():
                self.margin = max(self.margin, float(
                    np.minimum(m, prev)[flip].max()))
            self._flips_by_neuron[pop] = flip.sum(axis=0)

    def counts_vs(self, counts: dict, decisions: dict) -> None:
        """Call after spikes_vs on the same decisions."""
        for pop, c in counts.items():
            gap = np.abs(np.asarray(c, np.int64)
                         - np.asarray(decisions[pop]).sum(axis=0))
            self.count_gap += int(np.maximum(
                gap - self._flips_by_neuron[pop], 0).sum())

    def v_vs(self, prog_v: dict, ref_v: dict) -> None:
        for pop, v in prog_v.items():
            a = np.asarray(v, np.float64)
            b = np.asarray(ref_v[pop], np.float64)
            d = np.abs(a - b)
            # both overflowed: the finite flags judge it; one side only: inf
            d = np.where(np.isnan(a) & np.isnan(b), 0.0, d)
            d = np.where(np.isnan(d), np.inf, d)
            self.v_gap = max(self.v_gap, float(np.max(d, initial=0.0)))

    def finite_vs(self, prog, ref) -> None:
        self.finite_mismatch += int(np.sum(np.asarray(prog)
                                           != np.asarray(ref)))

    def add(self, other: "Tally") -> None:
        self.flips += other.flips
        self.spikes += other.spikes
        self.margin = max(self.margin, other.margin)
        self.count_gap += other.count_gap
        self.v_gap = max(self.v_gap, other.v_gap)
        self.finite_mismatch += other.finite_mismatch

    def numbers(self) -> dict:
        per = 1e6 / max(self.spikes, 1)
        out = {"flip_margin": self.margin,
               "spike_flips_ppm": self.flips * per,
               "count_gap": self.count_gap,
               "v_gap_mV": self.v_gap,
               "finite_mismatch": self.finite_mismatch}
        return {k: out[k] for k in self.keys}


def over(numbers: dict, limits: dict) -> bool:
    """True when any number lies above its limit."""
    return any(v > limits.get(k, 0) for k, v in numbers.items())
