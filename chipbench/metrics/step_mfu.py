"""The whole step's share of the chip's peak, in percent: the least time
the window's necessary work needs (synaptic events, plus every neuron and
conductance state read and written once per step, and their operations at
the peak rate; the bytes bound it here) over the window's wall time."""

from chipbench import work


def read(ctx):
    if ctx["trace"] is None or ctx["peak"] is None:
        return None
    return 100.0 * work.least_seconds(ctx["work"], ctx["peak"]) \
        / ctx["window"]["seconds"]
