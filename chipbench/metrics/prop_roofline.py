"""Propagation's share of its roofline, in percent: the least time the
window's synaptic events need (spikes fired x fan-out x 16 bytes, at the
device's peak bandwidth) over the device time of the propagation ops.
The necessary bytes do not depend on what implements propagation."""

from chipbench import work


def read(ctx):
    t = ctx["trace"]
    if t is None or t["prop_s"] <= 0 or ctx["work"]["events"] == 0:
        return None
    return 100.0 * work.least_seconds(ctx["work"], ctx["peak"],
                                      prop_only=True) / t["prop_s"]
