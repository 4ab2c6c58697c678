"""Percent of device busy time spent in synaptic propagation: the ops the
trace reduction classes as propagation (XLA scatters, dense products of
the spike vector, and the Pallas ELL kernels ``ell_spmv*``)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * t["prop_s"] / t["busy_s"]
