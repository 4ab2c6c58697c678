"""Percent of device busy time under the step's ``neuron/<pop>`` scopes:
the populations' neuron updates."""

from chipbench import scopes


def read(ctx):
    return scopes.read(ctx, "neuron_update_share")
