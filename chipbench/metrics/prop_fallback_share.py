"""Percent of the device time under ``prop/<group>`` that ran under
``prop/<group>/fallback``: the dense branch that event propagation takes
when more rows fire than its capacity holds."""

from chipbench import scopes


def read(ctx):
    return scopes.read(ctx, "prop_fallback_share")
