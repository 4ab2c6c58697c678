"""Percent of device busy time under the step's ``prop/<group>`` scopes
(each synapse group's compaction, event scatter, dense fallback and dense
product), from the traced window reduced by named scope
(``chipbench.scopes``)."""

from chipbench import scopes


def read(ctx):
    return scopes.read(ctx, "prop_scope_share")
