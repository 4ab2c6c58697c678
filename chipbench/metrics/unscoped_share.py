"""Percent of device busy time under no named scope: the scan's
bookkeeping and the copies XLA adds."""

from chipbench import scopes


def read(ctx):
    return scopes.read(ctx, "unscoped_share")
