"""Share of the detail cut's device time in the gated memory units: the
scope ``gmu``."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("gmu",))
