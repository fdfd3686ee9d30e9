"""Share of the detail cut's device time under ``optimizer_update``."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("optimizer_update",))
