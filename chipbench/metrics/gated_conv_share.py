"""Share of the detail cut's device time under ``gated_conv``."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("gated_conv",))
