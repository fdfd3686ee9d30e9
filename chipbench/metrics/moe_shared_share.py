"""Share of the detail cut's device time under ``moe_shared``: the expert
that every token takes beside the routed ones."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("moe_shared",))
