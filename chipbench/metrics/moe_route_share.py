"""Share of the detail cut's device time in routing: ``moe_router`` (scores,
top-k, weights) and ``moe_dispatch`` (sort, gather, weighted sum back)."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("moe_router", "moe_dispatch"))
