"""Share of the detail cut's device time in the routed layers' grouped
products: the scope ``moe_experts`` (the activation between them) and the
compiler's own ``ragged-dot`` kernels, which it names itself."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("moe_experts",), kernels=("ragged-dot",))
