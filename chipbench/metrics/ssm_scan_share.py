"""Share of the detail cut's device time in the selective scan alone,
forward and backward: the scope ``ssm_scan``."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("ssm_scan",))
