"""``fleet_step_mfu`` with the routed layers' experts counted at the load
the window's own counters show (``moe_held_load``), not at the even router's:
the step's share of the chip's bf16 peak for the products the run really
made. A build's count is linear in one forward pass's, so the stated share
is scaled by the two counts of a window. The folds' predictions (under 2 %
of a build's passes) route outside the counters and are counted at the
training steps' load."""
from chipbench import reference
from chipbench.metrics import fleet_step_mfu, moe_held_load


def read(ctx):
    load, stated = moe_held_load.read(ctx), fleet_step_mfu.read(ctx)
    if load is None or stated is None:
        return None
    config = ctx["cell"]["config"]
    count = reference.model_reference(config).forward_flops_per_window
    return stated * count(config, held_load=load) / count(config)
