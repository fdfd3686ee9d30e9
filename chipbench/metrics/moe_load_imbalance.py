"""The fullest held expert's assignments over the mean held expert's, summed
over the window's routed layers and training steps: 1 is even."""
from chipbench.shares import counter_ratio


def read(ctx):
    held = int(ctx["cell"]["config"]["model"].get("experts_held", 0))
    if not held:
        return None
    return counter_ratio(
        ctx, "gordo_build_moe_peak_load_total",
        "gordo_build_moe_assignments_total{where=held}", scale=held,
    )
