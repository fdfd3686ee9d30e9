"""Assignments the held experts took, a token a routed layer, over the
window's training steps: ``top_k x experts_held / num_experts`` (1 in
``lfm2_8b_a1b``) where the router spreads evenly over all its experts."""
from chipbench.shares import counter_ratio


def read(ctx):
    return counter_ratio(
        ctx, "gordo_build_moe_assignments_total{where=held}", "gordo_build_moe_tokens_total"
    )
