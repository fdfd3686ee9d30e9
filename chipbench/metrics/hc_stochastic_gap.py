"""How far the streams' mixing matrix is from doubly stochastic after its last
Sinkhorn iteration: the largest |row or column sum - 1| of ``H_res``, the mean
over tokens and over the window's sublayer-steps (0 is doubly stochastic)."""
from chipbench.shares import counter_ratio


def read(ctx):
    return counter_ratio(
        ctx, "gordo_build_hc_stochastic_gap_total", "gordo_build_hc_sublayer_steps_total"
    )
