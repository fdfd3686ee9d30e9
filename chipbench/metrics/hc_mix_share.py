"""Share of the detail cut's device time in the residual streams' mixing:
``hc_coeff`` (the norm over a token's streams, the coefficients' product and
the Sinkhorn iterations) and ``hc_mix`` (``H_pre X``, ``H_res X + H_post^T
F``)."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("hc_coeff", "hc_mix"))
