"""Share of the detail cut's device time in latent attention's scores,
softmax and weighted values, forward and backward: the scope ``attention``,
and on the XLA path its two products, which ``jnp.einsum`` names after their
own subscripts inside it (the innermost name is what the cut is reduced by)."""
from chipbench.shares import scope_share

EINSUMS = ("...qd,...kd->...qk", "...qk,...kd->...qd")


def read(ctx):
    if "attention" not in ctx["trace"].scope_s:
        return None
    return scope_share(ctx, ("attention",) + EINSUMS)
