"""Share of the detail cut's device time in differential attention's maps,
the λ combination and the head norm, forward and backward: the scope
``diff_attention``, and on the XLA path its two products, which
``jnp.einsum`` names after their own subscripts inside it (the innermost
name is what the cut is reduced by)."""
from chipbench.shares import scope_share

EINSUMS = ("...qd,...kd->...qk", "...qk,...kd->...qd")


def read(ctx):
    if "diff_attention" not in ctx["trace"].scope_s:
        return None
    return scope_share(ctx, ("diff_attention",) + EINSUMS)
