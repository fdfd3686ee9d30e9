"""Share of the detail cut's device time in a Mamba layer's work round its
scan: the scopes ``ssm_in`` (``W_in``, the convolution, ``W_x``, ``W_dt``
and the softplus) and ``ssm_out`` (the z gate and ``W_out``)."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("ssm_in", "ssm_out"))
