"""Share of the detail cut's device time in latent attention's projections:
the scopes ``mla_down`` (the two down-projections and the norm inside each),
``mla_up`` (``W_uq``, ``W_ukv``, RoPE and the assembly of q and k) and
``mla_out`` (``W_o``)."""
from chipbench.shares import scope_share


def read(ctx):
    return scope_share(ctx, ("mla_down", "mla_up", "mla_out"))
