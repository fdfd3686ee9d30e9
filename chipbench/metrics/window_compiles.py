"""Programs XLA compiled (not loaded) during the traced builds."""
from chipbench.readers import counter_delta


def read(ctx):
    return counter_delta(ctx, "compiles")
