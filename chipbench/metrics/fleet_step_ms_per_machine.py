"""Device time of the chunk program's executions, a machine."""
from chipbench.readers import per_machine_ms, step_seconds


def read(ctx):
    return per_machine_ms(ctx, step_seconds(ctx))
