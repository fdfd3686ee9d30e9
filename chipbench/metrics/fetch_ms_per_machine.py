"""Thread-seconds the fleet plan spent fetching and joining a machine's data."""
from chipbench.readers import counter_delta, per_machine_ms


def read(ctx):
    return per_machine_ms(ctx, counter_delta(ctx, "phase_s.fetch"))
