"""The build thread's wall with nothing dispatched to the device yet: the
plan, the fetch stage, validation and bucketing, the bucket's preparation
and its first dispatch (the program's stage spans), a machine."""
from chipbench.readers import counter_delta, per_machine_ms

STAGES = ("plan", "fetch_stage", "validate_stage", "bucket_prep", "compile")


def read(ctx):
    parts = [counter_delta(ctx, f"phase_s.{stage}") for stage in STAGES]
    if any(p is None for p in parts):
        return None
    return per_machine_ms(ctx, sum(parts))
