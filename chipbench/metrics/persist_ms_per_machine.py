"""Thread-seconds of assembly (thresholds, scores, metadata) and of the
serializer, a machine."""
from chipbench.readers import counter_delta, per_machine_ms


def read(ctx):
    parts = [counter_delta(ctx, f"phase_s.{p}") for p in ("assemble", "serialize")]
    if any(p is None for p in parts):
        return None
    return per_machine_ms(ctx, sum(parts))
