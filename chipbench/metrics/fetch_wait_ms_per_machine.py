"""The build thread's wall blocked on a chunk's own fetches just before it
stacks the chunk (the program's ``fetch_wait`` stage, once a chunk after a
bucket's first), a machine: what of the fleet's fetch the device's chunks
did not cover. A program that fetches everything before its first dispatch
has no such stage, and nothing to read."""
from chipbench.readers import counter_delta, per_machine_ms


def read(ctx):
    return per_machine_ms(ctx, counter_delta(ctx, "phase_s.fetch_wait"))
