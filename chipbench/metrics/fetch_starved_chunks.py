"""Chunks of the window's builds that waited for their own fetches while the
chunk before them had already finished on the device (Δ
``gordo_build_fetch_starved_chunks_total``): the device idled for the host's
fetch. A build of one chunk has no later chunk to starve, and a program
without the counter has nothing to read."""
from chipbench.readers import counter_delta


def read(ctx):
    traffic = ctx["cell"]["traffic"]
    if traffic.machines_per_build <= traffic.chunk_machines:
        return None
    return counter_delta(ctx, "gordo_build_fetch_starved_chunks_total")
