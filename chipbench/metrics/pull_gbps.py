"""The rate of the pull of a chunk's outputs to the host over the window's
builds: Δ ``gordo_build_transfer_bytes_total{direction=d2h}`` over Δ the
``d2h`` stage's seconds, in GB/s. A program that does not count the bytes has
nothing to read."""
from chipbench.readers import counter_delta


def read(ctx):
    pulled = counter_delta(ctx, "gordo_build_transfer_bytes_total{direction=d2h}")
    seconds = counter_delta(ctx, "phase_s.d2h")
    if pulled is None or not seconds:
        return None
    return pulled / seconds / 1e9
