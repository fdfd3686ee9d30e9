"""The operations the traced builds' machines require over what the chip
could have done in the chunk programs' device time, at its bf16 peak."""
from chipbench import flops
from chipbench.readers import step_seconds


def read(ctx):
    seconds = step_seconds(ctx)
    if not seconds or not ctx["machines"]:
        return None
    try:
        peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    except KeyError:
        if ctx["device_kind"] == "cpu":
            return None  # a rehearsal: a host has no published peak to hold it against
        raise
    cell = ctx["cell"]
    needed = ctx["machines"] * flops.build_flops_per_machine(
        cell["config"], cell["traffic"].rows
    )
    return 100.0 * needed / (seconds * peak * ctx["n_devices"])
