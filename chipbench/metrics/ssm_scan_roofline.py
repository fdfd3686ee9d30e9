"""The selective scan against the bandwidth side of its roofline: the least
time the chip's HBM could take to move the bytes the window's scans required,
over the seconds the scans took. The bytes are counted from the window's
counters, the positions scanned (``gordo_build_ssm_positions_total``) and the
layer-steps (``gordo_build_ssm_layer_steps_total``), by the plain model's
``ssm_scan_bytes`` (forward and backward), at 819 GB/s. The scan's
arithmetic is float32 elementwise work on the vector unit, not the matrix
unit whose bf16 peak ``chipbench/peaks.json`` holds, and no vector-unit rate
of the v5e is published: so no operations side is held, and where the
vector unit binds the reading errs low. The seconds are the window's
chunk-program device seconds times the scope ``ssm_scan``'s share of the
detail cut, so the cut's share is read as the window's. The scans of the
folds' predictions (forward only, a small part) are in the seconds and not
in the bytes: the reading errs low there too, never above what the scan
achieved. None where the cut names no ``ssm_scan`` (the parent, a stale
compile cache, a CPU rehearsal) or no scan was counted."""
from chipbench.configs import sambay
from chipbench.readers import counter_delta, step_seconds

# Google Cloud documentation, TPU v5e system architecture: 819 GB/s of HBM
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def read(ctx):
    trace = ctx["trace"]
    positions = counter_delta(ctx, "gordo_build_ssm_positions_total")
    layer_steps = counter_delta(ctx, "gordo_build_ssm_layer_steps_total")
    if not trace.detail_s or not trace.scope_s.get("ssm_scan") or not positions:
        return None
    kind = ctx["device_kind"]
    if kind not in HBM_BYTES_PER_S:
        return None  # a rehearsal: a host has no published peaks
    seconds = step_seconds(ctx)
    if not seconds:
        return None
    scan_s = seconds * trace.scope_s["ssm_scan"] / trace.detail_s
    nbytes = sambay.ssm_scan_bytes(ctx["cell"]["config"]["model"], positions, layer_steps)
    return 100.0 * nbytes / HBM_BYTES_PER_S[kind] / scan_s
