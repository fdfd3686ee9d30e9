"""The flash kernel's forward call in training steps against its roofline:
the least time the chip could take for what one call has to do, over the
seconds a call took in the detail cut. None where the cut holds no such
custom call under the scope ``attention`` (the XLA path, a CPU rehearsal, a
configuration without attention)."""
from chipbench import flops

# Google Cloud documentation, TPU v5e system architecture: 819 GB/s of HBM.
# chipbench/peaks.json holds the bf16 peak only (PERF.md, open questions)
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def required(model: dict, machines: int):
    """(operations, bytes) one forward call has to do: causal scores and
    weighted values (half of 4·T²·Dh a head a window), and q, k, v, o once in
    bfloat16, k and v at their own head count."""
    t, dh = int(model["lookback_window"]), int(model["head_dim"])
    hq, hkv = int(model["num_heads"]), int(model["num_kv_heads"])
    windows = machines * int(model["batch_size"])
    return 2.0 * windows * hq * t * t * dh, 2.0 * windows * (2 * hq + 2 * hkv) * t * dh


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    model = cell["config"]["model"]
    if "num_kv_heads" not in model:
        return None
    # the kernel under differentiation, forward: Mosaic names the call after
    # the name stack it was traced under, vmap(jvp(attention)); the backward
    # calls carry transpose(...)
    calls = [
        entry for op, entry in trace.op_s.items()
        if trace.op_scope.get(op) == "attention" and "attention" in op
        and "jvp" in op and "transpose" not in op
    ]
    seconds, runs = sum(c[0] for c in calls), sum(c[1] for c in calls)
    if not runs or not seconds:
        return None
    operations, nbytes = required(model, cell["traffic"].chunk_machines)
    kind = ctx["device_kind"]
    least = max(
        operations / flops.peaks(kind)["bf16_flops_per_s"], nbytes / HBM_BYTES_PER_S[kind]
    )
    return 100.0 * least / (seconds / runs)
