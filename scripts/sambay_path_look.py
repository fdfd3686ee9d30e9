"""How long the selective scan of the SambaY layers takes on the chip at
each chunk length, at the widths of ``phi4_mini_flash_3_8b`` (batch 16, T 256,
d_inner 5,120, d_state 16, ``u`` in bfloat16): one line of JSON a chunk
length, with :func:`gordo_tpu.ops.nn.selective_scan`'s forward and its
forward with the gradient, each compiled once and timed over ``--runs``
calls after two warm-up calls, ``block_until_ready`` round each. What
``ops/nn.py SCAN_CHUNK`` is set from (PERF.md section 6). Run it on a
machine that holds the chip (``--rehearsal`` lets a CPU through at a tiny
size: control flow, no number):

    python3 scripts/sambay_path_look.py --chunks 1 2 4 8 16
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _time(fn, args, runs: int) -> float:
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / runs


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chunks", type=int, nargs="+", default=[1, 2, 4, 8, 16])
    parser.add_argument("--runs", type=int, default=10)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from gordo_tpu.ops import nn

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearsal:
        print(f"found {device.platform!r}, not a TPU (--rehearsal for a CPU)", file=sys.stderr)
        return 2
    bsz, t, di, ds = (2, 24, 32, 4) if args.rehearsal else (16, 256, 5120, 16)
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(shape, dtype):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    base = {"device_kind": device.device_kind, "batch": bsz, "t": t}
    u, b, c = normal((bsz, t, di), bf16), normal((bsz, t, ds), bf16), normal((bsz, t, ds), bf16)
    delta = jax.nn.softplus(normal((bsz, t, di), f32) - 4.0)
    a = -jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=f32), (di, ds))
    d_skip = jnp.ones((di,), f32)
    dy = normal((bsz, t, di), f32)
    scan_args = (u, delta, a, b, c, d_skip)
    for chunk in args.chunks:

        def forward(*xs, chunk=chunk):
            return nn.selective_scan(*xs, chunk=chunk)

        def loss(*xs, chunk=chunk):
            return jnp.sum(nn.selective_scan(*xs, chunk=chunk).astype(f32) * dy)

        fwd = jax.jit(forward)
        both = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))
        line = dict(base, what="scan", chunk=chunk)
        line["forward_ms"] = 1e3 * _time(fwd, scan_args, args.runs)
        line["forward_backward_ms"] = 1e3 * _time(both, scan_args, args.runs)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
