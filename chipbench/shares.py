"""What the readers of the hybrid block's per-layer metrics share: a named
scope's share of the detail cut's device time, and a ratio of two of the
program's counters over the window."""

from __future__ import annotations

from typing import Optional, Sequence

from chipbench.readers import counter_delta


def scope_share(ctx: dict, scopes: Sequence[str], kernels: Sequence[str] = ()) -> Optional[float]:
    """Percent of the detail cut's device self time under the named
    ``scopes`` (innermost ``jax.named_scope``) plus the compiled operations
    whose names start with one of ``kernels``: the TPU compiler gives a
    kernel it puts in itself a name and an ``op_name`` of its own, so no
    scope covers it. None where the cut names none of them (a program loaded
    from a compile cache that predates the scopes; a CPU rehearsal)."""
    trace = ctx["trace"]
    if not trace.detail_s:
        return None
    found = [trace.scope_s[s] for s in scopes if s in trace.scope_s]
    found += [
        seconds for op, (seconds, _) in trace.op_s.items()
        if any(op.startswith(k) for k in kernels)
    ]
    return 100.0 * sum(found) / trace.detail_s if found else None


def counter_ratio(ctx: dict, over: str, under: str, scale: float = 1.0) -> Optional[float]:
    """``scale`` x what counter ``over`` gained in the window over what
    ``under`` gained; None where the program has neither or nothing moved."""
    top, bottom = counter_delta(ctx, over), counter_delta(ctx, under)
    if top is None or not bottom:
        return None
    return scale * top / bottom
