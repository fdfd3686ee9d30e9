"""An example reader, not a metric of ``BENCHMARK.json``: the share of the
detail cut's device time spent under one ``jax.named_scope``. A cut that
names no such scope (a program loaded from a compile cache that predates the
scope; a CPU rehearsal) gives nothing to read."""
SCOPE = "attention"


def read(ctx):
    trace = ctx["trace"]
    if SCOPE not in trace.scope_s or not trace.detail_s:
        return None
    return 100.0 * trace.scope_s[SCOPE] / trace.detail_s
