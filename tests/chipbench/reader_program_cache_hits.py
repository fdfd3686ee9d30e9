"""An example reader, not a metric of ``BENCHMARK.json``: builds of the window
that found their chunk program already traced, from a counter of the
program's catalog that the harness has no name of its own for."""
from chipbench.readers import counter_delta


def read(ctx):
    return counter_delta(ctx, "gordo_build_program_cache_requests_total{result=hit}")
