"""Seconds the set-up spent tracing Python into jaxprs: the program's
``gordo_build_xla_compile_seconds_total{stage=trace}`` (jax's own trace
events, a nested trace counted once) as the first window build found it, so
everything before the window and nothing in it. A program without the label
has nothing to read."""

SERIES = "gordo_build_xla_compile_seconds_total{stage=trace}"


def read(ctx):
    return ctx["before"].get(SERIES)
