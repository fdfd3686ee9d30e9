"""Seconds the set-up spent lowering jaxprs to MLIR modules: the program's
``gordo_build_xla_compile_seconds_total{stage=lower}`` as the first window
build found it. A program without the label has nothing to read."""

SERIES = "gordo_build_xla_compile_seconds_total{stage=lower}"


def read(ctx):
    return ctx["before"].get(SERIES)
