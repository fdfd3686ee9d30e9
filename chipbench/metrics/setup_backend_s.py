"""Seconds the set-up waited on the backend: compiling programs after a
persistent-cache miss (``stage=compile``) and loading them back after a hit
(``stage=load``) of the program's ``gordo_build_xla_compile_seconds_total``,
as the first window build found it. A program without the label has nothing
to read."""

SERIES = "gordo_build_xla_compile_seconds_total{{stage={}}}"


def read(ctx):
    parts = [ctx["before"].get(SERIES.format(stage)) for stage in ("compile", "load")]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
