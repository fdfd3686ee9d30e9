"""Process start to the window's start: imports, the native helpers, the
compile cache's load (or the compile), the warm-up build."""


def read(ctx):
    return ctx["setup_s"]
