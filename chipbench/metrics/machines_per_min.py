"""Machines whose artifact was persisted, a minute of the whole window: the
first build's machine generation to the last build's return."""


def read(ctx):
    if not ctx["machines"]:
        return None
    return ctx["machines"] * 60.0 / ctx["window_s"]
