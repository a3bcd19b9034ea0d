"""Programs the launch chain compiled (``jit.compiles``) inside the window
of a scan cell; warm-up should leave none."""


def read(ctx):
    return ctx["counters"].get("jit.compiles", 0)
