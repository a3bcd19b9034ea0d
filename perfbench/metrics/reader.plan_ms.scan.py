"""``rg.plan`` span time (page checksums, FP-delta escape resolution and
launch planning on the host) per scan in the window, in ms; read from the
program's obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    return 1e3 * sum(ctx["spans"].get("rg.plan", [])) / n if n else None
