"""``rg.fetch`` span time (coalesced range reads of a row group's pages) per
scan in the window, in ms; read from the program's obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    return 1e3 * sum(ctx["spans"].get("rg.fetch", [])) / n if n else None
