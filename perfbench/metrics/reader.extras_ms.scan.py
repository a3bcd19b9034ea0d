"""``rg.extras`` span time (decode of the attribute columns' pages, their
checksums included) per scan in the window, in ms; read from the program's
obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("rg.extras")
    return 1e3 * sum(spans) / n if n and spans else None
