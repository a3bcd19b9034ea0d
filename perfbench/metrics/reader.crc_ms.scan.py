"""``rg.crc`` span time (page metadata and checksum verification of the
coordinate and attribute pages) per scan in the window, in ms; read from
the program's obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("rg.crc")
    return 1e3 * sum(spans) / n if n and spans else None
