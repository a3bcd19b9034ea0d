"""``rg.exact`` span time (the host settling the records the exact stage
left undecided: their coordinates' transfer and the orientation signs of
their edges) per scan in the window, in ms; read from the program's obs
spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("rg.exact")
    return 1e3 * sum(spans) / n if n and spans else None
