"""``rg.stream_plan`` span time (the host's FP-delta plans of the coordinate
pages, escape resolution included) per scan in the window, in ms; read from
the program's obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("rg.stream_plan")
    return 1e3 * sum(spans) / n if n and spans else None
