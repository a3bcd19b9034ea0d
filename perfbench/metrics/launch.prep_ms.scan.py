"""``launch.build`` span time (the host building a launch's page stream and
refine operands) per scan in the window, in ms; read from the program's obs
spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("launch.build")
    return 1e3 * sum(spans) / n if n and spans else None
