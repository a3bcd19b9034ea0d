"""``device.wait`` span time (the host blocked on a device result: the refine
chain's record mask, the survivors' values) per scan in the window, in ms;
read from the program's obs spans."""


def read(ctx):
    n = ctx["n_requests"]
    spans = ctx["spans"].get("device.wait")
    return 1e3 * sum(spans) / n if n and spans else None
