"""Share of the decode lanes launched that carry no value, in %:
100 * (1 - ``launch.values`` / ``launch.values_padded``), both counted where
the launch chain builds a page stream."""


def read(ctx):
    c = ctx["counters"]
    vals, padded = c.get("launch.values"), c.get("launch.values_padded")
    if vals is None or not padded:
        return None
    return 100.0 * (1.0 - vals / padded)
