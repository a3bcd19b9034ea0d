"""Share of the edges the exact stage tested that the host decided with
orientation signs, in %: 100 * ``exact.edges_host`` / ``exact.edges``;
nothing is read where the program has neither counter."""


def read(ctx):
    c = ctx["counters"]
    edges, host = c.get("exact.edges"), c.get("exact.edges_host")
    if not edges or host is None:
        return None
    return 100.0 * host / edges
