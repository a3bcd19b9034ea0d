"""Share of the records whose MBR met the box (and that passed the
predicate) that the exact test dropped, in %: 100 *
``exact.records_dropped`` / ``exact.records_in``; nothing is read where the
program has neither counter."""


def read(ctx):
    c = ctx["counters"]
    rin, dropped = c.get("exact.records_in"), c.get("exact.records_dropped")
    if not rin or dropped is None:
        return None
    return 100.0 * dropped / rin
