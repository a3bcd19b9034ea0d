"""Share of the FP-delta pages whose escapes were resolved in closed form,
in %: 100 * ``fp_delta.escape_pages.closed`` over the pages the candidate
resolvers took (``.closed`` + ``.hop`` + ``.walk``), counted where plans
are made. Pages without escapes or resolved by the fixpoint are not
counted; nothing is read where the program has none of the counters."""

PATHS = ("closed", "hop", "walk")


def read(ctx):
    c = ctx["counters"]
    pages = [c.get(f"fp_delta.escape_pages.{p}", 0) for p in PATHS]
    total = sum(pages)
    return 100.0 * pages[0] / total if total else None
