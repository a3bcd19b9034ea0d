"""Shards read over shards in the lake, summed over the window's scans, in
%; read from each scan's ``ReadStats``."""


def read(ctx):
    stats = ctx.get("read_stats") or []
    total = sum(s.shards_total for s in stats)
    return 100.0 * sum(s.shards_read for s in stats) / total if total else None
