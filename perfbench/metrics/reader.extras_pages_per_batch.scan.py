"""Attribute pages decoded per batched decode call, over the window:
``reader.extras_pages`` over ``reader.extras_batches``, both counted where
the reader decodes the attribute pages (a call decodes one extra column's
hit pages of a row group). Nothing is read where the program has neither
counter."""


def read(ctx):
    c = ctx["counters"]
    pages, batches = c.get("reader.extras_pages"), c.get("reader.extras_batches")
    if pages is None or not batches:
        return None
    return pages / batches
