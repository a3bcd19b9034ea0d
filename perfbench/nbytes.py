"""Necessary bytes of the lake's device launches, reckoned from the logical
work so that they read the same whatever implements it: never from padded
shapes, block sizes or gather layouts.

The refine chain of a scan (FP-delta decode -> segmented min/max -> bbox
mask, one launch per stream of pages) must read the stored x and y bytes of
every page whose box meets the query (and whose zone statistics admit the
predicate), and hand back one mask byte per record of those pages. The
survivors' coordinates are taken by launches of their own after the chain,
so their bytes are not the chain's.
"""

from __future__ import annotations

import numpy as np


def page_hits(pages, bbox, pred=None) -> np.ndarray:
    x0, y0, x1, y1 = bbox
    hit = ((pages.xmin <= x1) & (pages.xmax >= x0)
           & (pages.ymin <= y1) & (pages.ymax >= y0))
    if pred is not None:
        _, lo, hi = pred
        hit &= ~((pages.zmax < lo) | (pages.zmin > hi))
    return hit


def chain_bytes(pages, bbox, pred) -> int:
    """Bytes one scan's refine chain must move (see the module docstring)."""
    hit = page_hits(pages, bbox, pred)
    return int(pages.nbytes[hit].sum() + pages.records[hit].sum())
