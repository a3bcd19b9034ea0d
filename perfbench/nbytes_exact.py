"""Necessary bytes of the exact stage of a scan (``jit_exact_fn``), reckoned
from the reference's records so that they read the same whatever
implements the stage: never from padded shapes or gather layouts.

The stage runs over the records whose MBR meets the box and that pass the
predicate (the refine's survivors). It must read both float64 coordinates
of each of their points, 16 bytes a pair, and hand back one byte a record
(its class). Undecided records' later transfer to the host, and the
survivors' gathers, are not the stage's.
"""

from __future__ import annotations

import numpy as np


def exact_bytes(rec, bbox, pred) -> int:
    """Bytes one scan's exact stage must move (see the module docstring)."""
    m = rec.mask(bbox, pred)
    return int(16 * rec.vcount[m].sum() + np.count_nonzero(m))
