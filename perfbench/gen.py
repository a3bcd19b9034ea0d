"""The general traffic generator: reads a mix's parameters (a file under
``traffic/``) and draws its requests from the seed.

Every seed gets the same composition of work in another order, so that runs
with different seeds differ by placement and not by how much work they do:
a ``scan`` mix repeats a fixed block of selectivity classes (and filtered
shares), shuffled within each block.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class ScanQuery:
    bbox: tuple
    selectivity: float      # target share of record centroids in the box
    pred: tuple | None      # (column, lo, hi) or None


def _box_for_share(cx, cy, sx, sy, share: float) -> tuple:
    """Square box centred on (cx, cy) holding ``share`` of the sample
    centroids (sx, sy): the k-th smallest Chebyshev distance is its
    half-side."""
    d = np.maximum(np.abs(sx - cx), np.abs(sy - cy))
    k = max(1, int(round(share * len(d))))
    h = float(np.partition(d, k - 1)[k - 1])
    return (float(cx - h), float(cy - h), float(cx + h), float(cy + h))


def scan_queries(rec, cfg: dict, mix: dict, rng, n: int) -> list[ScanQuery]:
    """``n`` closed-loop scans of a ``scan`` mix over records ``rec``."""
    m = max(1, int(round(mix["sample_share"] * rec.n)))
    sample = rng.choice(rec.n, m, replace=False)
    sx, sy = rec.cx[sample], rec.cy[sample]
    flt = cfg["filter"]
    pred = (flt["column"], flt["lo"], flt["hi"])
    block = []
    for cls in mix["block"]:
        block += [(cls["selectivity"], i < cls["filtered"])
                  for i in range(cls["count"])]
    out: list[ScanQuery] = []
    while len(out) < n:
        for j in rng.permutation(len(block)):
            if len(out) == n:
                break
            sel, filtered = block[j]
            r = int(rng.integers(rec.n))
            box = _box_for_share(rec.cx[r], rec.cy[r], sx, sy, sel)
            out.append(ScanQuery(box, sel, pred if filtered else None))
    return out
