"""Plain reference of exact box-intersects answers (the ``scan_exact``
traffic), and the record mask it hands to ``reference.compare``.

A record answers ``(bbox, predicate)`` when it passes the predicate and the
closed point set of its geometry meets the closed box: the region a
polygon's rings bound under the even–odd rule, boundary included; the
edges join consecutive vertices of each ring, with the closing edge
implied where a ring is not closed. It works on the generated arrays alone
and shares no code with the program: numpy finds the records whose MBR
meets the box and, among them, those with a vertex in it; every other
candidate is decided in Python, edge by edge, with orientation signs
computed in ``fractions.Fraction``, which is exact for every float.
Answers are kept per distinct query, since a run repeats its queries.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

POLYGON_TYPES = (3, 6)


def _orient(p, q, c) -> int:
    """Sign of (q - p) × (c - p) in exact rationals."""
    d = (q[0] - p[0]) * (c[1] - p[1]) - (q[1] - p[1]) * (c[0] - p[0])
    return (d > 0) - (d < 0)


def _segment_meets_box(p, q, box) -> bool:
    x0, y0, x1, y1 = box
    if (max(p[0], q[0]) < x0 or min(p[0], q[0]) > x1
            or max(p[1], q[1]) < y0 or min(p[1], q[1]) > y1):
        return False
    sides = {_orient(p, q, c) for c in ((x0, y0), (x1, y0), (x0, y1), (x1, y1))}
    return sides != {1} and sides != {-1}


def _ray_crosses(p, q, corner) -> bool:
    """Does the edge cross the rightward ray from ``corner`` (half-open in
    y, so a vertex on the ray counts once)?"""
    cx, cy = corner
    if (p[1] > cy) == (q[1] > cy):
        return False
    if min(p[0], q[0]) > cx:
        return True
    if max(p[0], q[0]) < cx:
        return False
    up = 1 if q[1] > p[1] else -1
    return _orient(p, q, corner) == up


class ExactReference:
    """Exact answers over the records of a ``reference.Records``; rings
    are read off its level slots (0 opens a record, 2 a part)."""

    def __init__(self, rec):
        self.rec = rec
        self.ring_start = np.flatnonzero(rec.rep != 3)
        self.ring_size = np.diff(np.append(self.ring_start, rec.n_values))
        self.first_ring = np.searchsorted(self.ring_start, rec.vstart)
        self.n_rings = np.diff(np.append(self.first_ring, len(self.ring_start)))
        self.polygon = np.isin(rec.types, POLYGON_TYPES)
        self._answers: dict = {}

    def mask(self, bbox, pred=None) -> np.ndarray:
        key = (tuple(bbox), pred)
        if key not in self._answers:
            self._answers[key] = self._mask(bbox, pred)
        return self._answers[key]

    def _mask(self, bbox, pred) -> np.ndarray:
        rec = self.rec
        x0, y0, x1, y1 = (float(v) for v in bbox)
        cand = rec.mask(bbox, pred)
        inb = ((rec.x >= x0) & (rec.x <= x1) & (rec.y >= y0) & (rec.y <= y1))
        vertex = np.add.reduceat(inb.astype(np.int64), rec.vstart) > 0
        out = cand & vertex
        box = tuple(Fraction(v) for v in (x0, y0, x1, y1))
        for r in np.flatnonzero(cand & ~vertex):
            out[r] = self._meets(int(r), box)
        return out

    def _meets(self, r: int, box) -> bool:
        rings = []
        for k in range(self.first_ring[r], self.first_ring[r] + self.n_rings[r]):
            s, n = int(self.ring_start[k]), int(self.ring_size[k])
            rings.append([(Fraction(float(self.rec.x[i])),
                           Fraction(float(self.rec.y[i])))
                          for i in range(s, s + n)])
        edges = []
        for ring in rings:
            edges += list(zip(ring[:-1], ring[1:]))
            if self.polygon[r] and len(ring) > 1 and ring[-1] != ring[0]:
                edges.append((ring[-1], ring[0]))
        if any(_segment_meets_box(p, q, box) for p, q in edges):
            return True
        if not self.polygon[r]:
            return False
        corner = (box[0], box[1])
        return sum(_ray_crosses(p, q, corner) for p, q in edges) % 2 == 1
