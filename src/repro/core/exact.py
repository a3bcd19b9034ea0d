"""Exact box-intersects refinement on the host (``refine=True, exact=True``).

A record answers an exact query iff the closed point set of its geometry
meets the closed query box ``B = [x0, x1] × [y0, y1]``, coordinates taken as
their exact float64 values. Edges join consecutive vertices of one part
(ring or line string); a polygon ring that is not closed gets the implied
closing edge ``last → first``. A polygon's point set is the region its rings
bound under the even–odd rule, boundary included (a MultiPolygon's rings
count together, which is its union for disjoint polygons). Points and
MultiPoints have no edges: a vertex in ``B`` is the whole test. A record
with a NaN or infinite coordinate never answers.

Per record whose MBR meets ``B`` the decision is, in order:

1. a vertex lies in ``B``;
2. else an edge meets ``B``;
3. else (polygons) the corner ``(x0, y0)`` lies inside the region, by
   even–odd crossings of the rightward ray over every ring's edges. After
   1 and 2 fail that corner lies on no edge, so the parity is well defined.

The work splits in two stages, as on the device path:

* :func:`classify` decides with compares alone. A record is ``IN`` when a
  vertex lies in ``B`` or an edge crosses it in a way compares show (both
  endpoints' y within ``[y0, y1]`` and the edge's x extent meeting
  ``[x0, x1]``, or the same with x and y swapped); ``OUT`` when no edge's
  box meets ``B`` and the ray's crossings are all decided by compares;
  ``UNDECIDED`` otherwise. ``repro.kernels.exact`` runs the same compares
  in key space on the device; this function is its oracle.
* :func:`resolve` decides the ``UNDECIDED`` records exactly, with the sign
  of the orientation determinant of each undecided edge against the box's
  corners (:func:`orient_sign`: a float64 error-bound filter, with exact
  rational arithmetic where the filter cannot certify the sign).
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from repro import obs

from .columnar import ragged_ranges
from .filters import canonical_bbox
from .geometry import TYPE_MULTIPOLYGON, TYPE_POLYGON

OUT, IN, UNDECIDED = 0, 1, 2

# Shewchuk's orient2d bound (3 + 16 eps) eps, doubled so that the absolute
# error of a product that underflows (at most 2**-1075) also fits inside it
# once the products' magnitude is at least _TINY
_EPS = 2.0 ** -53
_ERR = 2.0 * (3.0 + 16.0 * _EPS) * _EPS
_TINY = 2.0 ** -1000


def edge_partners(rep, defn, types) -> tuple[np.ndarray, np.ndarray]:
    """The edge that starts at each coordinate value of a record-aligned
    level slice, as ``(off, poly)`` over the values (slots with ``defn ==
    1``): the edge joins value ``i`` to value ``i + off[i]``, and ``poly[i]``
    says whether it bounds a polygon region (it counts for the even–odd
    rule). A part's last value takes the closing edge back to the part's
    first value in a polygon ring, and none (``off == 0``, a degenerate
    edge that no test can tell from its vertex) in a line string or a
    one-point part."""
    rep = np.asarray(rep)
    has = np.asarray(defn) == 1
    n = int(np.count_nonzero(has))
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, bool)
    # slots with rep <= 1 open a sub-geometry, the unit of the type column
    sub = np.cumsum(rep <= 1) - 1
    poly_slot = np.isin(np.asarray(types)[sub],
                        (TYPE_POLYGON, TYPE_MULTIPOLYGON))
    vrep = rep[has]
    poly = poly_slot[has]
    starts = np.flatnonzero(vrep <= 2)
    ends = np.append(starts[1:], n) - 1
    off = np.ones(n, np.int32)
    off[ends] = np.where(poly[ends], starts - ends, 0)
    return off, poly


def _value_tests(x, y, off, poly, box):
    """Per value (and the edge starting there): the compare-only tests."""
    x0, y0, x1, y1 = box
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    j = np.arange(len(x)) + np.asarray(off, np.int64)
    xl, xr, yb, ya = x < x0, x > x1, y < y0, y > y1
    bad = ~(np.isfinite(x) & np.isfinite(y))
    inside = ~(xl | xr | yb | ya) & ~bad
    miss = (xl & xl[j]) | (xr & xr[j]) | (yb & yb[j]) | (ya & ya[j])
    xin, yin = ~(xl | xr), ~(yb | ya)
    cross = ~miss & ((yin & yin[j]) | (xin & xin[j]))
    und = ~miss & ~cross
    above = y > y0
    straddle = np.asarray(poly, bool) & (above != above[j])
    # a straddling edge whose box misses B misses it in x: wholly right of
    # x1 (it crosses the corner's rightward ray) or wholly left of x0
    ray = straddle & xr & xr[j]
    return {"x": x, "y": y, "j": j, "bad": bad, "inside": inside,
            "cross": cross, "und": und, "straddle": straddle, "ray": ray}


def _per_record(flags: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Number of set flags in each record's contiguous value slice."""
    cs = np.concatenate([[0], np.cumsum(flags, dtype=np.int64)])
    ends = np.cumsum(counts)
    return cs[ends] - cs[ends - counts]


def classify(x, y, counts, off, poly, bbox) -> tuple[np.ndarray, np.ndarray]:
    """Compare-only stage over records laid out one after another in
    ``x``/``y`` (``counts`` values each). Returns ``(cls, vertex)``: the
    class of each record (``OUT``, ``IN`` or ``UNDECIDED``) and whether a
    vertex of it lies in the box."""
    counts = np.asarray(counts, np.int64)
    box = canonical_bbox(bbox)
    cls = np.zeros(len(counts), np.int8)
    if box is None or len(counts) == 0:
        return cls, np.zeros(len(counts), bool)
    t = _value_tests(x, y, off, poly, box)
    bad = _per_record(t["bad"], counts) > 0
    n_in = _per_record(t["inside"] | t["cross"], counts)
    n_und = _per_record(t["und"], counts)
    odd = _per_record(t["ray"], counts) % 2 == 1
    cls[odd] = IN
    cls[n_und > 0] = UNDECIDED
    cls[n_in > 0] = IN
    cls[bad] = OUT
    vertex = (_per_record(t["inside"], counts) > 0) & ~bad
    return cls, vertex


def resolve(x, y, counts, off, poly, bbox) -> tuple[np.ndarray, int]:
    """Exact answers for records laid out as for :func:`classify`; meant for
    those it left ``UNDECIDED``. Returns the keep mask and the number of
    edges decided by orientation signs."""
    counts = np.asarray(counts, np.int64)
    box = canonical_bbox(bbox)
    if box is None or len(counts) == 0:
        return np.zeros(len(counts), bool), 0
    x0, y0, x1, y1 = box
    t = _value_tests(x, y, off, poly, box)
    finite = ~t["bad"] & ~t["bad"][t["j"]]
    e = np.flatnonzero(t["und"] & finite)
    px, py = t["x"][e], t["y"][e]
    qx, qy = t["x"][t["j"][e]], t["y"][t["j"][e]]
    # an undecided edge's box meets B, so the edge meets B iff the four
    # corners do not all lie strictly on one side of its line
    signs = np.stack([orient_sign(px, py, qx, qy, cx, cy)
                      for cx, cy in ((x0, y0), (x1, y0), (x0, y1), (x1, y1))])
    meets = ~(signs > 0).all(0) & ~(signs < 0).all(0)
    # an undecided edge that straddles y0 crosses the ray right of x0 iff
    # the corner (x0, y0) lies on the side the edge's upward direction
    # makes positive
    up = np.sign(qy - py).astype(np.int8)
    ray = t["ray"].copy()
    ray[e] = t["straddle"][e] & (signs[0] == up)
    hit = t["inside"] | (t["cross"] & finite)
    hit[e] = meets
    keep = (_per_record(hit, counts) > 0) | (_per_record(ray, counts) % 2 == 1)
    keep &= _per_record(t["bad"], counts) == 0
    return keep, len(e)


def exact_keep(x, y, counts, off, poly, bbox) -> tuple[np.ndarray, np.ndarray, int]:
    """Both stages on the host: ``(keep, vertex, edges_host)``."""
    counts = np.asarray(counts, np.int64)
    cls, vertex = classify(x, y, counts, off, poly, bbox)
    keep = cls == IN
    und = np.flatnonzero(cls == UNDECIDED)
    n_host = 0
    if len(und):
        starts = np.cumsum(counts) - counts
        iv = ragged_ranges(starts[und], counts[und])
        keep[und], n_host = resolve(np.asarray(x)[iv], np.asarray(y)[iv],
                                    counts[und], np.asarray(off)[iv],
                                    np.asarray(poly)[iv], bbox)
    return keep, vertex, n_host


def narrow(x, y, counts, off, poly, keep, bbox) -> np.ndarray:
    """The exact test on the host over the records ``keep`` holds (their
    MBR met the box and they passed the predicate); returns ``keep``
    narrowed to the records that answer. ``x``/``y``/``off``/``poly`` hold
    every record's values one record after another, ``counts`` per record."""
    counts = np.asarray(counts, np.int64)
    cand = np.flatnonzero(keep)
    iv = ragged_ranges((np.cumsum(counts) - counts)[cand], counts[cand])
    off = np.asarray(off)[iv]
    k, vertex, n_host = exact_keep(np.asarray(x)[iv], np.asarray(y)[iv],
                                   counts[cand], off, np.asarray(poly)[iv],
                                   bbox)
    count(len(cand), int(vertex.sum()), int(k.sum()),
          int(np.count_nonzero(off)), n_host)
    out = np.array(keep, bool)
    out[cand] = k
    return out


def count(records_in: int, vertex: int, kept: int, edges: int,
          edges_host: int) -> None:
    """The exact stage's counters: records that entered it (their MBR met
    the box and they passed the predicate), those a vertex in the box
    answered, those it dropped, the edges it tested and those decided by
    orientation signs on the host."""
    obs.count("exact.records_in", records_in)
    obs.count("exact.records_vertex", vertex)
    obs.count("exact.records_dropped", records_in - kept)
    obs.count("exact.edges", edges)
    obs.count("exact.edges_host", edges_host)


def orient_sign(px, py, qx, qy, cx: float, cy: float) -> np.ndarray:
    """Exact sign (-1, 0, 1) of the orientation determinant
    ``(q - p) × (c - p)`` for float64 edges ``p → q`` and one point ``c``.

    The float64 determinant is trusted where its magnitude exceeds a
    forward error bound on its rounding; the rest are computed with
    rationals, which are exact for every finite float.
    """
    px, py, qx, qy = (np.asarray(a, np.float64) for a in (px, py, qx, qy))
    with np.errstate(over="ignore", invalid="ignore", under="ignore"):
        left = (px - cx) * (qy - cy)
        right = (py - cy) * (qx - cx)
        det = left - right
        mag = np.abs(left) + np.abs(right)
        sure = (np.isfinite(det) & np.isfinite(mag) & (mag >= _TINY)
                & (np.abs(det) > _ERR * mag))
    out = np.where(sure, np.sign(det), 0).astype(np.int8)
    c = (Fraction(cx), Fraction(cy))
    for i in np.flatnonzero(~sure):
        a = (Fraction(float(px[i])) - c[0]) * (Fraction(float(qy[i])) - c[1])
        b = (Fraction(float(py[i])) - c[1]) * (Fraction(float(qx[i])) - c[0])
        out[i] = (a > b) - (a < b)
    return out
