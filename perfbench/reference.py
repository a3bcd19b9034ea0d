"""Plain numpy reference of the lake's range query, and the comparison that
decides ``correct``.

The reference works on the generated arrays alone: it shares no code with
the program and reads nothing the program wrote. A record is in the answer
of ``(bbox, predicate)`` when its MBR meets the box on closed intervals (the
scanner's ``refine=True`` semantics) and its attribute passes the closed
range. Records are matched by the configuration's id column, since the lake
stores them in Hilbert order.

``Records(data, cfg, dtype=np.float32)`` is the control: the same
reference, computed in the precision below the configuration's float64.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# the numbers compared; each is an exact count, so each limit is 0
CHECKS = ("answers_failed", "records_missing", "records_extra",
          "levels_wrong", "coords_wrong", "attrs_wrong")


@dataclass
class Answer:
    """One query's answer in the lake's columnar form (the fields of the
    program's ``GeometryColumns`` that the comparison reads)."""

    types: np.ndarray
    type_rep: np.ndarray
    rep: np.ndarray
    defn: np.ndarray
    x: np.ndarray
    y: np.ndarray


def ragged_ranges(starts, counts) -> np.ndarray:
    """Concatenation of ``arange(s, s + c)`` over (start, count) pairs."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    excl = np.cumsum(counts) - counts
    return (np.repeat(np.asarray(starts, np.int64) - excl, counts)
            + np.arange(total, dtype=np.int64))


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


class Records:
    """Per-record geometry of the generated data: value ranges, MBRs,
    centroids and the expected level slots (one sub-geometry per record,
    no empty parts, as both configurations generate)."""

    def __init__(self, data: dict, cfg: dict, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        coords = np.asarray(data["coords"])
        self.x = np.ascontiguousarray(coords[:, 0]).astype(self.dtype)
        self.y = np.ascontiguousarray(coords[:, 1]).astype(self.dtype)
        parts = np.asarray(data["parts_per_record"], np.int64)
        psize = np.asarray(data["part_sizes"], np.int64)
        if (parts < 1).any() or (psize < 1).any():
            raise ValueError("the reference expects no empty records or parts")
        part_end = np.cumsum(psize)
        rec_end = part_end[np.cumsum(parts) - 1]
        self.vcount = np.diff(np.concatenate([[0], rec_end]))
        self.vstart = rec_end - self.vcount
        self.n = len(self.vcount)
        s = self.vstart
        self.xmin = np.minimum.reduceat(self.x, s)
        self.xmax = np.maximum.reduceat(self.x, s)
        self.ymin = np.minimum.reduceat(self.y, s)
        self.ymax = np.maximum.reduceat(self.y, s)
        x64 = coords[:, 0].astype(np.float64)
        y64 = coords[:, 1].astype(np.float64)
        self.cx = np.add.reduceat(x64, s) / self.vcount
        self.cy = np.add.reduceat(y64, s) / self.vcount
        # level slots: 0 opens a record, 2 opens a part, 3 continues one
        rep = np.full(len(self.x), 3, np.uint8)
        rep[part_end - psize] = 2
        rep[self.vstart] = 0
        self.rep = rep
        self.types = np.asarray(data["types"], np.uint8)
        self.extras = data["extras"]
        self.id_column = cfg["id_column"]
        ids = np.asarray(self.extras[self.id_column])
        if not np.array_equal(ids, np.arange(self.n)):
            raise ValueError(f"{self.id_column} must be the generation index")

    @property
    def n_values(self) -> int:
        return len(self.x)

    def mask(self, bbox, pred=None) -> np.ndarray:
        """Records in the answer of ``bbox`` ∧ ``pred`` (pred = (column,
        lo, hi), closed)."""
        x0, y0, x1, y1 = (self.dtype.type(v) for v in bbox)
        m = ((self.xmin <= x1) & (self.xmax >= x0)
             & (self.ymin <= y1) & (self.ymax >= y0))
        if pred is not None:
            col, lo, hi = pred
            v = np.asarray(self.extras[col])
            m &= (v >= lo) & (v <= hi)
        return m

    def answer(self, bbox, pred=None) -> tuple[Answer, dict]:
        """The answer itself, in generation order, as the lake's columns."""
        recs = np.flatnonzero(self.mask(bbox, pred))
        iv = ragged_ranges(self.vstart[recs], self.vcount[recs])
        geo = Answer(self.types[recs], np.zeros(len(recs), np.uint8),
                     self.rep[iv], np.ones(len(iv), np.uint8),
                     self.x[iv].astype(np.float64), self.y[iv].astype(np.float64))
        return geo, {k: np.asarray(v)[recs] for k, v in self.extras.items()}


def compare(ref: Records, expected: np.ndarray, geo, extras: dict) -> dict:
    """Counts by which one answer departs from the reference's.

    ``expected`` is the reference's record mask; ``geo``/``extras`` are the
    answer under test (``geo`` may be None for an empty answer). Records
    are matched by id; a matched record is compared slot by slot (type,
    levels, both coordinates bit for bit) and on every other attribute.
    """
    out = dict.fromkeys(CHECKS, 0)
    n_exp = int(expected.sum())
    if geo is None:
        n_ids = len(extras.get(ref.id_column, ()))
        out["records_missing"] = n_exp
        out["records_extra"] = n_ids
        return out
    rep = np.asarray(geo.rep)
    starts = np.flatnonzero(rep == 0)
    ids = np.asarray(extras.get(ref.id_column, np.zeros(0, np.int64)))
    n_p = len(starts)
    if (len(ids) != n_p or len(geo.types) != n_p or len(geo.type_rep) != n_p
            or len(geo.defn) != len(rep) or len(geo.x) != len(rep)
            or len(geo.y) != len(rep)):
        # the answer's own columns disagree on its record count
        out["levels_wrong"] = max(n_p, len(ids), 1)
        out["records_missing"] = n_exp
        return out
    inrange = (ids >= 0) & (ids < ref.n)
    safe = np.where(inrange, ids, 0)
    first = np.zeros(n_p, bool)
    _, at = np.unique(np.where(inrange, ids, -1), return_index=True)
    first[at] = True
    hit = inrange & expected[safe] & first
    out["records_extra"] = int(n_p - hit.sum())
    out["records_missing"] = n_exp - int(hit.sum())

    nslots = np.diff(np.append(starts, len(rep)))
    g = safe[hit]
    same = nslots[hit] == ref.vcount[g]
    typ_ok = (np.asarray(geo.types)[hit] == ref.types[g]) & (
        np.asarray(geo.type_rep)[hit] == 0)
    gs, ps = g[same], starts[hit][same]
    iv_ref = ragged_ranges(ref.vstart[gs], ref.vcount[gs])
    iv_got = ragged_ranges(ps, nslots[hit][same])
    lvl_bad = ((rep[iv_got] != ref.rep[iv_ref])
               | (np.asarray(geo.defn)[iv_got] != 1))
    rec_of = np.repeat(np.arange(len(gs)), ref.vcount[gs])
    bad_rec = np.zeros(len(gs), bool)
    bad_rec[rec_of[lvl_bad]] = True
    out["levels_wrong"] = int((~same).sum() + (~typ_ok[same] | bad_rec).sum())

    gx = np.asarray(geo.x)
    gy = np.asarray(geo.y)
    if gx.dtype != np.float64 or gy.dtype != np.float64:
        out["coords_wrong"] = len(iv_got)
    else:
        rx = ref.x[iv_ref].astype(np.float64)
        ry = ref.y[iv_ref].astype(np.float64)
        out["coords_wrong"] = int(((_bits(gx[iv_got]) != _bits(rx))
                                   | (_bits(gy[iv_got]) != _bits(ry))).sum())

    for k, want in ref.extras.items():
        if k == ref.id_column:
            continue
        got = extras.get(k)
        want = np.asarray(want)
        if got is None or len(got) != n_p or np.asarray(got).dtype != want.dtype:
            out["attrs_wrong"] += int(hit.sum())
            continue
        out["attrs_wrong"] += int(
            (_bits(np.asarray(got)[hit]) != _bits(want[g])).sum())
    return out
