"""Exact box-intersects scans (``scan(bbox, refine=True, exact=True)``)
against a small hand-written checker in rationals.

The checker knows nothing of the program's stages: per record it tests
every vertex, clips every edge against the box (Liang–Barsky in
``Fraction``) and, for polygons, counts the crossings of the rightward ray
from the box's corner ``(x0, y0)``. The host executor (``device="cpu"``)
and the device path (``device="jax"``, interpret mode here) must both give
its answers.
"""

import importlib.util
from fractions import Fraction
from pathlib import Path

import numpy as np
import pytest

from repro.core import exact as host_exact
from repro.core.columnar import from_ragged
from repro.core.geometry import (
    TYPE_LINESTRING,
    TYPE_MULTIPOLYGON,
    TYPE_POLYGON,
    Geometry,
)
from repro.core.filters import Range
from repro.core.reader import _bbox_keep_mask
from repro.data.synthetic import ebird_like, porto_taxi_like
from repro.dataset import SpatialDatasetScanner, write_dataset

from geom_helpers import random_geometry

POLYGONS = (TYPE_POLYGON, TYPE_MULTIPOLYGON)
BOX = (0.0, 0.0, 10.0, 10.0)


# ------------------------------------------------------------ the checker
def _segment_meets(p, q, box) -> bool:
    """Liang–Barsky: some t in [0, 1] puts p + t (q - p) in the closed box."""
    x0, y0, x1, y1 = box
    dx, dy = q[0] - p[0], q[1] - p[1]
    t0, t1 = Fraction(0), Fraction(1)
    for a, b in ((-dx, p[0] - x0), (dx, x1 - p[0]),
                 (-dy, p[1] - y0), (dy, y1 - p[1])):
        if a == 0:
            if b < 0:
                return False
        elif a < 0:
            t0 = max(t0, b / a)
        else:
            t1 = min(t1, b / a)
    return t0 <= t1


def hand_intersects(g: Geometry, bbox) -> bool:
    subs = g.sub_geometries or [g]
    box = tuple(Fraction(float(v)) for v in bbox)
    x0, y0, x1, y1 = box
    rings = [(s.geom_type in POLYGONS, p) for s in subs for p in s.parts]
    if any(not np.isfinite(p).all() for _, p in rings):
        return False
    rings = [(poly, [(Fraction(float(a)), Fraction(float(b))) for a, b in p])
             for poly, p in rings]
    if any(x0 <= x <= x1 and y0 <= y <= y1 for _, r in rings for x, y in r):
        return True
    edges = []
    for poly, r in rings:
        edges += [(poly, p, q) for p, q in zip(r[:-1], r[1:])]
        if poly and len(r) > 1 and r[-1] != r[0]:
            edges.append((poly, r[-1], r[0]))
    if any(_segment_meets(p, q, box) for _, p, q in edges):
        return True
    crossings = 0
    for poly, p, q in edges:
        if poly and (p[1] > y0) != (q[1] > y0):
            xc = p[0] + (y0 - p[1]) * (q[0] - p[0]) / (q[1] - p[1])
            crossings += xc > x0
    return crossings % 2 == 1


def mbr_meets(g: Geometry, bbox) -> bool:
    subs = g.sub_geometries or [g]
    pts = [p for s in subs for p in s.parts]
    if not pts or not sum(len(p) for p in pts):
        return False
    allc = np.vstack(pts)
    if np.isnan(allc).any():
        return False
    x0, y0, x1, y1 = bbox
    return bool(allc[:, 0].min() <= x1 and allc[:, 0].max() >= x0
                and allc[:, 1].min() <= y1 and allc[:, 1].max() >= y0)


# ------------------------------------------------------------- the lakes
def _lake(tmp_path, geoms, name="lake", **kw):
    root = tmp_path / name
    kw.setdefault("n_shards", 1)
    write_dataset(root, geometries=geoms,
                  extra={"gid": np.arange(len(geoms), dtype=np.int64)}, **kw)
    return SpatialDatasetScanner(root)


def _ids(sc, bbox, device, exact=True, **kw):
    _, extras, _ = sc.scan(bbox, refine=True, device=device, exact=exact, **kw)
    return sorted(int(v) for v in extras.get("gid", []))


def _rect(x0, y0, x1, y1):
    return [(x0, y0), (x0, y1), (x1, y1), (x1, y0)]


CASES = {
    # name: (geometry, answers the exact query on BOX)
    "vertex_on_box_edge": (Geometry.polygon(_rect(10, 4, 14, 8)), True),
    "vertex_on_box_corner": (Geometry.polygon(_rect(10, 10, 12, 12)), True),
    "edge_across_no_vertex_inside": (Geometry.polygon(_rect(-2, 4, 12, 5)),
                                     True),
    "edge_through_corner_only": (
        Geometry.polygon([(-3, 7), (3, 13), (-3, 13)]), True),
    "edge_past_corner": (Geometry.polygon([(-3, 7.5), (2.5, 13), (-3, 13)]),
                         False),
    "box_inside_shell": (Geometry.polygon(_rect(-5, -5, 15, 15)), True),
    "box_inside_hole": (Geometry.polygon(_rect(-10, -10, 20, 20),
                                         [_rect(-5, -5, 15, 15)]), False),
    "l_shape_around_box": (Geometry.polygon(
        [(-10, -10), (-10, 20), (-1, 20), (-1, -1), (20, -1), (20, -10)]),
        False),
    "unclosed_ring_closing_edge_crosses": (
        Geometry(TYPE_POLYGON, [np.array([(-2.0, 12.0), (-2.0, -2.0),
                                          (12.0, -2.0)])]), True),
    "same_points_as_line_string": (
        Geometry(TYPE_LINESTRING, [np.array([(-2.0, 12.0), (-2.0, -2.0),
                                             (12.0, -2.0)])]), False),
    "multipolygon_box_in_second": (Geometry.multipolygon(
        [_rect(-20, -20, -15, -15), _rect(-5, -5, 15, 15)]), True),
    "multipolygon_around_box": (Geometry.multipolygon(
        [_rect(-20, -20, -15, -15), _rect(20, 20, 25, 25)]), False),
    "multilinestring_crossing": (Geometry.multilinestring(
        [[(-5, 5), (-1, 5)], [(5, -5), (5, 15)]]), True),
    "multilinestring_around_box": (Geometry.multilinestring(
        [[(-5, -5), (-5, 15)], [(15, -5), (15, 15)]]), False),
    "multipoint_around_box": (Geometry.multipoint([(-1, -1), (11, 11)]),
                              False),
}


@pytest.fixture(scope="module")
def case_lake(tmp_path_factory):
    geoms = [g for g, _ in CASES.values()]
    return _lake(tmp_path_factory.mktemp("cases"), geoms)


@pytest.mark.parametrize("name", sorted(CASES))
def test_hand_case(case_lake, name):
    g, want = CASES[name]
    assert hand_intersects(g, BOX) == want
    i = list(CASES).index(name)
    for device in ("cpu", "jax"):
        assert (i in _ids(case_lake, BOX, device)) == want, device


def test_hand_cases_mbr_answers_unchanged(case_lake):
    """Without ``exact`` the same lake answers by MBR, as before."""
    want = [i for i, (g, _) in enumerate(CASES.values()) if mbr_meets(g, BOX)]
    for device in ("cpu", "jax"):
        assert _ids(case_lake, BOX, device, exact=False) == want
    dropped = set(want) - set(_ids(case_lake, BOX, "cpu"))
    assert {list(CASES)[i] for i in dropped} >= {
        "edge_past_corner", "box_inside_hole", "l_shape_around_box",
        "multipolygon_around_box", "multipoint_around_box"}


def test_non_finite_records_never_answer(tmp_path):
    """An infinite coordinate drops its record under exact only (an MBR may
    reach infinity); a NaN one drops it under both (a NaN makes the page's
    statistics, and so the page, miss every box, so the stages are checked
    on their own for it)."""
    from repro.core.reader import SpatialParquetReader
    from repro.core.writer import write_file
    from repro.kernels import exact as dev_exact

    geoms = [Geometry.linestring([(5, 5), (np.inf, 5)]),
             Geometry.polygon(_rect(1, 1, 2, 2))]
    assert [hand_intersects(g, BOX) for g in geoms] == [False, True]
    path = tmp_path / "f.spqf"
    write_file(path, geometries=geoms, sort=None,
               extra={"gid": np.arange(2, dtype=np.int64)},
               extra_schema={"gid": "<i8"})
    with SpatialParquetReader(path) as r:
        for device in ("cpu", "jax"):
            got = {exact: r.read_columnar(bbox=BOX, refine=True, device=device,
                                          exact=exact)[1]["gid"].tolist()
                   for exact in (False, True)}
            assert got == {False: [0, 1], True: [1]}, device

    nan_poly = Geometry.polygon([(1, 1), (2, np.nan), (2, 2)])
    assert not hand_intersects(nan_poly, BOX)
    ring = nan_poly.parts[0]
    off, poly = host_exact.edge_partners(
        np.array([0, 3, 3, 3], np.uint8), np.ones(4, np.uint8),
        np.array([TYPE_POLYGON], np.uint8))
    keep, _, _ = host_exact.exact_keep(ring[:, 0], ring[:, 1], [4], off, poly,
                                       BOX)
    assert not keep.any()
    import jax.numpy as jnp

    bits = np.concatenate([ring[:, 0], ring[:, 1]]).view(np.uint64)
    cls, vertex = dev_exact.classify_device(
        jnp.asarray((bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray((bits >> np.uint64(32)).astype(np.uint32)),
        np.arange(4), 4 + np.arange(4), off, poly, [4], BOX, 64)
    assert cls.tolist() == [host_exact.OUT] and not vertex.any()


def test_exact_needs_refine(case_lake):
    with pytest.raises(ValueError, match="refine"):
        case_lake.scan(BOX, exact=True)


@pytest.mark.parametrize("seed", [0, 1])
def test_random_geometries_match_checker(tmp_path, seed):
    """Random geometries of every kind (collections and empties too), boxes
    drawn around them and boxes whose sides pass through vertices."""
    rng = np.random.default_rng(seed)
    geoms = [random_geometry(rng) for _ in range(150)]
    sc = _lake(tmp_path, geoms, page_values=256)
    verts = np.vstack([p for g in geoms for s in (g.sub_geometries or [g])
                       for p in s.parts])
    boxes = []
    for _ in range(12):
        c = rng.normal(0, 20, 2)
        h = rng.uniform(0.5, 15, 2)
        boxes.append((c[0] - h[0], c[1] - h[1], c[0] + h[0], c[1] + h[1]))
    for _ in range(8):
        a, b = verts[rng.integers(len(verts), size=2)]
        boxes.append((min(a[0], b[0]), min(a[1], b[1]),
                      max(a[0], b[0]), max(a[1], b[1])))
    for bbox in boxes:
        want = [i for i, g in enumerate(geoms) if hand_intersects(g, bbox)]
        assert _ids(sc, bbox, "cpu") == want, bbox
        assert _ids(sc, bbox, "jax") == want, bbox


def _mb_generator():
    path = (Path(__file__).resolve().parents[1] / "perfbench" / "configs"
            / "mb_buildings.py")
    spec = importlib.util.spec_from_file_location("mb_buildings_gen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def mb_lake(tmp_path_factory):
    """A seeded tiny MB lake: 2,000 building footprints in the benchmark's
    shape mix (rectangles, L-shapes, courtyards)."""
    data = _mb_generator().generate({"n_records": 2000}, 2**31 + 3)
    cols = from_ragged(data["types"], data["coords"], data["part_sizes"],
                       data["parts_per_record"])
    root = tmp_path_factory.mktemp("mb") / "lake"
    write_dataset(root, columns=cols, extra={"gid": data["extras"]["bldg_id"]},
                  n_shards=2, page_values=1024)
    rings = np.split(data["coords"], np.cumsum(data["part_sizes"])[:-1])
    first = np.cumsum(data["parts_per_record"]) - data["parts_per_record"]
    geoms = [Geometry(TYPE_POLYGON, rings[f:f + k])
             for f, k in zip(first, data["parts_per_record"])]
    return SpatialDatasetScanner(root), geoms


def test_tiny_mb_lake_cpu_jax_checker_agree(mb_lake):
    sc, geoms = mb_lake
    rng = np.random.default_rng(7)
    verts = np.vstack([p for g in geoms for p in g.parts])
    dropped = 0
    for k in range(24):
        c = verts[rng.integers(len(verts))]
        h = rng.uniform(2e-4, 3e-3)
        bbox = (c[0] - h, c[1] - h, c[0] + h, c[1] + h)
        if k % 3 == 0:  # a corner on a vertex, the box past it
            bbox = (c[0], c[1], c[0] + h, c[1] + h)
        elif k % 3 == 1:  # a corner just inside a footprint's MBR corner
            b = geoms[rng.integers(len(geoms))].parts[0]
            lo = b.min(axis=0) + 2e-7
            bbox = (lo[0] - h, lo[1] - h, lo[0], lo[1])
        want = [i for i, g in enumerate(geoms) if hand_intersects(g, bbox)]
        mbr = [i for i, g in enumerate(geoms) if mbr_meets(g, bbox)]
        assert _ids(sc, bbox, "cpu") == want
        assert _ids(sc, bbox, "jax") == want
        assert _ids(sc, bbox, "jax", exact=False) == mbr
        dropped += len(mbr) - len(want)
    assert dropped > 0


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("filtered", [False, True])
def test_host_and_device_stats_agree(mb_lake, filtered, exact):
    """On a polygon lake with holes (courtyards) both executors return the
    same records and the same ``ReadStats``, every field, with and without
    a ``gid`` range and ``exact``."""
    sc, geoms = mb_lake
    verts = np.vstack([p for g in geoms for p in g.parts])
    lo, hi = np.quantile(verts, 0.3, axis=0), np.quantile(verts, 0.7, axis=0)
    bbox = (lo[0], lo[1], hi[0], hi[1])
    pred = Range("gid", len(geoms) // 4, 3 * len(geoms) // 4) if filtered else None
    host, dev = (sc.scan(bbox, refine=True, device=device, filter=pred,
                         exact=exact) for device in ("cpu", "jax"))
    assert host[2] == dev[2]
    assert 0 < host[2].records_returned < host[2].records_scanned
    assert host[2].pages_read < host[2].pages_total
    assert (host[0].rep == 2).any()  # a returned polygon has a hole
    for f in ("types", "type_rep", "rep", "defn"):
        assert np.array_equal(getattr(host[0], f), getattr(dev[0], f)), f
    for f in ("x", "y"):
        assert np.array_equal(getattr(host[0], f).view(np.int64),
                              getattr(dev[0], f).view(np.int64)), f
    assert np.array_equal(host[1]["gid"], dev[1]["gid"])


@pytest.mark.parametrize("make", [porto_taxi_like, ebird_like],
                         ids=["pt", "eb"])
def test_mbr_answers_stay_bit_identical(tmp_path, make):
    """PT and eB lakes: the default scan is the MBR scan, bit for bit on
    both executors; the exact scan of their points is the vertex test."""
    cols = make(3000, seed=4) if make is porto_taxi_like else make(6000, seed=4)
    root = tmp_path / "lake"
    write_dataset(root, columns=cols,
                  extra={"gid": np.arange(cols.n_records, dtype=np.int64)},
                  n_shards=2, page_values=2048)
    sc = SpatialDatasetScanner(root)
    cx, cy = float(np.median(cols.x)), float(np.median(cols.y))
    bbox = (cx - 0.02, cy - 0.02, cx + 0.02, cy + 0.02)
    starts = cols.record_value_starts()
    counts = np.diff(np.append(starts, cols.n_values))
    want = np.flatnonzero(_bbox_keep_mask(cols.x, cols.y, counts, bbox))
    inb = ((cols.x >= bbox[0]) & (cols.x <= bbox[2])
           & (cols.y >= bbox[1]) & (cols.y <= bbox[3]))
    vertex = np.flatnonzero(np.add.reduceat(inb, starts) > 0)
    ref = None
    for device in ("cpu", "jax"):
        geo, ex, _ = sc.scan(bbox, refine=True, device=device)
        geo2, ex2, _ = sc.scan(bbox, refine=True, device=device, exact=False)
        assert sorted(ex["gid"]) == list(want)
        assert np.array_equal(geo.x.view(np.int64), geo2.x.view(np.int64))
        assert np.array_equal(geo.rep, geo2.rep)
        if ref is not None:
            assert np.array_equal(geo.y.view(np.int64), ref.y.view(np.int64))
        ref = geo
        assert _ids(sc, bbox, device) == list(vertex)


# ------------------------------------------------- the stages themselves
def test_orient_sign_is_exact_near_degenerate():
    rng = np.random.default_rng(3)
    n = 4000
    p = rng.normal(0, 1, (n, 2)) * 10.0 ** rng.integers(-8, 9, (n, 1))
    d = rng.normal(0, 1, (n, 2))
    q = p + d
    # points on the line through p and q, nudged by an ulp or not at all
    t = rng.uniform(-2, 3, n)
    c = p + t[:, None] * d
    c[::3] = np.nextafter(c[::3], np.inf)
    c[1::3, 0] = p[1::3, 0]  # some on a vertex
    c[1::3, 1] = p[1::3, 1]
    want = []
    for i in range(n):
        P = [Fraction(float(v)) for v in p[i]]
        Q = [Fraction(float(v)) for v in q[i]]
        C = [Fraction(float(v)) for v in c[i]]
        det = (Q[0] - P[0]) * (C[1] - P[1]) - (Q[1] - P[1]) * (C[0] - P[0])
        want.append((det > 0) - (det < 0))
    got = np.array([host_exact.orient_sign(p[i:i + 1, 0], p[i:i + 1, 1],
                                           q[i:i + 1, 0], q[i:i + 1, 1],
                                           c[i, 0], c[i, 1])[0]
                    for i in range(n)])
    assert np.array_equal(got, np.array(want))
    assert (np.array(want) == 0).sum() > 100


@pytest.mark.parametrize("width", [32, 64])
def test_device_stage_matches_host_classify(width):
    """The device stage's classes equal the host oracle's bit for bit,
    NaNs, infinities and signed zeros included."""
    import jax.numpy as jnp

    from repro.kernels import exact as dev_exact

    rng = np.random.default_rng(width)
    dtype = np.float32 if width == 32 else np.float64
    n_rec = 400
    counts = rng.integers(1, 9, n_rec)
    n = int(counts.sum())
    x = rng.choice([-1.0, -0.0, 0.0, 0.5, 1.0, 2.0, 3.0], n).astype(dtype)
    y = rng.choice([-1.0, -0.0, 0.0, 0.5, 1.0, 2.0, 3.0], n).astype(dtype)
    x = x + rng.normal(0, 0.3, n).astype(dtype) * (rng.random(n) < 0.5)
    x[rng.random(n) < 0.01] = np.nan
    y[rng.random(n) < 0.01] = np.inf
    # one part per record: polygon rings on even records, lines on odd
    rep = np.full(n, 3, np.uint8)
    rep[np.cumsum(counts) - counts] = 0
    types = np.where(np.arange(n_rec) % 2 == 0, TYPE_POLYGON,
                     TYPE_LINESTRING).astype(np.uint8)
    off, poly = host_exact.edge_partners(rep, np.ones(n, np.uint8), types)
    bits = np.concatenate([x, y]).view(np.uint32 if width == 32 else np.uint64)
    if width == 64:
        lo, hi = bits & np.uint64(0xFFFFFFFF), bits >> np.uint64(32)
    else:
        lo, hi = bits, np.zeros_like(bits)
    lo = jnp.asarray(lo.astype(np.uint32))
    hi = jnp.asarray(hi.astype(np.uint32))
    classes = set()
    for bbox in [(0.0, 0.0, 2.0, 2.0), (-0.0, -0.0, 0.0, 0.0),
                 (0.2, 0.7, 1.3, 2.9), (5.0, 5.0, 6.0, 6.0)]:
        want = host_exact.classify(x, y, counts, off, poly, bbox)
        got = dev_exact.classify_device(lo, hi, np.arange(n), n + np.arange(n),
                                        off, poly, counts, bbox, width)
        assert np.array_equal(got[0], want[0]), bbox
        assert np.array_equal(got[1], want[1]), bbox
        classes |= set(want[0].tolist())
    assert classes == {host_exact.OUT, host_exact.IN, host_exact.UNDECIDED}


def test_edge_partners_rings_lines_and_empties():
    # polygon (shell 4 + hole 3), linestring of 3, empty, multipoint of 2
    rep = np.array([0, 3, 3, 3, 2, 3, 3, 0, 3, 3, 0, 0, 2], np.uint8)
    defn = np.array([1] * 10 + [0, 1, 1], np.uint8)
    types = np.array([TYPE_POLYGON, TYPE_LINESTRING, 0, 4], np.uint8)
    off, poly = host_exact.edge_partners(rep, defn, types)
    assert off.tolist() == [1, 1, 1, -3, 1, 1, -2, 1, 1, 0, 0, 0]
    assert poly.tolist() == [True] * 7 + [False] * 5


def test_query_server_refuses_exact(case_lake):
    """The server answers by MBR only; it refuses exact queries outright
    and still takes the MBR ones."""
    from repro.serve.query_scheduler import SpatialQueryServer

    with SpatialQueryServer(case_lake, device="cpu") as srv:
        with pytest.raises(ValueError, match="MBR only"):
            srv.submit(BOX, exact=True)
        assert not srv.pending
        q = srv.submit(BOX)
        srv.run()
    want = [i for i, (g, _) in enumerate(CASES.values()) if mbr_meets(g, BOX)]
    assert sorted(q.extras["gid"].tolist()) == want
