"""Data of the ``mb_buildings`` configuration: building footprints as
Polygons, after Microsoft's US Building Footprints (the paper's MB).

Adapted from ``repro.data.synthetic.buildings_like`` and kept here so that
a later change to the program cannot move the yardstick. Towns over a US
extent hold buildings on a jittered street grid; town sizes are the
quantiles of 1 + Pareto(1.2), split by largest remainders as
``eb_points`` splits its hotspots, and the shape mix is split the same
way, so every seed writes the same number of buildings, rings and points
into towns of the same sizes. The towns and their street grids stand
where one fixed map puts them, as the published footprints have one map:
the Hilbert shards then cut the same towns in every run. The seed moves
the buildings on that map: each lot's jitter, the footprints' sizes and
angles, which building takes which shape, and the heights.

Shapes (shells clockwise, holes counter-clockwise, every ring closed):
rectangles (5 points), L-shapes (7 points: a rectangle with one corner
notched out) and rectangles with one rectangular courtyard hole (5 + 5
points). Coordinates are rounded to 6 decimals, as the published
GeoJSON is.
"""

from __future__ import annotations

import numpy as np

US_BBOX = (-124.0, 25.0, -67.0, 49.0)
TYPE_POLYGON = 3
N_TOWNS = 800
MAP_SEED = 0                     # the stream of the towns and their grids
PARETO_SHAPE = 1.2
M_PER_DEG = 111_320.0
# (rectangle, L-shape, courtyard) shares of the buildings
SHAPE_SHARES = (0.77, 0.20, 0.03)
RING_POINTS = (5, 7, 5)          # points of each shape's shell
SPACING_M = (25.0, 40.0)         # street-grid lot spacing of a town
SIDE_M = (8.0, 30.0)             # footprint sides
LOT_JITTER_M = 2.0
ANGLE_JITTER_DEG = 3.0


def _largest_remainders(weights: np.ndarray, n: int) -> np.ndarray:
    raw = weights / weights.sum() * n
    sizes = np.floor(raw).astype(np.int64)
    short = n - int(sizes.sum())
    sizes[np.argsort(raw - sizes, kind="stable")[::-1][:short]] += 1
    return sizes


def town_sizes(n: int) -> np.ndarray:
    """Buildings per town: ``n`` split in proportion to the quantiles of
    1 + Pareto(``PARETO_SHAPE``) at ``(i + 0.5) / N_TOWNS``."""
    u = (np.arange(N_TOWNS) + 0.5) / N_TOWNS
    return _largest_remainders((1.0 - u) ** (-1.0 / PARETO_SHAPE), n)


def town_map() -> tuple[np.ndarray, ...]:
    """The towns' centres (longitude, latitude: uniform over the US
    extent), lot spacings (metres) and grid angles (radians), the same for
    every seed."""
    rng = np.random.default_rng(MAP_SEED)
    return (rng.uniform(US_BBOX[0], US_BBOX[2], N_TOWNS),
            rng.uniform(US_BBOX[1], US_BBOX[3], N_TOWNS),
            rng.uniform(*SPACING_M, N_TOWNS),
            rng.uniform(0.0, np.pi / 2, N_TOWNS))


def shape_counts(n: int) -> np.ndarray:
    """Rectangles, L-shapes and courtyards among ``n`` buildings."""
    return _largest_remainders(np.asarray(SHAPE_SHARES), n)


def _lot_order(k: int) -> np.ndarray:
    """Grid offsets (i, j) of the ``k`` lots nearest a town's centre, in
    order of distance (ties broken by angle)."""
    r = int(np.ceil(np.sqrt(k) / 2)) + 1
    g = np.arange(-r, r + 1)
    i, j = (a.ravel() for a in np.meshgrid(g, g, indexing="ij"))
    order = np.lexsort((np.arctan2(j, i), i * i + j * j))
    return np.stack([i[order], j[order]], 1)[:k].astype(np.float64)


def _ring(*pts) -> np.ndarray:
    """(m, k, 2) rings from k (u, v) pairs of length-m arrays."""
    return np.stack([np.stack(np.broadcast_arrays(u, v), -1) for u, v in pts],
                    1)


def _shells(kind, a, b, rng):
    """Local shell vertices (metres, clockwise, closed) padded to 7."""
    pts = np.zeros((len(kind), 7, 2))
    pts[:, :5] = _ring((-a, b), (a, b), (a, -b), (-a, -b), (-a, b))
    ell = kind == 1
    m = int(ell.sum())
    if m:
        aa, bb = a[ell], b[ell]
        nx = rng.uniform(0.3, 0.6, m) * 2 * aa
        ny = rng.uniform(0.3, 0.6, m) * 2 * bb
        v = _ring((-aa, bb), (aa - nx, bb), (aa - nx, bb - ny),
                  (aa, bb - ny), (aa, -bb), (-aa, -bb), (-aa, bb))
        # the notched corner: k quarter turns, which keep the winding
        k = rng.integers(0, 4, m)
        for _ in range(3):
            turn = k > 0
            v[turn] = np.stack([-v[turn][..., 1], v[turn][..., 0]], -1)
            k = k - turn
        pts[ell] = v
    return pts


def generate(cfg: dict, seed: int) -> dict:
    """Footprints and per-building attributes from ``seed`` (see
    ``pt_taxi.generate`` for the returned arrays)."""
    n = int(cfg["n_records"])
    sizes = town_sizes(n)
    lon, lat, spacing, grid_angle = town_map()
    rng = np.random.default_rng([seed, 0])

    town = np.repeat(np.arange(N_TOWNS), sizes)
    rank = np.arange(n) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    lots = _lot_order(int(sizes.max()))[rank] * spacing[town][:, None]
    lots += rng.normal(0.0, LOT_JITTER_M, (n, 2))
    kind = np.repeat(np.arange(3), shape_counts(n))[rng.permutation(n)]
    side_max = np.minimum(SIDE_M[1], spacing[town] - 3.0)
    a = rng.uniform(SIDE_M[0], side_max) / 2
    b = rng.uniform(SIDE_M[0], side_max) / 2
    theta = grid_angle[town] + np.deg2rad(
        rng.normal(0.0, ANGLE_JITTER_DEG, n))

    shell = _shells(kind, a, b, rng)
    court = kind == 2
    c = rng.uniform(0.2, 0.35, n) * a
    d = rng.uniform(0.2, 0.35, n) * b
    hole = _ring((-c, -d), (c, -d), (c, d), (-c, d), (-c, -d))  # CCW
    local = np.concatenate([shell, hole], 1)                  # (n, 12, 2)
    slot_n = np.array(RING_POINTS)[kind]
    valid = np.zeros((n, 12), bool)
    valid[:, :7] = np.arange(7) < slot_n[:, None]
    valid[court, 7:] = True

    # rotate each building, place it on its lot, convert to degrees
    cs, sn = np.cos(theta)[:, None], np.sin(theta)[:, None]
    gx = np.cos(grid_angle[town])[:, None]
    gy = np.sin(grid_angle[town])[:, None]
    lx = lots[:, :1] * gx - lots[:, 1:] * gy
    ly = lots[:, :1] * gy + lots[:, 1:] * gx
    mx = lx + local[..., 0] * cs - local[..., 1] * sn
    my = ly + local[..., 0] * sn + local[..., 1] * cs
    x = lon[town][:, None] + mx / (M_PER_DEG * np.cos(np.deg2rad(lat[town])))[:, None]
    y = lat[town][:, None] + my / M_PER_DEG
    coords = np.round(np.stack([x[valid], y[valid]], 1), 6)

    ring_sizes = np.stack([slot_n, np.full(n, 5)], 1)[
        np.stack([np.ones(n, bool), court], 1)]
    arng = np.random.default_rng([seed, 1])
    extras = {
        "height": (3.0 + arng.gamma(2.0, 2.5, n)).astype(np.float32),
        "bldg_id": np.arange(n, dtype=np.int64),
    }
    return {
        "types": np.full(n, TYPE_POLYGON, np.uint8),
        "coords": coords,
        "part_sizes": ring_sizes.astype(np.int64),
        "parts_per_record": np.where(court, 2, 1).astype(np.int64),
        "extras": extras,
    }
