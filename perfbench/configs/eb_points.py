"""Data of the ``eb_points`` configuration: eBird-like point observations.

Adapted from ``repro.data.synthetic.ebird_like`` (hotspot clustering,
unsorted at source) and kept here so that a later change to the program
cannot move the yardstick. One change: the original draws its hotspots'
Pareto weights anew for every seed, so the seed changed how the points
cluster and with it the work of every scan. Here the hotspot sizes are the
quantiles of the same 1 + Pareto(1.2), split by largest remainders: every
seed gets the same sizes, and only where the hotspots lie, which points
fall to which and their order change with the seed.
"""

from __future__ import annotations

import numpy as np

US_BBOX = (-124.0, 25.0, -67.0, 49.0)
TYPE_POINT = 1
N_HOTSPOTS = 2000
PARETO_SHAPE = 1.2


def hotspot_sizes(n: int) -> np.ndarray:
    """Points per hotspot: ``n`` split in proportion to the quantiles of
    1 + Pareto(``PARETO_SHAPE``) at ``(i + 0.5) / N_HOTSPOTS``."""
    u = (np.arange(N_HOTSPOTS) + 0.5) / N_HOTSPOTS
    w = (1.0 - u) ** (-1.0 / PARETO_SHAPE)
    raw = w / w.sum() * n
    sizes = np.floor(raw).astype(np.int64)
    short = n - int(sizes.sum())
    sizes[np.argsort(raw - sizes, kind="stable")[::-1][:short]] += 1
    return sizes


def generate(cfg: dict, seed: int) -> dict:
    """Points and their attributes from ``seed`` (see ``pt_taxi.generate``
    for the returned arrays)."""
    n = int(cfg["n_records"])
    rng = np.random.default_rng([seed, 0])
    hots = np.stack([rng.uniform(US_BBOX[0], US_BBOX[2], N_HOTSPOTS),
                     rng.uniform(US_BBOX[1], US_BBOX[3], N_HOTSPOTS)], 1)
    hid = np.repeat(np.arange(N_HOTSPOTS), hotspot_sizes(n))
    coords = np.round(hots[hid] + rng.normal(0, 0.01, (n, 2)), 6)
    coords = coords[rng.permutation(n)]

    arng = np.random.default_rng([seed, 1])
    extras = {
        "count": arng.geometric(0.3, n).astype(np.int32),
        "obs_id": np.arange(n, dtype=np.int64),
    }
    return {
        "types": np.full(n, TYPE_POINT, np.uint8),
        "coords": coords,
        "part_sizes": np.ones(n, np.int64),
        "parts_per_record": np.ones(n, np.int64),
        "extras": extras,
    }
