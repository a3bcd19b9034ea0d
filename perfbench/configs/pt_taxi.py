"""Data of the ``pt_taxi`` configuration: Porto-taxi-like MultiPoint trips.

A copy of ``repro.data.synthetic.porto_taxi_like`` as of this benchmark's
first version, kept here so that a later change to the program cannot move
the yardstick. It returns plain numpy arrays; the harness hands them to the
program's writer.
"""

from __future__ import annotations

import numpy as np

PORTO_BBOX = (-8.70, 41.10, -8.50, 41.25)
TYPE_MULTIPOINT = 4


def generate(cfg: dict, seed: int) -> dict:
    """Trips, their points and per-trip attributes from ``seed``.

    Returns ``types`` (one per record), ``coords`` (n_values, 2) float64,
    ``part_sizes`` (one point per part), ``parts_per_record`` and ``extras``.
    """
    n = int(cfg["n_records"])
    mean_pts = int(cfg["mean_points"])
    rng = np.random.default_rng([seed, 0])
    npts = rng.poisson(mean_pts, n).clip(2, 4 * mean_pts)
    total = int(npts.sum())
    x0 = rng.uniform(PORTO_BBOX[0], PORTO_BBOX[2], n)
    y0 = rng.uniform(PORTO_BBOX[1], PORTO_BBOX[3], n)
    # ~15 m GPS steps at ~1e-4 degrees
    steps = rng.normal(0, 1.5e-4, (total, 2))
    traj = np.repeat(np.arange(n), npts)
    first = np.concatenate([[0], np.cumsum(npts)[:-1]])
    steps[first] = 0.0
    walk = np.cumsum(steps, axis=0)
    walk -= np.repeat(walk[first], npts, axis=0)
    coords = np.round(np.stack([x0[traj], y0[traj]], 1) + walk, 6)

    arng = np.random.default_rng([seed, 1])
    extras = {
        "speed": arng.gamma(4.0, 6.0, n).astype(np.float32),
        "stand": arng.integers(0, 64, n).astype(np.int32),
        "trip_id": np.arange(n, dtype=np.int64),
    }
    return {
        "types": np.full(n, TYPE_MULTIPOINT, np.uint8),
        "coords": coords,
        "part_sizes": np.ones(total, np.int64),
        "parts_per_record": npts.astype(np.int64),
        "extras": extras,
    }
