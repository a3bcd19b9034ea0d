"""The traffic generators: deterministic from the seed, with the same work
for every seed, and selectivities near their targets."""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import gen, harness, reference

BENCH = Path(__file__).resolve().parents[1]


def _records(config: str, n: int):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["n_records"] = n
    mod = harness.load_module(BENCH / "configs" / f"{config}.py")
    return cfg, reference.Records(mod.generate(cfg, 2**31 + 11), cfg)


def _mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def pt():
    return _records("pt_taxi", 40_000)


def test_generators_are_deterministic(pt):
    cfg, rec = pt
    data = harness.load_module(BENCH / "configs" / "pt_taxi.py").generate(cfg, 5)
    again = harness.load_module(BENCH / "configs" / "pt_taxi.py").generate(cfg, 5)
    assert np.array_equal(data["coords"], again["coords"])
    mix = _mix("scan_mixed")
    a = gen.scan_queries(rec, cfg, mix, np.random.default_rng([7, 2]), 120)
    b = gen.scan_queries(rec, cfg, mix, np.random.default_rng([7, 2]), 120)
    c = gen.scan_queries(rec, cfg, mix, np.random.default_rng([8, 2]), 120)
    assert a == b and a != c


def test_every_seed_gets_the_same_work(pt):
    cfg, rec = pt
    mix = _mix("scan_mixed")
    block = sum(c["count"] for c in mix["block"])
    comp = []
    for seed in (1, 2**31 + 5):
        qs = gen.scan_queries(rec, cfg, mix, np.random.default_rng([seed, 2]),
                              3 * block)
        comp.append(sorted((q.selectivity, q.pred is not None) for q in qs))
    assert comp[0] == comp[1]


def test_eb_hotspot_sizes_are_the_same_for_every_seed():
    """Every seed clusters the same number of points into each hotspot;
    only where the hotspots lie changes."""
    cfg = json.loads((BENCH / "configs" / "eb_points.json").read_text())
    cfg["n_records"] = 20_000
    mod = harness.load_module(BENCH / "configs" / "eb_points.py")
    sizes = mod.hotspot_sizes(20_000)
    assert sizes.sum() == 20_000 and (np.diff(sizes) >= 0).all()
    for seed in (1, 2**31 + 5):
        coords = mod.generate(cfg, seed)["coords"]
        # the hotspots' centres are the generator's first draws
        rng = np.random.default_rng([seed, 0])
        hots = np.stack([rng.uniform(mod.US_BBOX[0], mod.US_BBOX[2],
                                     mod.N_HOTSPOTS),
                         rng.uniform(mod.US_BBOX[1], mod.US_BBOX[3],
                                     mod.N_HOTSPOTS)], 1)
        # the three largest blobs (sigma 0.01 degrees) hold their sizes
        for i in (-1, -2, -3):
            near = np.abs(coords - hots[i]).max(axis=1) < 0.06
            assert near.sum() == sizes[i]


@pytest.mark.parametrize("target", [0.01, 0.1])
def test_scan_boxes_hold_their_target_share(pt, target):
    cfg, rec = pt
    mix = dict(_mix("scan_mixed"),
               block=[{"selectivity": target, "count": 1, "filtered": 0}])
    qs = gen.scan_queries(rec, cfg, mix, np.random.default_rng([3, 2]), 40)
    shares = []
    for q in qs:
        x0, y0, x1, y1 = q.bbox
        shares.append(np.mean((rec.cx >= x0) & (rec.cx <= x1)
                              & (rec.cy >= y0) & (rec.cy <= y1)))
    assert 0.7 * target < np.median(shares) < 1.3 * target
