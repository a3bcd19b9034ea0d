"""Telemetry of exact scans: the exact stage's spans nest where the reader
opens them, its counters add up to the answer, and with telemetry off the
answers are bit-identical to those with it on."""

import numpy as np
import pytest

from repro import obs
from repro.core.geometry import Geometry
from repro.dataset import SpatialDatasetScanner, write_dataset

BOX = (30.0, 30.0, 60.0, 60.0)
COUNTERS = ("exact.records_in", "exact.records_vertex",
            "exact.records_dropped", "exact.edges", "exact.edges_host")


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    """Rotated squares and triangles strewn over the box's neighbourhood,
    plus shapes whose edges pass by or through the box's corners."""
    rng = np.random.default_rng(11)
    geoms = []
    for _ in range(600):
        c = rng.uniform(0, 90, 2)
        r, a = rng.uniform(0.5, 4), rng.uniform(0, np.pi / 2)
        k = rng.integers(3, 5)
        ang = a + 2 * np.pi * np.arange(k) / k
        geoms.append(Geometry.polygon(c + r * np.stack([np.cos(ang),
                                                        np.sin(ang)], 1)))
    for x0, y0, sx, sy in ((30, 30, -1, -1), (60, 30, 1, -1),
                           (30, 60, -1, 1), (60, 60, 1, 1)):
        for d in (0.0, 0.5):  # through the corner, and past it
            geoms.append(Geometry.polygon(
                [(x0 + sx * 3, y0 - sy * (3 - d)), (x0 - sx * (3 - d),
                                                     y0 + sy * 3),
                 (x0 + sx * 3, y0 + sy * 3)]))
    root = tmp_path_factory.mktemp("exact_obs") / "lake"
    write_dataset(root, geometries=geoms, n_shards=2, page_values=512,
                  extra={"gid": np.arange(len(geoms), dtype=np.int64)})
    return root


def _traced(root, device):
    sc = SpatialDatasetScanner(root)
    tracer = obs.enable()
    try:
        out = sc.scan(BOX, refine=True, device=device, exact=True)
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
    return tracer, out, counters


def test_exact_spans_nest_in_the_launch(lake):
    tracer, _, _ = _traced(lake, "jax")
    ids = {e["args"]["span_id"]: e for e in tracer.spans()}
    kids: dict = {}
    for e in tracer.spans():
        kids.setdefault(e["args"]["parent_id"], []).append(e["name"])
    launches = tracer.spans("device.exact_launch")
    assert launches
    for e in launches:
        assert kids[e["args"]["span_id"]] == ["device.wait"]
        assert ids[e["args"]["parent_id"]]["name"] == "rg.launch"
    settled = tracer.spans("rg.exact")
    assert len(settled) == len(launches)
    assert {ids[e["args"]["parent_id"]]["name"] for e in settled} == {
        "rg.launch"}
    # the undecided records' coordinates come back inside rg.exact
    assert any("device.gather" in kids.get(e["args"]["span_id"], [])
               for e in settled)


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_exact_counters_add_up(lake, device):
    _, (geo, extras, _), c = _traced(lake, device)
    n = len(extras["gid"])
    assert n == geo.n_records > 0
    assert c["exact.records_in"] - c["exact.records_dropped"] == n
    assert 0 < c["exact.records_vertex"] <= n
    assert c["exact.records_dropped"] > 0
    assert c["exact.edges"] >= c["exact.edges_host"] > 0


def test_counters_agree_between_executors(lake):
    got = [{k: _traced(lake, d)[2][k] for k in COUNTERS}
           for d in ("cpu", "jax")]
    assert got[0] == got[1]


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_obs_off_answers_bit_identical(lake, device):
    sc = SpatialDatasetScanner(lake)
    off = sc.scan(BOX, refine=True, device=device, exact=True)
    _, on, _ = _traced(lake, device)
    for a, b in ((off[0].x, on[0].x), (off[0].y, on[0].y)):
        assert np.array_equal(a.view(np.int64), b.view(np.int64))
    assert np.array_equal(off[0].rep, on[0].rep)
    assert np.array_equal(off[1]["gid"], on[1]["gid"])
