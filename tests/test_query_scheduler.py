"""Multi-tenant bbox query server: bit-identity vs sequential scans, cache
behavior (hit / evict / generation invalidation), and per-query ReadStats
attribution (see repro/serve/query_scheduler.py)."""

import numpy as np
import pytest

from repro.core.filters import Range
from repro.data.synthetic import PORTO_BBOX, porto_taxi_like
from repro.dataset.scanner import SpatialDatasetScanner
from repro.dataset.writer import write_dataset
from repro.serve.query_scheduler import SpatialQueryServer

STAT_FIELDS = ("pages_total", "pages_read", "bytes_total", "bytes_read",
               "records_scanned", "records_returned", "shards_total",
               "shards_read")


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    cols = porto_taxi_like(n_traj=300, seed=11)
    extra = {"tid": np.arange(cols.n_records, dtype=np.int64)}
    root = tmp_path_factory.mktemp("serve_lake") / "lake"
    write_dataset(root, columns=cols, extra=extra, n_shards=3,
                  sort="hilbert", page_values=2048)
    return SpatialDatasetScanner(root)


def _boxes():
    """Overlapping grid cells + full extent, empty, None and NaN queries."""
    x0, y0, x1, y1 = PORTO_BBOX
    xs = np.linspace(x0, x1, 4)
    ys = np.linspace(y0, y1, 4)
    boxes = [(xs[i], ys[j], xs[i + 1], ys[j + 1])
             for i in range(3) for j in range(3)]
    boxes.append(PORTO_BBOX)                 # full extent
    boxes.append((50.0, 50.0, 51.0, 51.0))   # empty: far from Porto
    boxes.append(None)                       # no filter
    boxes.append((np.nan, y0, x1, y1))       # NaN bound: keeps nothing
    return boxes


def _assert_geo_equal(a, b, ctx):
    if a is None or b is None:
        assert a is None and b is None, ctx
        return
    for f in ("types", "type_rep", "rep", "defn", "x", "y"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), (ctx, f)


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_concurrent_queries_match_sequential_scan(lake, device):
    srv = SpatialQueryServer(lake, device=device, cache_rgs=64, max_wave=8)
    boxes = _boxes()
    with srv:
        qs = [srv.submit(b) for b in boxes]
        done = srv.run()
        assert done == qs and all(q.done for q in qs)
        assert srv.waves >= 2  # 13 queries over max_wave=8: multi-wave
        for q, b in zip(qs, boxes):
            geo, extras, _ = lake.scan(b, refine=True, device=device,
                                       parallel=False)
            _assert_geo_equal(q.geo, geo, (device, b))
            assert set(q.extras) == set(extras), (device, b)
            for k in extras:
                assert np.array_equal(q.extras[k], extras[k]), (device, b, k)


def _stat_queries(lake):
    """``(bbox, columns, filter)``: every box of ``_boxes``, then geometry
    only, and geometry only under a ``tid`` range (a column the filter
    reads but the query does not return)."""
    n = lake.manifest.n_records
    tid = Range("tid", n // 5, n // 2)
    return ([(b, None, None) for b in _boxes()]
            + [(PORTO_BBOX, ("geometry",), None),
               (PORTO_BBOX, ("geometry",), tid),
               (_boxes()[4], ("geometry",), tid),
               (None, None, tid)])


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_per_query_stats_match_solo_scan(lake, device):
    queries = _stat_queries(lake)
    with SpatialQueryServer(lake, device=device, cache_rgs=64) as srv:
        qs = [srv.submit(b, columns=c, filter=f) for b, c, f in queries]
        srv.run()
    for q, (b, c, f) in zip(qs, queries):
        geo, extras, st = lake.scan(b, columns=c, refine=True, device=device,
                                    parallel=False, filter=f)
        for name in STAT_FIELDS:
            assert getattr(q.stats, name) == getattr(st, name), (device, b, c, name)
        _assert_geo_equal(q.geo, geo, (device, b, c, f))
        assert set(q.extras) == set(extras), (device, b, c, f)
        assert q.latency_s >= 0.0
    assert 0 < qs[-3].stats.records_returned < qs[-4].stats.records_returned


def test_shared_decode_and_cache_hits(lake):
    bbox = PORTO_BBOX
    with SpatialQueryServer(lake, device="cpu", cache_rgs=64,
                            max_wave=64) as srv:
        n_q = 16
        for _ in range(n_q):
            srv.submit(bbox)
        srv.run()
        union = {(s, rg) for s in range(len(lake.index))
                 for rg, _, _ in srv._reader(s).index.page_runs(bbox)}
        # the whole wave decoded each surviving row group exactly once
        assert srv.rg_decodes == len(union)
        assert srv.rg_touches == n_q * len(union)
        m = srv.metrics()
        assert m["shared_decode_ratio"] == pytest.approx(n_q)
        # a second wave over the same region is pure cache hits
        srv.submit(bbox)
        srv.run()
        assert srv.rg_decodes == len(union)
        assert srv.cache.hits >= len(union)


def test_cache_eviction_keeps_results_exact(lake):
    boxes = _boxes()[:10]
    with SpatialQueryServer(lake, device="cpu", cache_rgs=1,
                            max_wave=4) as srv:
        qs = [srv.submit(b) for b in boxes]
        srv.run()
        assert srv.cache.evictions > 0
        assert len(srv.cache) <= 1
    for q, b in zip(qs, boxes):
        geo, extras, _ = lake.scan(b, refine=True, parallel=False)
        _assert_geo_equal(q.geo, geo, ("evict", b))
        for k in extras:
            assert np.array_equal(q.extras[k], extras[k])


def test_generation_invalidation_forces_redecode(lake):
    with SpatialQueryServer(lake, device="cpu", cache_rgs=64) as srv:
        srv.submit(PORTO_BBOX)
        srv.run()
        decodes = srv.rg_decodes
        assert decodes > 0
        srv.submit(PORTO_BBOX)
        srv.run()
        assert srv.rg_decodes == decodes  # warm: no new decode
        srv.invalidate()
        assert len(srv.cache) == 0
        q = srv.submit(PORTO_BBOX)
        srv.run()
        assert srv.rg_decodes == 2 * decodes  # stale entries unreachable
        geo, _, _ = lake.scan(PORTO_BBOX, refine=True, parallel=False)
        _assert_geo_equal(q.geo, geo, "post-invalidate")


def test_catalog_commit_auto_invalidates_next_wave(tmp_path):
    """A compaction commit between waves must bump the server's generation:
    readers reopen, the row-group cache redecodes, results stay identical."""
    from repro.dataset import Catalog, Compactor

    cols = porto_taxi_like(n_traj=240, seed=13)
    extra = {"tid": np.arange(cols.n_records, dtype=np.int64)}
    root = tmp_path / "lake"
    write_dataset(root, columns=cols, extra=extra, n_shards=6,
                  sort="hilbert", page_values=2048)
    scanner = SpatialDatasetScanner(root)
    with SpatialQueryServer(scanner, device="cpu", cache_rgs=64) as srv:
        assert srv.data_generation == 1
        q0 = srv.submit(PORTO_BBOX)
        srv.run()
        decodes = srv.rg_decodes
        assert decodes > 0
        gen_key = srv.generation

        cat = Catalog.open(root)
        comp = Compactor(cat, target_records=1 << 30, page_values=2048)
        assert comp.run_once().generation == 2

        # next wave: refresh() sees gen 2 → readers closed, cache dropped
        q1 = srv.submit(PORTO_BBOX)
        srv.run()
        assert srv.data_generation == 2
        assert srv.generation == gen_key + 1  # stale cache keys unreachable
        assert srv.rg_decodes > decodes  # the wave redecoded, not served stale
        _assert_geo_equal(q1.geo, q0.geo, "post-compaction")
        for k in q0.extras:
            assert np.array_equal(q1.extras[k], q0.extras[k])
        # steady state: no bump without a commit, cache warm again
        decodes = srv.rg_decodes
        srv.submit(PORTO_BBOX)
        srv.run()
        assert srv.data_generation == 2
        assert srv.rg_decodes == decodes


def test_columns_subset(lake):
    with SpatialQueryServer(lake, device="cpu") as srv:
        q_all = srv.submit(PORTO_BBOX)
        q_geom = srv.submit(PORTO_BBOX, columns=("geometry",))
        srv.run()
    geo, extras, _ = lake.scan(PORTO_BBOX, refine=True, parallel=False)
    _assert_geo_equal(q_all.geo, geo, "columns=None")
    assert set(q_all.extras) == {"tid"}
    assert np.array_equal(q_all.extras["tid"], extras["tid"])
    _assert_geo_equal(q_geom.geo, geo, "columns=(geometry,)")
    assert q_geom.extras == {}
