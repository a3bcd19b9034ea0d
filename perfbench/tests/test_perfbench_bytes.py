"""The byte function counts the logical work of the refine chain: the pages
a scan's box and predicate admit, the same whatever padding the program's
launch chain uses."""

import json
import tempfile
from pathlib import Path

import numpy as np
import pytest

from perfbench import gen, harness, lake, nbytes, reference

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def small_lake():
    cfg = json.loads((BENCH / "configs" / "pt_taxi.json").read_text())
    cfg.update(n_records=1200, n_shards=2, page_values=1024)
    data = harness.load_module(BENCH / "configs" / "pt_taxi.py").generate(cfg, 4)
    with tempfile.TemporaryDirectory() as root:
        lake.write_lake(root, cfg, data)
        yield cfg, reference.Records(data, cfg), root


def test_chain_bytes_are_the_pages_the_query_meets(small_lake):
    from repro.dataset import SpatialDatasetScanner

    cfg, rec, root = small_lake
    with SpatialDatasetScanner(root) as sc:
        pages = lake.page_table(sc, "speed")
        footers = []
        for i in range(len(sc.manifest.shards)):
            with sc.open_shard(i) as r:
                footers.append(r.footer)
    n_pages = sum(len(rg["x_pages"]) for f in footers for rg in f["row_groups"])
    assert len(pages.nbytes) == n_pages > 4
    everything = (-1e9, -1e9, 1e9, 1e9)
    stored = sum(p["nbytes"] for f in footers for rg in f["row_groups"]
                 for p in rg["x_pages"] + rg["y_pages"])
    assert nbytes.chain_bytes(pages, everything, None) == stored + rec.n
    nowhere = (1e8, 1e8, 1e8 + 1, 1e8 + 1)
    assert nbytes.chain_bytes(pages, nowhere, None) == 0
    # a predicate no page's zone admits reads nothing
    assert nbytes.chain_bytes(pages, everything, ("speed", -2.0, -1.0)) == 0


def test_same_launch_same_bytes_at_other_paddings(small_lake, monkeypatch):
    """Coarser pow2 buckets change every padded shape of the launches; the
    answers and the necessary bytes stay put."""
    from repro.dataset import SpatialDatasetScanner
    from repro.kernels.fp_delta import ops

    cfg, rec, root = small_lake
    mix = json.loads((BENCH / "traffic" / "scan_mixed.json").read_text())
    qs = gen.scan_queries(rec, cfg, mix, np.random.default_rng([1, 2]), 6)

    def measure():
        with SpatialDatasetScanner(root) as sc:
            pages = lake.page_table(sc, "speed")
            total, values = 0, []
            for q in qs:
                geo, _, _ = sc.scan(q.bbox, refine=True, device="jax")
                values.append(geo.n_values if geo is not None else 0)
                total += nbytes.chain_bytes(pages, q.bbox, q.pred)
        return total, values

    base = measure()
    real = ops._pow2_bucket
    monkeypatch.setattr(ops, "_pow2_bucket",
                        lambda x, floor: real(x, floor) * 4)
    ops._COMPILED.clear()
    try:
        assert measure() == base
    finally:
        ops._COMPILED.clear()
