"""Serve-tier smoke benchmark: concurrent bbox queries with shared decodes.

For each query count (default 1, 16, 256) this builds a fresh
:class:`~repro.serve.query_scheduler.SpatialQueryServer` over a sharded PT
dataset, submits that many overlapping bbox queries, drains them in
admission waves, and records the per-query latency histogram percentiles
(``serve_p50_s``/``serve_p99_s``, from the ``serve.query_latency_s`` obs
histogram — the serving view: tails, not the floor) plus the
``shared_decode_ratio`` (row-group touches per actual decode: how many solo
decodes one shared decode replaced; at 256 queries it shows each surviving
row group decoded once per wave). ``sequential_s`` times the same queries as
solo ``scanner.scan`` calls for the unshared baseline.

Results merge into the smoke benchmark's JSON (default ``BENCH_read.json``)
under the ``"serve"`` key, so CI keeps one perf-trajectory artifact::

    PYTHONPATH=src python -m benchmarks.smoke --out BENCH_read.json
    PYTHONPATH=src python -m benchmarks.bench_serve --out BENCH_read.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro import obs
from repro.dataset import SpatialDatasetScanner, write_dataset
from repro.kernels import enable_compile_cache
from repro.serve.query_scheduler import SpatialQueryServer

from .common import make_dataset
from .smoke import selectivity_bbox

# selectivity targets the query mix cycles through (overlapping central
# boxes, so concurrent queries share row groups)
QUERY_FRACS = (0.01, 0.05, 0.10, 0.25, 0.50)


def _query_boxes(geo, n: int) -> list:
    return [selectivity_bbox(geo, QUERY_FRACS[i % len(QUERY_FRACS)])
            for i in range(n)]


def run(scale: float = 0.1, dataset: str = "PT", n_shards: int = 4,
        query_counts=(1, 16, 256), device: str = "cpu",
        max_wave: int = 64) -> dict:
    cols = make_dataset(dataset, scale, sort="hilbert")
    droot = tempfile.mkdtemp(prefix="bench_serve_")
    try:
        write_dataset(droot, columns=cols, n_shards=n_shards, sort="hilbert",
                      codec="none")
        sc = SpatialDatasetScanner(droot)
        geo, _, _ = sc.scan()
        rows = []
        for n_q in query_counts:
            boxes = _query_boxes(geo, n_q)
            # warm-up: compile/populate off the clock, then a fresh server
            # and a fresh metrics registry per count
            with SpatialQueryServer(sc, device=device,
                                    max_wave=max_wave) as warm:
                warm.submit(boxes[0])
                warm.run()
            obs.enable()
            try:
                with SpatialQueryServer(sc, device=device,
                                        max_wave=max_wave) as srv:
                    t0 = time.perf_counter()
                    for b in boxes:
                        srv.submit(b)
                    srv.run()
                    served_s = time.perf_counter() - t0
                    pcts = obs.percentiles("serve.query_latency_s")
                    m = srv.metrics()
            finally:
                obs.disable()
            t0 = time.perf_counter()
            for b in boxes:
                sc.scan(bbox=b, refine=True, device=device, parallel=False)
            sequential_s = time.perf_counter() - t0
            rows.append({
                "queries": n_q,
                "serve_p50_s": round(pcts.get("p50", 0.0), 6),
                "serve_p99_s": round(pcts.get("p99", 0.0), 6),
                "served_s": round(served_s, 6),
                "sequential_s": round(sequential_s, 6),
                "waves": m["waves"],
                "rg_touches": m["rg_touches"],
                "rg_decodes": m["rg_decodes"],
                "shared_decode_ratio": round(m["shared_decode_ratio"], 3),
            })
    finally:
        shutil.rmtree(droot, ignore_errors=True)
    return {
        "dataset": dataset,
        "scale": scale,
        "device": device,
        "n_shards": n_shards,
        "max_wave": max_wave,
        "by_query_count": rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--dataset", default="PT")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--device", default="cpu", choices=("cpu", "jax"))
    ap.add_argument("--queries", type=int, nargs="+", default=[1, 16, 256])
    ap.add_argument("--max-wave", type=int, default=64)
    ap.add_argument("--out", default="BENCH_read.json",
                    help="merge results under the 'serve' key of this JSON")
    args = ap.parse_args()
    enable_compile_cache()
    result = run(scale=args.scale, dataset=args.dataset, n_shards=args.shards,
                 query_counts=tuple(args.queries), device=args.device,
                 max_wave=args.max_wave)
    merged = {}
    if os.path.exists(args.out):
        with open(args.out) as fh:
            merged = json.load(fh)
    merged["serve"] = result
    with open(args.out, "w") as fh:
        json.dump(merged, fh, indent=1)
        fh.write("\n")
    print(json.dumps(result, indent=1))
    print(f"[bench_serve] merged into {args.out}")


if __name__ == "__main__":
    main()
