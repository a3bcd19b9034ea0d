"""Smoke read/write benchmark: a fast perf-trajectory anchor for CI.

Writes a JSON file (default ``BENCH_read.json``) with wall-clock seconds and
byte counts for the PT dataset so later PRs can regress against a recorded
baseline::

    PYTHONPATH=src python -m benchmarks.smoke [--scale 0.25] [--out BENCH_read.json]

Reported fields: ``write_s``, ``read_columnar_s`` (coalesced fast path,
double-buffered row groups), ``read_columnar_legacy_s`` (one read per blob,
same decode), ``device_decode_s`` (``device="jax"`` page-stream decode),
``device_refine_s`` (fused on-device decode→bbox-refine at ~50% record
selectivity) and ``refine_sweep`` — host vs fused device refinement at ~1%,
~10% and ~50% record selectivity with the measured selectivity per box.
Off-TPU the kernels run in Pallas interpret mode, so the device numbers are
correctness-plane trajectories in CI, not speedups. Also recorded:
``file_bytes``, ``raw_coord_bytes``, ``n_records``, ``n_values``, plus the
sharded-dataset trajectory: ``dataset_write_s``, ``dataset_scan_s`` (async
full scan over ``dataset_n_shards`` shards), ``dataset_scan_bbox_s`` and its
pruning ratio ``dataset_bbox_bytes_read``/``dataset_bytes_total``, the
predicate-pushdown trajectory: ``filter_scan_s`` (attribute-filtered scan
over a lake whose per-shard zone maps are disjoint on the filter column)
with ``filter_zone_pruned_bytes`` / ``filter_zone_pruned_ratio`` (bytes the
zone maps pruned before any shard file was opened), the
crash-safe catalog trajectory: ``catalog_commit_s`` (atomic snapshot commit
latency) and ``compact_s`` with ``compact_shards_before`` /
``compact_shards_after`` (one background-compaction cycle), plus the
fault-tolerant remote path: ``remote_scan_s`` (full read through a
``RemoteRangeSource`` over an in-process range-GET server, ``cold_cache``
vs ``warm_cache`` block cache). Timings are best-of-N to shrink scheduler
noise; ``latency_percentiles`` additionally reports the p50/p99 of every
repeated timing (the serve-tier view: tails, not just the floor).

``--trace scan_trace.json`` re-runs the fused device dataset scan with
:mod:`repro.obs` tracing enabled, verifies the traced results are
bit-identical to the untraced ones (exit code 1 otherwise), and writes the
Chrome trace-event JSON (with the metrics snapshot embedded) for Perfetto.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import shutil
import tempfile
import time

import numpy as np

from repro.core.reader import SpatialParquetReader
from repro.core.writer import write_file
from repro.dataset import (
    Catalog,
    Compactor,
    SpatialDatasetScanner,
    write_dataset,
)
from repro.io import InProcessRangeServer, RemoteRangeSource
from repro.kernels import enable_compile_cache

from .common import SCALE_1, make_dataset, tmppath

# record-selectivity targets of the fused-refine sweep (fraction of records
# a central quantile box should retain)
SWEEP_TARGETS = (0.01, 0.10, 0.50)


def selectivity_bbox(geo, frac: float):
    """A central bbox retaining roughly ``frac`` of the records: quantile
    span of sqrt(frac) per axis around the median."""
    x = np.asarray(geo.x, np.float64)
    y = np.asarray(geo.y, np.float64)
    side = float(np.sqrt(frac)) / 2.0
    return (
        float(np.quantile(x, 0.5 - side)), float(np.quantile(y, 0.5 - side)),
        float(np.quantile(x, 0.5 + side)), float(np.quantile(y, 0.5 + side)),
    )


def run(scale: float = 0.25, dataset: str = "PT", repeats: int = 3,
        n_shards: int = 4, trace: str | None = None) -> dict:
    cols = make_dataset(dataset, scale, sort="hilbert")
    path = tmppath(".spqf")
    droot = tempfile.mkdtemp(prefix="smoke_ds_")
    froot = tempfile.mkdtemp(prefix="smoke_flt_")
    # p50/p99 of every repeated timing, keyed like the min-based fields
    pcts: dict[str, dict] = {}

    def bench(name: str, fn) -> float:
        samples = [_timed(fn) for _ in range(repeats)]
        pcts[name] = _percentiles(samples)
        return min(samples)

    try:
        write_s = bench(
            "write_s",
            lambda: write_file(path, columns=cols, sort=None, codec="none"))
        file_bytes = os.path.getsize(path)
        with SpatialParquetReader(path) as r:
            read_s = bench("read_columnar_s", lambda: r.read_columnar())
            read_legacy_s = bench(
                "read_columnar_legacy_s",
                lambda: r.read_columnar(coalesce=False))
            r.read_columnar(device="jax")  # warm-up: jit compile off the clock
            device_decode_s = bench(
                "device_decode_s", lambda: r.read_columnar(device="jax"))
            geo, _, stats = r.read_columnar()

            # fused decode→refine selectivity sweep (host vs device)
            refine_sweep = []
            for target in SWEEP_TARGETS:
                bbox = selectivity_bbox(geo, target)
                # warm-up compiles this bucket off the clock
                _, _, dstats_r = r.read_columnar(
                    bbox=bbox, refine=True, device="jax")
                host = [
                    _timed(lambda: r.read_columnar(bbox=bbox, refine=True))
                    for _ in range(repeats)
                ]
                dev = [
                    _timed(lambda: r.read_columnar(
                        bbox=bbox, refine=True, device="jax"))
                    for _ in range(repeats)
                ]
                row = {
                    "target": target,
                    "selectivity": round(
                        dstats_r.records_returned / max(geo.n_records, 1), 4),
                    "host_refine_s": round(min(host), 6),
                    "device_refine_s": round(min(dev), 6),
                    "records": dstats_r.records_returned,
                }
                row.update({f"host_refine_{k}": v
                            for k, v in _percentiles(host).items()})
                row.update({f"device_refine_{k}": v
                            for k, v in _percentiles(dev).items()})
                refine_sweep.append(row)
            device_refine_s = refine_sweep[-1]["device_refine_s"]

        # remote (object-store-style) scan through the fault-tolerant
        # source: in-process range-GET server, cold vs warm block cache
        server = InProcessRangeServer(path)

        def remote_scan_cold():
            with SpatialParquetReader(source=RemoteRangeSource(server)) as rr:
                rr.read_columnar()

        remote_scan_cold_s = bench("remote_scan_cold_s", remote_scan_cold)
        with SpatialParquetReader(source=RemoteRangeSource(server)) as rr:
            rr.read_columnar()  # populate the block cache off the clock
            remote_scan_warm_s = bench(
                "remote_scan_warm_s", lambda: rr.read_columnar())

        # sharded dataset: async full scan + shard-pruned bbox scan
        dataset_write_s = bench(
            "dataset_write_s",
            lambda: write_dataset(droot, columns=cols, n_shards=n_shards,
                                  sort="hilbert", codec="none"))
        sc = SpatialDatasetScanner(droot, max_workers=n_shards)
        dataset_scan_s = bench("dataset_scan_s", lambda: sc.scan())
        x0, y0, x1, y1 = sc.manifest.mbr
        bbox = (x0, y0, x0 + (x1 - x0) / 4, y0 + (y1 - y0) / 4)
        dataset_scan_bbox_s = bench(
            "dataset_scan_bbox_s", lambda: sc.scan(bbox=bbox))
        _, _, dstats = sc.scan(bbox=bbox)
        trace_info = (_traced_scan_check(sc, bbox, trace)
                      if trace is not None else None)

        # attribute-predicate pushdown: a sort=None lake whose `seq` column
        # is contiguous per shard, so the persisted zone maps prune all but
        # one shard before any file is opened
        from repro.core.filters import Range

        write_dataset(
            froot, columns=cols,
            extra={"seq": np.arange(cols.n_records, dtype=np.int64)},
            n_shards=n_shards, sort=None, codec="none")
        fsc = SpatialDatasetScanner(froot, max_workers=n_shards)
        pred = Range("seq", 0, max(0, cols.n_records // n_shards - 1))
        fhit = fsc.index.query(None, filter=pred)
        filter_zone_pruned_bytes = int(
            fsc.index.data_bytes.sum() - fsc.index.data_bytes[fhit].sum())
        filter_scan_s = bench("filter_scan_s", lambda: fsc.scan(filter=pred))
        _, _, fstats = fsc.scan(filter=pred)
        fsc.close()

        # crash-safe catalog: metadata-only snapshot commit latency, then one
        # background-compaction cycle (merges the bench lake back to SFC
        # order; single run — a second cycle would be a no-op)
        cat = Catalog.open(droot)
        catalog_commit_s = bench(
            "catalog_commit_s",
            lambda: cat.commit_manifest(cat.head_snapshot().manifest))
        compact_shards_before = cat.head_snapshot().manifest.n_shards
        compactor = Compactor(cat, target_records=1 << 62)
        compact_s = _timed(compactor.run_once)
        compact_shards_after = cat.head_snapshot().manifest.n_shards
    finally:
        if os.path.exists(path):
            os.unlink(path)
        shutil.rmtree(droot, ignore_errors=True)
        shutil.rmtree(froot, ignore_errors=True)
    return {
        "dataset": dataset,
        "scale": scale,
        "scale_1_config": SCALE_1[dataset],
        "write_s": round(write_s, 6),
        "read_columnar_s": round(read_s, 6),
        "read_columnar_legacy_s": round(read_legacy_s, 6),
        "device_decode_s": round(device_decode_s, 6),
        "device_refine_s": device_refine_s,
        "refine_sweep": refine_sweep,
        "file_bytes": file_bytes,
        "raw_coord_bytes": int(cols.n_values) * 2 * cols.x.dtype.itemsize,
        "bytes_read": stats.bytes_read,
        "dataset_n_shards": n_shards,
        "dataset_write_s": round(dataset_write_s, 6),
        "dataset_scan_s": round(dataset_scan_s, 6),
        "dataset_scan_bbox_s": round(dataset_scan_bbox_s, 6),
        "dataset_bbox_bytes_read": dstats.bytes_read,
        "dataset_bytes_total": dstats.bytes_total,
        "dataset_bbox_shards_read": dstats.shards_read,
        "filter_scan_s": round(filter_scan_s, 6),
        "filter_zone_pruned_bytes": filter_zone_pruned_bytes,
        "filter_zone_pruned_ratio": round(
            filter_zone_pruned_bytes / max(1, fstats.bytes_total), 4),
        "filter_shards_read": fstats.shards_read,
        "filter_records_returned": fstats.records_returned,
        "catalog_commit_s": round(catalog_commit_s, 6),
        "compact_s": round(compact_s, 6),
        "compact_shards_before": compact_shards_before,
        "compact_shards_after": compact_shards_after,
        "remote_scan_s": {
            "cold_cache": round(remote_scan_cold_s, 6),
            "warm_cache": round(remote_scan_warm_s, 6),
        },
        "n_records": int(geo.n_records),
        "n_values": int(geo.n_values),
        "latency_percentiles": pcts,
        "trace": trace_info,
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _percentiles(samples) -> dict:
    return {"p50": round(float(np.percentile(samples, 50)), 6),
            "p99": round(float(np.percentile(samples, 99)), 6)}


def _result_fingerprint(geo, extras) -> bytes:
    parts = []
    if geo is not None:
        geo = geo.coords_to_host()
        for f in ("types", "type_rep", "rep", "defn", "x", "y"):
            parts.append(np.asarray(getattr(geo, f)).tobytes())
    for k in sorted(extras):
        parts.append(k.encode())
        parts.append(np.asarray(extras[k]).tobytes())
    return b"".join(parts)


def _traced_scan_check(sc, bbox, trace_path: str) -> dict:
    """Traced fused device scan, verified bit-identical to the untraced one.

    Exports the Chrome trace JSON (metrics snapshot embedded) to
    ``trace_path``; exits non-zero if tracing perturbed the results.
    """
    from repro import obs

    ref = sc.scan(bbox=bbox, refine=True, device="jax")
    tracer = obs.enable()
    try:
        out = sc.scan(bbox=bbox, refine=True, device="jax")
    finally:
        obs.disable()
    if _result_fingerprint(ref[0], ref[1]) != _result_fingerprint(out[0], out[1]):
        raise SystemExit(
            "[smoke] traced scan results differ from untraced scan")
    tracer.export(trace_path, metrics=obs.snapshot())
    spans = [e for e in tracer.events if e["ph"] == "X"]
    return {
        "path": trace_path,
        "spans": len(spans),
        "stages": sorted({e["name"] for e in spans}),
        "bit_identical": True,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--dataset", default="PT")
    ap.add_argument("--out", default="BENCH_read.json")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run a traced fused device scan, verify it is "
                         "bit-identical to the untraced one, and write the "
                         "Chrome trace-event JSON here")
    args = ap.parse_args()
    enable_compile_cache()
    result = run(scale=args.scale, dataset=args.dataset, repeats=args.repeats,
                 n_shards=args.shards, trace=args.trace)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
        fh.write("\n")
    print(json.dumps(result, indent=1))
    print(f"[smoke] saved {args.out}")


if __name__ == "__main__":
    main()
