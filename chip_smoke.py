"""Smoke run of the lake's device path on one TPU chip.

Builds a PT-like lake at the published size of the Porto taxi trajectories
(ECML/PKDD 2015 "Taxi Service Trajectory" data: ~1.7 M trips, ~82 M
points) from ``--seed``, writes it as 16 Hilbert-sorted shards, then drives
the normal path with ``device="jax"``:

* sharded refine scans at ~1 %, ~10 % and ~50 % record selectivity, a
  ``keep_on_device`` scan, a scan with a ``Range`` filter on a float32
  attribute, and a plain decode (``read_columnar(device="jax")``);
* a :class:`~repro.serve.query_scheduler.SpatialQueryServer` wave of 64
  concurrent bbox queries, then a second wave served from its decoded
  row-group cache.

Every device result is compared bit for bit with the same call on the host
path (``device="cpu"``), and every compiled kernel launch must have been a
Mosaic kernel, with no page decoded on the host. Wall-clock seconds per
phase and compile counts are printed along the way: this is a smoke run,
not a benchmark. Run it from the repository root::

    python chip_smoke.py [--n-traj N] [--seed S]

It exits non-zero, without its last line, when JAX finds no TPU or any
check fails. Its last line is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import resource
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

PT_TRIPS = 1_700_000  # Porto taxi trips (ECML/PKDD 2015 challenge data)
N_SHARDS = 16
PAGE_VALUES = 8192
SELECTIVITIES = (0.01, 0.10, 0.50)
N_QUERIES = 64
PALLAS_KINDS = ("limbs", "refine", "refine_multi")  # AOT keys with kernels


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"[chip_smoke] FAILED: {what}")
    log(f"ok: {what}")


class Phases:
    """Wall-clock seconds per phase (host clock, smoke-run granularity)."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.seconds[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.seconds[name]} s")
        return out


class CompileLog:
    """Backend compiles (or persistent-cache loads) of every jitted program,
    read from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _bits(a) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def same_arrays(a, b) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(
        _bits(a), _bits(b))


def same_result(dev, host, stats: bool = True) -> bool:
    """``(geo, extras, stats)`` equality: every level, coordinate and extra
    array bit for bit, plus the stats account."""
    gd, ed, sd = dev
    gh, eh, sh = host
    if (gd is None) != (gh is None) or set(ed) != set(eh):
        return False
    if gd is not None:
        gd = gd.coords_to_host()
        if not all(same_arrays(getattr(gd, f), getattr(gh, f))
                   for f in ("types", "type_rep", "rep", "defn", "x", "y")):
            return False
    if not all(same_arrays(ed[k], eh[k]) for k in eh):
        return False
    return sd == sh if stats else True


def device_check():
    import jax

    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    log(f"devices: {info}")
    if info["platform"] != "tpu":
        sys.exit(f"[chip_smoke] no TPU: JAX's default device is "
                 f"{info['platform']!r}")
    return jax, info


def build_lake(root: str, n_traj: int, seed: int):
    from repro.data.synthetic import porto_taxi_like
    from repro.dataset import write_dataset

    cols = porto_taxi_like(n_traj=n_traj, seed=seed)
    rng = np.random.default_rng(seed + 1)
    extra = {
        # per-trip average speed (km/h) and a taxi-stand code
        "speed": rng.gamma(4.0, 6.0, n_traj).astype(np.float32),
        "stand": rng.integers(0, 64, n_traj).astype(np.int32),
    }
    log(f"lake: {n_traj} trips, {cols.n_values} points, {N_SHARDS} shards, "
        f"page_values={PAGE_VALUES}")
    write_dataset(root, columns=cols, extra=extra, n_shards=N_SHARDS,
                  sort="hilbert", page_values=PAGE_VALUES)
    return cols


def check_page_stats(sc) -> None:
    """The writer's float32 page min/max (the device ``page_minmax``
    launch) equals a numpy reduction of each shard's pages."""
    from repro.core.reader import SpatialParquetReader

    for shard in sc.manifest.shards:
        with SpatialParquetReader(os.path.join(sc.root, shard.path)) as r:
            _, ex, _ = r.read_columnar(columns=("speed",))
            v, base = ex["speed"], 0
            for rg in r.footer["row_groups"]:
                for p in rg["extra"]["speed"]:
                    page = v[base + p["rec_start"]:
                             base + p["rec_start"] + p["rec_count"]]
                    if (p["vmin"], p["vmax"]) != (float(page.min()),
                                                  float(page.max())):
                        check(False, f"page stats of {shard.path}")
                base += rg["n_records"]
    check(True, "writer page min/max of the float32 column equals numpy")


def run_scans(sc, cols) -> None:
    from benchmarks.smoke import selectivity_bbox
    from repro.core.filters import Range

    boxes = {f: selectivity_bbox(cols, f) for f in SELECTIVITIES}
    for f, b in boxes.items():
        dev = sc.scan(b, refine=True, device="jax")
        host = sc.scan(b, refine=True, device="cpu")
        check(same_result(dev, host),
              f"refine scan at ~{f:.0%}: {host[2].records_returned} records "
              "bit-identical to host")
    b = boxes[0.10]
    dev = sc.scan(b, refine=True, device="jax", keep_on_device=True)
    host = sc.scan(b, refine=True, device="cpu")
    check(type(dev[0].x).__name__ == "DeviceCoords"
          and same_result(dev, host),
          "keep_on_device scan bit-identical to host")
    pred = Range("speed", 15.0, 30.0)
    dev = sc.scan(b, refine=True, device="jax", filter=pred)
    host = sc.scan(b, refine=True, device="cpu", filter=pred)
    check(same_result(dev, host),
          f"Range-filtered scan: {host[2].records_returned} records "
          "bit-identical to host")
    dev = sc.read_columnar(b, device="jax")
    host = sc.read_columnar(b, device="cpu")
    check(same_result(dev, host),
          f"plain decode: {dev[0].n_values} values bit-identical to host")


def _viewports(cols, rng, n: int) -> list:
    """``n`` small bbox queries centred on random points, each 5 % of the
    lake's extent on a side."""
    x, y = np.asarray(cols.x), np.asarray(cols.y)
    w = 0.05 * (float(x.max()) - float(x.min()))
    h = 0.05 * (float(y.max()) - float(y.min()))
    at = rng.integers(0, len(x), n)
    cx, cy = x[at], y[at]
    return [(float(a - w / 2), float(c - h / 2), float(a + w / 2),
             float(c + h / 2)) for a, c in zip(cx, cy)]


def run_server(sc, cols, seed: int) -> None:
    from repro.serve.query_scheduler import SpatialQueryServer

    rng = np.random.default_rng(seed + 2)
    first = _viewports(cols, rng, N_QUERIES)
    # half a box east: new answers from the row groups the first wave cached
    second = [(x0 + (x1 - x0) / 2, y0, x1 + (x1 - x0) / 2, y1)
              for x0, y0, x1, y1 in first]
    with SpatialQueryServer(sc, device="jax", max_wave=N_QUERIES) as srv:
        for name, boxes in (("first", first), ("second", second)):
            misses0, hits0 = srv.cache.misses, srv.cache.hits
            qs = [srv.submit(b) for b in boxes]
            srv.run()
            log(f"server {name} wave: {srv.cache.misses - misses0} row-group "
                f"cache misses, {srv.cache.hits - hits0} hits")
            for q, b in zip(qs, boxes):
                solo = sc.scan(b, refine=True, device="cpu")
                if not same_result((q.geo, q.extras, q.stats), solo,
                                   stats=False):
                    check(False, f"server query {q.qid} equals its solo scan")
            check(True, f"{len(qs)} server queries of the {name} wave equal "
                  "their solo host scans")
            if name == "second":
                check(srv.cache.hits > hits0,
                      "second wave served from the row-group cache")
        log(f"server metrics: {srv.metrics()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-traj", type=int, default=PT_TRIPS,
                    help="trips in the lake (default: the published PT size)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    jax, info = device_check()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import obs
    from repro.kernels import enable_compile_cache
    from repro.kernels.fp_delta import compile_cache_stats
    from repro.dataset import SpatialDatasetScanner

    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({entries} entries at start)")
    compiles = CompileLog(jax)
    obs.enable()
    phases = Phases()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        cols = phases.run("build", build_lake, root, args.n_traj, args.seed)
        with SpatialDatasetScanner(root, on_error="raise") as sc:
            phases.run("page_stats", check_page_stats, sc)
            phases.run("scans", run_scans, sc, cols)
            phases.run("server", run_server, sc, cols, args.seed)

    counters = obs.snapshot()["counters"]
    keys = [ast.literal_eval(k) for k in compile_cache_stats()["keys"]]
    kernel_keys = [k for k in keys if k[0] in PALLAS_KINDS]
    check({k[0] for k in kernel_keys} == set(PALLAS_KINDS)
          and all(k[-2:] == (True, False) for k in kernel_keys),
          f"{len(kernel_keys)} kernel AOT entries, all Pallas without "
          "interpret mode")
    check(counters.get("device.host_fallback_pages", 0) == 0,
          "no page decoded on the host fallback")
    log(f"compile: {compiles.programs} programs, {compiles.seconds} s in "
        f"backend compile or cache load, {compiles.cache_hits} persistent "
        f"cache hits; jit.compiles={counters.get('jit.compiles', 0)}, "
        f"jit.cache_hits={counters.get('jit.cache_hits', 0)}")
    log(f"phase seconds (smoke run, not a benchmark): {phases.seconds}; "
        f"host peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
