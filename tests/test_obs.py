"""Observability layer: tracer no-op guarantees, span attribution, export.

Three properties carry the whole subsystem and are pinned here:

1. **Disabled is free and invisible** — ``obs.span`` returns one shared
   singleton (no allocation), and a scan traced vs untraced returns
   bit-identical bytes.
2. **Attribution is correct** — spans nest by explicit parent ids, survive
   thread hand-offs (scanner workers, prefetch), and the fused device scan's
   trace covers every pipeline stage with per-shard / per-row-group args.
3. **The numbers are right** — histogram quantile estimates track numpy
   percentiles, stats folding matches the stats objects, and a skip-policy
   scan keeps the failed attempts' SourceStats (the silent-drop regression).
"""

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro import obs
from repro.core.columnar import from_ragged
from repro.core.reader import ReadStats, SpatialParquetReader
from repro.core.writer import write_file
from repro.dataset import SpatialDatasetScanner, write_dataset
from repro.io import LocalFileSource
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer


@pytest.fixture
def rng():
    return np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts with telemetry disabled and an empty tracer and
    registry (another test file in the same process may have left its
    own), and ends with telemetry disabled."""
    obs.enable()
    obs.disable()
    yield
    obs.disable()


def _point_cols(rng, n, spread=100.0):
    pts = np.round(rng.uniform(-spread, spread, (n, 2)), 6)
    return from_ragged(np.ones(n, np.uint8), pts,
                       np.ones(n, np.int64), np.ones(n, np.int64))


def _fingerprint(geo, extras):
    geo = geo.coords_to_host()
    parts = [np.asarray(getattr(geo, f)).tobytes()
             for f in ("types", "type_rep", "rep", "defn", "x", "y")]
    for k in sorted(extras):
        parts.append(np.asarray(extras[k]).tobytes())
    return b"".join(parts)


@pytest.fixture
def sample_file(rng, tmp_path):
    path = str(tmp_path / "obs.spqf")
    cols = _point_cols(rng, 4000)
    tag = rng.integers(0, 50, 4000).astype(np.int32)
    write_file(path, columns=cols, extra={"tag": tag},
               extra_schema={"tag": "<i4"}, page_values=512,
               sort="hilbert", row_group_records=1000)
    return path


@pytest.fixture
def lake(rng, tmp_path):
    root = str(tmp_path / "lake")
    os.makedirs(root)
    write_dataset(root, columns=_point_cols(rng, 6000), n_shards=4,
                  page_values=512)
    return root


# ------------------------------------------------------------ disabled = free
def test_disabled_span_is_shared_singleton():
    # no Span object is ever allocated while tracing is off
    assert obs.span("decode", shard=1) is NULL_SPAN
    assert obs.span("anything") is obs.span("else")
    assert obs.timed("io.read_s") is NULL_SPAN
    with obs.span("decode", rg=3) as sp:
        assert sp is NULL_SPAN
        sp.add(pages=7)  # attribute adds are absorbed
    assert obs.current_span() is None


def test_disabled_recorders_are_noops():
    obs.count("a", 5)
    obs.gauge("b", 1.0)
    obs.observe("c", 0.1)
    obs.instant("d")
    assert obs.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_disabled_submit_is_plain_submit():
    with ThreadPoolExecutor(max_workers=1) as pool:
        assert obs.submit(pool, lambda x: x + 1, 41).result() == 42


def test_reads_bit_identical_tracing_on_vs_off(sample_file):
    bbox = (-50.0, -50.0, 50.0, 50.0)
    with SpatialParquetReader(sample_file) as r:
        variants = [
            dict(),
            dict(bbox=bbox, refine=True),
            dict(bbox=bbox, refine=True, device="jax"),
        ]
        for kw in variants:
            g0, e0, s0 = r.read_columnar(**kw)
            obs.enable()
            try:
                g1, e1, s1 = r.read_columnar(**kw)
            finally:
                obs.disable()
            assert _fingerprint(g0, e0) == _fingerprint(g1, e1), kw
            assert s0.bytes_read == s1.bytes_read


def test_scan_bit_identical_tracing_on_vs_off(lake):
    sc = SpatialDatasetScanner(lake)
    bbox = (-60.0, -60.0, 60.0, 60.0)
    g0, e0, _ = sc.scan(bbox=bbox, refine=True, device="jax")
    obs.enable()
    try:
        g1, e1, _ = sc.scan(bbox=bbox, refine=True, device="jax")
    finally:
        obs.disable()
    assert _fingerprint(g0, e0) == _fingerprint(g1, e1)


# --------------------------------------------------- nesting + thread handoff
def test_span_nesting_parent_ids():
    tracer = obs.enable()
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert obs.current_span() is inner
        assert obs.current_span() is outer
    obs.disable()
    ev = {e["name"]: e for e in tracer.spans()}
    assert ev["inner"]["args"]["parent_id"] == ev["outer"]["args"]["span_id"]
    assert ev["outer"]["args"]["parent_id"] == 0


def test_span_handoff_across_threads():
    tracer = obs.enable()
    with ThreadPoolExecutor(max_workers=2) as pool:
        with obs.span("parent") as parent:
            def worker(i):
                with obs.span("child", i=i) as c:
                    return c.parent_id, threading.get_ident()
            futs = [obs.submit(pool, worker, i) for i in range(4)]
            got = [f.result() for f in futs]
    obs.disable()
    # every child, on whatever thread, parents under the submitting span
    assert all(pid == parent.span_id for pid, _ in got)
    children = tracer.spans("child")
    assert len(children) == 4
    assert {e["args"]["i"] for e in children} == {0, 1, 2, 3}
    # real OS thread ids recorded (pool threads differ from main)
    assert {e["tid"] for e in children} <= {t for _, t in got}


def test_scanner_trace_per_shard_attribution(lake):
    sc = SpatialDatasetScanner(lake)
    tracer = obs.enable()
    try:
        sc.scan(bbox=None, refine=False)
    finally:
        obs.disable()
    ds = tracer.spans("scan.dataset")
    assert len(ds) == 1
    shards = tracer.spans("shard")
    assert {e["args"]["shard"] for e in shards} == {0, 1, 2, 3}
    # worker-thread shard spans all parent under the dataset span
    assert {e["args"]["parent_id"] for e in shards} == \
        {ds[0]["args"]["span_id"]}
    # row-group work attributes to a row group and nests under some span
    rgs = tracer.spans("rg.decode") + tracer.spans("rg.launch")
    assert rgs and all("rg" in e["args"] for e in rgs)


def test_fused_device_scan_trace_covers_stages(lake):
    sc = SpatialDatasetScanner(lake)
    bbox = (-60.0, -60.0, 60.0, 60.0)
    tracer = obs.enable()
    try:
        sc.scan(bbox=bbox, refine=True, device="jax")
    finally:
        obs.disable()
    names = {e["name"] for e in tracer.spans()}
    # plan → fetch → decode/refine launch → transfer, shard + file context
    assert {"scan.dataset", "shard", "scan.file", "rg.plan", "rg.fetch",
            "rg.launch"} <= names
    launches = tracer.spans("rg.launch")
    assert all("rg" in e["args"] for e in launches)
    snap = obs.snapshot()
    assert snap["counters"]["read.shards_read"] == 4
    assert "scan.dataset_latency_s" in snap["histograms"]
    assert "scan.latency_s" in snap["histograms"]
    assert snap["gauges"]["scan.host_cpu_s_per_gb"] > 0


# ------------------------------------------------------------------- export
def test_chrome_trace_export_roundtrip(tmp_path, lake):
    sc = SpatialDatasetScanner(lake)
    tracer = obs.enable()
    try:
        sc.scan()
    finally:
        obs.disable()
    out = str(tmp_path / "trace.json")
    tracer.export(out, metrics=obs.snapshot())
    with open(out) as fh:
        doc = json.load(fh)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    # schema: every event carries the chrome trace-event required fields
    for ev in events:
        assert {"name", "ph", "pid"} <= set(ev)
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert {"span_id", "parent_id"} <= set(ev["args"])
    # thread metadata names the worker threads
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and all(e["name"] == "thread_name" for e in meta)
    # the metrics snapshot rides along without breaking the trace shape
    assert "counters" in doc["metrics"]


def test_tracer_summary_aggregates():
    tracer = Tracer()
    for i in range(3):
        span = type("S", (), {"name": "stage", "cat": "x", "args": {},
                              "span_id": i + 1, "parent_id": 0})()
        tracer._complete(span, 0, 1000 * (i + 1))
    (row,) = tracer.summary()
    assert row["name"] == "stage" and row["count"] == 3
    assert row["total_ms"] == pytest.approx(0.006)
    assert row["max_ms"] == pytest.approx(0.003)


# ------------------------------------------------------------------ metrics
def test_histogram_quantiles_track_numpy(rng):
    h = Histogram("lat")
    samples = rng.lognormal(mean=-4.0, sigma=1.5, size=5000)
    for v in samples:
        h.observe(v)
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        exact = float(np.percentile(samples, q * 100))
        assert est == pytest.approx(exact, rel=0.15), q
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["min"] == pytest.approx(samples.min())
    assert snap["max"] == pytest.approx(samples.max())


def test_histogram_edges():
    h = Histogram("x")
    assert np.isnan(h.quantile(0.5))
    h.observe(0.01)
    # one observation: every quantile collapses to it (clamped bounds)
    assert h.quantile(0.0) == pytest.approx(0.01)
    assert h.quantile(1.0) == pytest.approx(0.01)
    # out-of-range values land in clamped under/overflow buckets
    h2 = Histogram("y", bounds=[1.0, 2.0])
    h2.observe(0.5)
    h2.observe(10.0)
    assert h2.quantile(0.0) == pytest.approx(0.5)
    assert h2.quantile(1.0) == pytest.approx(10.0)


def test_histogram_extreme_quantiles_exact():
    """q=0 / q=1 return the exact observed extremes (no interpolation), and
    a single-bucket histogram still answers every quantile sanely."""
    h = Histogram("x", bounds=[0.0, 100.0])  # one real bucket
    for v in (3.0, 7.0, 50.0):
        h.observe(v)
    assert h.quantile(0.0) == 3.0
    assert h.quantile(1.0) == 50.0
    assert 3.0 <= h.quantile(0.5) <= 50.0
    with pytest.raises(ValueError):
        h.quantile(-0.1)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_huge_counts_no_float_drift():
    """Bucket totals past 2**53: a float accumulator would absorb small
    counts (cum + c == cum) and push every quantile into the last bucket;
    the exact integer accumulation must keep low quantiles in the first."""
    h = Histogram("x", bounds=[0.0, 1.0, 2.0, 3.0])
    h._counts[1] = 3            # bucket [0, 1)
    h._counts[2] = 2**60        # bucket [1, 2)
    h._counts[3] = 5            # bucket [2, 3)
    h.count = 3 + 2**60 + 5
    h.min, h.max = 0.5, 2.5
    h.sum = float(h.count)
    # q tiny enough that the target falls inside the 3-count bucket
    q = 1.0 / float(h.count)
    assert 0.0 <= h.quantile(q) <= 1.0
    assert 1.0 <= h.quantile(0.5) <= 2.0
    # near-1 quantile interpolates inside the last bucket, clamped to max
    assert 2.0 <= h.quantile(1.0 - 1e-18) <= 2.5
    assert h.quantile(1.0) == 2.5


def test_fold_read_stats_counters():
    reg = MetricsRegistry()
    st = ReadStats(pages_total=10, pages_read=4, bytes_total=1000,
                   bytes_read=400, retries=2, cache_hits=3)
    reg.fold_read_stats(st)
    reg.fold_read_stats(st)  # accumulates across queries
    snap = reg.snapshot()
    assert snap["counters"]["read.pages_read"] == 8
    assert snap["counters"]["read.retries"] == 4
    assert snap["counters"]["read.cache_hits"] == 6
    # bools and non-numerics never become counters
    assert "read.failures" in snap["counters"]


# --------------------------------------------- satellite: failed-attempt stats
def test_skip_policy_keeps_failed_attempt_source_stats(lake):
    """A skipped shard's attempts did real I/O (and recoveries); their
    SourceStats deltas must fold into the aggregate, not vanish."""
    bad = {"n": 0}

    def factory(path):
        src = LocalFileSource(path)
        if path.endswith("shard-00000.spqf"):
            def boom(offset, nbytes, *, refresh=False):
                # a failing attempt that accrued recoveries before dying
                src.stats.requests += 1
                src.stats.retries += 3
                src.stats.timeouts += 1
                src.stats.cache_hits += 2
                src.stats.cache_misses += 5
                bad["n"] += 1
                raise IOError("injected failure")
            src.read_at = boom
            src.readinto_at = lambda off, buf: boom(off, len(buf))
        return src

    sc = SpatialDatasetScanner(lake, on_error="skip", shard_retries=1,
                               source_factory=factory)
    geo, _, st = sc.scan()
    assert bad["n"] >= 2  # both attempts really failed
    assert len(st.failures) == 1 and st.failures[0].shard_index == 0
    assert st.shards_read == 3 and geo is not None
    # the regression: every failed attempt's deltas are in the aggregate
    n = bad["n"]
    assert st.retries == 3 * n
    assert st.timeouts == 1 * n
    assert st.cache_hits == 2 * n
    assert st.cache_misses == 5 * n


def test_raise_policy_attaches_partial_stats(lake):
    def factory(path):
        src = LocalFileSource(path)
        if path.endswith("shard-00001.spqf"):
            def boom(offset, nbytes, *, refresh=False):
                src.stats.retries += 7
                raise IOError("injected failure")
            src.read_at = boom
            src.readinto_at = lambda off, buf: boom(off, len(buf))
        return src

    sc = SpatialDatasetScanner(lake, on_error="raise", source_factory=factory)
    with pytest.raises(Exception) as ei:
        sc.scan()
    cause = ei.value.__cause__
    assert getattr(cause, "spqf_source_stats").retries == 7


# ------------------------------------------ step spans, CPU time, annotations
@pytest.fixture
def extras_lake(rng, tmp_path):
    root = str(tmp_path / "xlake")
    os.makedirs(root)
    n = 6000
    write_dataset(root, columns=_point_cols(rng, n), n_shards=4,
                  page_values=512,
                  extra={"tag": rng.integers(0, 50, n).astype(np.int32),
                         "w": rng.uniform(0, 1, n)})
    return root


def _traced_scan(root, **kw):
    sc = SpatialDatasetScanner(root)
    tracer = obs.enable()
    try:
        out = sc.scan(bbox=(-60.0, -60.0, 60.0, 60.0), refine=True,
                      device="jax", **kw)
    finally:
        obs.disable()
    return tracer, out


def _children(tracer):
    kids: dict = {}
    for e in tracer.spans():
        kids.setdefault(e["args"]["parent_id"], []).append(e)
    return kids


def test_plan_step_spans_nest_under_rg_plan(extras_lake):
    tracer, _ = _traced_scan(extras_lake)
    ids = {e["args"]["span_id"]: e for e in tracer.spans()}
    kids = _children(tracer)
    plans = tracer.spans("rg.plan")
    assert plans
    for name in ("rg.stream_plan", "rg.extras"):
        got = tracer.spans(name)
        assert got and all(ids[e["args"]["parent_id"]]["name"] == "rg.plan"
                           for e in got), name
    # coordinate CRCs under rg.plan, the extras' own under rg.extras
    crc_parents = {ids[e["args"]["parent_id"]]["name"]
                   for e in tracer.spans("rg.crc")}
    assert crc_parents == {"rg.plan", "rg.extras"}
    for p in plans:
        steps = [k for k in kids[p["args"]["span_id"]]
                 if k["name"] in ("rg.crc", "rg.stream_plan", "rg.extras")]
        assert {k["name"] for k in steps} == {"rg.crc", "rg.stream_plan",
                                              "rg.extras"}
        assert sum(k["dur"] for k in steps) <= p["dur"]


def test_device_wait_nests_under_launches(extras_lake):
    from repro.core.fp_delta import fp_delta_encode, fp_delta_plan
    from repro.kernels.fp_delta import build_page_stream, decode_page_stream

    tracer, _ = _traced_scan(extras_lake)
    ids = {e["args"]["span_id"]: e for e in tracer.spans()}
    kids = _children(tracer)
    refines = tracer.spans("device.refine_launch")
    assert refines
    for e in refines:
        assert [k["name"] for k in kids[e["args"]["span_id"]]] == ["device.wait"]
    waits = {ids[e["args"]["parent_id"]]["name"]
             for e in tracer.spans("device.wait")}
    assert waits == {"device.refine_launch", "device.gather"}
    # a plain stream decode: the launch span holds the wait for its values
    vals = np.cumsum(np.full(3000, 0.25))
    stream = build_page_stream([fp_delta_plan(fp_delta_encode(vals)[0],
                                              len(vals), np.float64)])
    tracer = obs.enable()
    try:
        out = decode_page_stream(stream)
    finally:
        obs.disable()
    assert np.array_equal(out.view(np.int64), vals.view(np.int64))
    (launch,) = tracer.spans("device.decode_launch")
    (wait,) = tracer.spans("device.wait")
    assert wait["args"]["parent_id"] == launch["args"]["span_id"]


def test_span_cpu_time_and_counters(extras_lake):
    tracer, _ = _traced_scan(extras_lake)
    spans = tracer.spans()
    for e in spans:
        assert 0 <= e["args"]["cpu_us"] <= e["dur"] + 1000.0, e["name"]
    counters = obs.snapshot()["counters"]
    names = {e["name"] for e in spans}
    assert {k for k in counters if k.startswith("cpu_ns.")} == \
        {f"cpu_ns.{n}" for n in names}
    for n in names:
        mine = tracer.spans(n)
        total_us = sum(e["args"]["cpu_us"] for e in mine)
        assert abs(counters[f"cpu_ns.{n}"] / 1e3 - total_us) <= len(mine), n


def test_launch_padding_counters():
    from repro.core.fp_delta import fp_delta_encode, fp_delta_plan
    from repro.kernels.fp_delta import build_page_stream

    def plan(n):
        x = np.cumsum(np.full(n, 0.5))
        return fp_delta_plan(fp_delta_encode(x)[0], n, np.float64)

    obs.enable()
    try:
        # 3300 values: ceil(3300 / 1024) = 4 blocks, a power of two
        build_page_stream([plan(1000), plan(300), plan(2000)])
        c = obs.snapshot()["counters"]
        assert (c["launch.values"], c["launch.values_padded"]) == (3300, 4096)
        # 4100 values: 5 blocks, bucketed up to 8 (8192 lanes)
        build_page_stream([plan(4100)])
    finally:
        obs.disable()
    c = obs.snapshot()["counters"]
    assert c["launch.values"] == 3300 + 4100
    assert c["launch.values_padded"] == 4096 + 8192


def test_tracing_off_scan_identical_and_registry_empty(extras_lake):
    from repro.core.filters import Range

    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    kw = dict(bbox=(-60.0, -60.0, 60.0, 60.0), refine=True, device="jax",
              filter=Range("tag", 5, 30))
    _, traced = _traced_scan(extras_lake, filter=kw["filter"])
    # a fresh, empty tracer and registry, then a scan with tracing off
    tracer = obs.enable()
    obs.disable()
    tracer.annotation = Recorder
    g, e, _ = SpatialDatasetScanner(extras_lake).scan(**kw)
    assert _fingerprint(g, e) == _fingerprint(traced[0], traced[1])
    assert obs.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert tracer.events == [] and opened == []
    # the same hook opens one annotation per span once tracing is on
    obs.enable(reset=False).annotation = Recorder
    with obs.span("probe"):
        pass
    obs.disable()
    assert opened == ["probe"]


def test_spans_on_profiler_clock(extras_lake, tmp_path):
    """Every obs span appears as a TraceAnnotation on a host plane of a
    ``jax.profiler`` trace, and the benchmark's clock mapping (one
    perf_counter -> wall offset sampled as the trace starts) puts each
    span's start within 1 ms of its annotation's."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    sc = SpatialDatasetScanner(extras_lake)
    bbox = (-60.0, -60.0, 60.0, 60.0)
    sc.scan(bbox=bbox, refine=True, device="jax")   # compile off the trace
    trace_dir = str(tmp_path / "trace")
    tracer = obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        perf_to_wall = time.time_ns() - time.perf_counter_ns()
        sc.scan(bbox=bbox, refine=True, device="jax")
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    start_ns, host = None, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start_ns = int(dict(plane.stats)["profile_start_time"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(int(e.start_ns))
    shift = tracer.epoch_ns + perf_to_wall - start_ns
    names = {e["name"] for e in tracer.spans()}
    assert {"rg.plan", "rg.crc", "launch.build", "device.wait"} <= names
    for name in names:
        ours = sorted(int(e["ts"] * 1e3) + shift for e in tracer.spans(name))
        theirs = sorted(host.get(name, []))
        assert len(ours) == len(theirs), name
        gap = max(abs(a - b) for a, b in zip(ours, theirs))
        assert gap < 1_000_000, (name, gap)


def test_obs_imports_and_traces_without_jax():
    """``repro.obs`` needs only the stdlib and numpy: with ``jax`` made
    unimportable it imports, enables (no annotations) and records spans."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name.split('.')[0] == 'jax':\n"
        "            raise ImportError('jax blocked')\n"
        "sys.meta_path.insert(0, Block())\n"
        "from repro import obs\n"
        "tracer = obs.enable()\n"
        "assert tracer.annotation is None\n"
        "with obs.span('a'):\n"
        "    pass\n"
        "obs.disable()\n"
        "assert [e['name'] for e in tracer.spans()] == ['a']\n"
        "assert 'cpu_ns.a' in obs.snapshot()['counters']\n"
        "assert not any(m.split('.')[0] == 'jax' for m in sys.modules)\n"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_escape_path_counters(tmp_path, bench_data):
    """The planner counts the pages each escape resolver took: a PT-like
    coordinate page run resolves in closed form without the walk, and an
    ``n* = 5`` attribute page (eB's ``count``) hops; nothing is counted
    with telemetry off."""
    from repro.core.fp_delta import fp_delta_plan
    from repro.core.pages import PageMeta, encode_page, page_stream_plans

    cfg, data = bench_data("pt_taxi", 400, 2**31 + 3)
    root = str(tmp_path / "pt")
    write_dataset(root, columns=from_ragged(
        data["types"], data["coords"], data["part_sizes"],
        data["parts_per_record"]), n_shards=1, sort=cfg["sort"],
        page_values=int(cfg["page_values"]))
    sc = SpatialDatasetScanner(root)
    with sc.open_shard(0) as r:
        with open(r.path, "rb") as f:
            raw = f.read()
        (rg,) = r.footer["row_groups"]
        run = []
        for dx, dy in zip(rg["x_pages"], rg["y_pages"]):
            for m in (PageMeta.from_dict(dx), PageMeta.from_dict(dy)):
                run.append((raw[m.offset : m.offset + m.nbytes], m))
        codec = r.codec
    assert len(run) >= 4
    _, eb = bench_data("eb_points", 8192, 2**31 + 5)
    count_page, st = encode_page(eb["extras"]["count"], "fp_delta", "none")
    assert st["n_bits"] == 5 and st["n_resets"] > 4

    page_stream_plans(run, np.float64, codec)
    fp_delta_plan(count_page, 8192, np.int32)
    assert not obs.snapshot()["counters"]
    obs.enable()
    try:
        plans = page_stream_plans(run, np.float64, codec)
        c = dict(obs.snapshot()["counters"])
        fp_delta_plan(count_page, 8192, np.int32)
        c_all = obs.snapshot()["counters"]
    finally:
        obs.disable()
    escaped = sum(p.n_escapes > 4 for p in plans)
    assert c.get("fp_delta.escape_pages.closed", 0) == escaped > 0
    assert "fp_delta.escape_pages.walk" not in c
    assert c_all["fp_delta.escape_pages.hop"] == \
        c.get("fp_delta.escape_pages.hop", 0) + 1
    assert "fp_delta.escape_pages.walk" not in c_all
