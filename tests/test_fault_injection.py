"""Fault-injection matrix for the fault-tolerant I/O layer.

Every scenario drives the real read path against the in-process range-GET
server with a deterministic fault schedule, then asserts two things: the
result is bit-identical to a clean local read (minus skipped shards for the
degraded scanner), and the recovery counters (ReadStats / SourceStats)
account for exactly the injected faults — no silent retries, no silent
data loss.
"""

import json
import os
import tempfile

import numpy as np
import pytest

from repro.core.columnar import from_ragged
from repro.core.reader import SpatialParquetReader
from repro.core.writer import MAGIC, MAGIC_V2, write_file
from repro.dataset import (
    DatasetError,
    DatasetManifest,
    ShardReadError,
    SpatialDatasetScanner,
    write_dataset,
)
from repro.io import (
    FAULT_CORRUPT,
    FAULT_ERROR,
    FAULT_STALL,
    FAULT_TRUNCATE,
    ChecksumError,
    FaultSpec,
    InProcessRangeServer,
    LocalFileSource,
    RangeRequestError,
    RemoteRangeSource,
    RetriesExhausted,
    crc32c,
)


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _point_cols(rng, n, spread=100.0):
    pts = np.round(rng.uniform(-spread, spread, (n, 2)), 6)
    return from_ragged(np.ones(n, np.uint8), pts,
                       np.ones(n, np.int64), np.ones(n, np.int64))


def _write_sample(path, rng, n=6000, **kw):
    cols = _point_cols(rng, n)
    tag = rng.integers(0, 100, n).astype(np.int32)
    kw.setdefault("page_values", 512)
    kw.setdefault("sort", "hilbert")
    kw.setdefault("row_group_records", 2000)
    return write_file(path, columns=cols, extra={"tag": tag},
                      extra_schema={"tag": "<i4"}, **kw)


def _remote(server, **kw):
    """A remote source tuned for tests: instant backoff, deterministic."""
    kw.setdefault("backoff_base", 0.0)
    kw.setdefault("backoff_max", 0.0)
    kw.setdefault("max_concurrency", 1)  # exact request-count assertions
    return RemoteRangeSource(server, **kw)


def _geo_equal(a, b):
    return (
        np.array_equal(a.x, b.x) and np.array_equal(a.y, b.y)
        and np.array_equal(a.types, b.types)
        and np.array_equal(a.rep, b.rep) and np.array_equal(a.defn, b.defn)
    )


@pytest.fixture
def sample(rng, tmp_path):
    p = str(tmp_path / "sample.spqf")
    _write_sample(p, rng)
    with SpatialParquetReader(p) as r:
        clean = r.read_columnar()
    return p, clean


# --------------------------------------------------------------- source unit
PAYLOAD = bytes(range(256)) * 64  # 16 KiB


def test_remote_source_reads_bytes_identically():
    server = InProcessRangeServer(PAYLOAD)
    with _remote(server, block_size=1024, cache_blocks=4) as src:
        assert src.read_at(0, 100) == PAYLOAD[:100]
        assert src.read_at(5000, 3000) == PAYLOAD[5000:8000]
        # reads past EOF are short, like a file
        assert src.read_at(len(PAYLOAD) - 10, 100) == PAYLOAD[-10:]
        buf = bytearray(500)
        assert src.readinto_at(1234, buf) == 500
        assert bytes(buf) == PAYLOAD[1234:1734]


def test_transient_5xx_retried_until_success():
    server = InProcessRangeServer(
        PAYLOAD, faults=[FaultSpec(FAULT_ERROR, times=2)])
    with _remote(server, max_retries=4) as src:
        assert src.read_at(0, 64) == PAYLOAD[:64]
        assert src.stats.retries == 2
        assert server.n_faulted(FAULT_ERROR) == 2
        assert server.n_requests == 3  # 2 failures + 1 success


def test_truncated_response_retried():
    server = InProcessRangeServer(
        PAYLOAD, faults=[FaultSpec(FAULT_TRUNCATE, times=1, drop_bytes=7)])
    with _remote(server) as src:
        assert src.read_at(0, 512) == PAYLOAD[:512]
        assert src.stats.retries == 1
        assert server.n_faulted(FAULT_TRUNCATE) == 1


def test_stalled_read_hits_deadline_and_retries():
    server = InProcessRangeServer(
        PAYLOAD, faults=[FaultSpec(FAULT_STALL, times=1, delay=0.08)])
    with _remote(server, timeout=0.02) as src:
        assert src.read_at(0, 64) == PAYLOAD[:64]
        assert src.stats.timeouts == 1
        assert src.stats.retries == 1


def test_retries_exhausted_is_attributed():
    server = InProcessRangeServer(
        PAYLOAD, faults=[FaultSpec(FAULT_ERROR, times=None)])  # never heals
    with _remote(server, max_retries=3) as src:
        with pytest.raises(RetriesExhausted) as ei:
            src.read_at(0, 64)
    err = ei.value
    assert err.attempts == 4  # 1 try + 3 retries
    assert err.offset == 0
    assert "503" in str(err.last_error)
    assert server.n_requests == 4


def test_fatal_4xx_fails_immediately_without_retry():
    server = InProcessRangeServer(
        PAYLOAD, faults=[FaultSpec(FAULT_ERROR, times=None, status=404)])
    with _remote(server, max_retries=5) as src:
        with pytest.raises(RangeRequestError):
            src.read_at(0, 64)
        assert src.stats.retries == 0
    assert server.n_requests == 1


def test_block_cache_hits_on_rescan():
    server = InProcessRangeServer(PAYLOAD)
    with _remote(server, block_size=1024, cache_blocks=32) as src:
        src.read_at(0, 4096)
        cold = server.n_requests
        src.read_at(0, 4096)
        assert server.n_requests == cold  # warm: zero new GETs
        assert src.stats.cache_hits >= 4
        # refresh bypasses and repopulates the cache
        src.read_at(0, 1024, refresh=True)
        assert server.n_requests == cold + 1


def test_request_coalescing_bounds_gets():
    server = InProcessRangeServer(PAYLOAD)
    with _remote(server, block_size=512, max_request_bytes=4096) as src:
        src.read_at(0, len(PAYLOAD))  # 32 blocks, 8 blocks per GET
        assert server.n_requests == 4


# ------------------------------------------------------------ reader + faults
def test_remote_read_bit_identical_to_local(sample):
    path, (geo, extras, _) = sample
    server = InProcessRangeServer(path)
    with SpatialParquetReader(source=_remote(server)) as r:
        rg, rex, st = r.read_columnar()
    assert _geo_equal(geo, rg)
    assert np.array_equal(extras["tag"], rex["tag"])
    assert st.checksum_failures == 0


def test_transient_faults_during_scan_are_recovered_and_counted(sample):
    path, (geo, extras, _) = sample
    size = os.path.getsize(path)
    # faults pinned to mid-file offsets so they hit data reads, not the
    # footer probes at open time (keeps the per-query ReadStats delta exact)
    mid = (size // 4, size // 2)
    server = InProcessRangeServer(path, faults=[
        FaultSpec(FAULT_ERROR, times=2, match_offset=mid),
        FaultSpec(FAULT_TRUNCATE, times=1, match_offset=mid),
    ])
    src = _remote(server, block_size=4096, timeout=5.0)
    with SpatialParquetReader(source=src) as r:
        rg, rex, st = r.read_columnar()
    assert _geo_equal(geo, rg)
    assert np.array_equal(extras["tag"], rex["tag"])
    assert st.retries == 3  # == injected faults, all transient
    assert server.n_faulted() == 3
    assert st.checksum_failures == 0


def test_corrupt_response_heals_via_checksum_refetch(sample, rng):
    path, (geo, _, _) = sample
    with SpatialParquetReader(path) as r:
        page = r.footer["row_groups"][1]["x_pages"][0]
    server = InProcessRangeServer(path, faults=[
        FaultSpec(FAULT_CORRUPT, times=1,
                  match_offset=(page["offset"], page["offset"] + page["nbytes"])),
    ])
    src = _remote(server, block_size=4096)
    with SpatialParquetReader(source=src) as r:
        rg, _, st = r.read_columnar()
    assert _geo_equal(geo, rg)  # recovered bytes, not the corrupt ones
    assert st.checksum_failures == 1
    assert st.retries >= 1  # the healing refetch
    assert server.n_faulted(FAULT_CORRUPT) == 1


def test_permanent_corruption_raises_attributed_checksum_error(sample):
    path, _ = sample
    with SpatialParquetReader(path) as r:
        page = r.footer["row_groups"][0]["x_pages"][0]
    # block_size > file size: every GET serves the whole object from offset
    # 0, so flip_at lands on the exact page byte in every (never-healing)
    # response — including the cache-bypassing checksum refetch
    server = InProcessRangeServer(path, faults=[
        FaultSpec(FAULT_CORRUPT, times=None, flip_at=page["offset"],
                  match_offset=(page["offset"], page["offset"] + page["nbytes"])),
    ])
    with SpatialParquetReader(source=_remote(server)) as r:
        with pytest.raises(ChecksumError) as ei:
            r.read_columnar()
    assert ei.value.offset == page["offset"]
    assert "checksum mismatch" in str(ei.value)


def test_on_disk_bitflip_detected_by_local_read(rng, tmp_path):
    p = str(tmp_path / "flip.spqf")
    _write_sample(p, rng)
    with SpatialParquetReader(p) as r:
        page = r.footer["row_groups"][0]["y_pages"][0]
    blob = bytearray(open(p, "rb").read())
    blob[page["offset"]] ^= 0x01
    open(p, "wb").write(bytes(blob))
    with SpatialParquetReader(p) as r:
        with pytest.raises(ChecksumError):
            r.read_columnar()
    # verification off: the reader no longer guards decode
    with SpatialParquetReader(p, verify_checksums=False) as r:
        g, _, st = r.read_columnar()  # decodes whatever the bits say
        assert st.checksum_failures == 0


def test_footer_corruption_detected_at_open(rng, tmp_path):
    p = str(tmp_path / "foot.spqf")
    _write_sample(p, rng)
    blob = bytearray(open(p, "rb").read())
    blob[-len(MAGIC_V2) - 4 - 10] ^= 0xFF  # inside the stored footer
    open(p, "wb").write(bytes(blob))
    with pytest.raises(ChecksumError):
        SpatialParquetReader(p)


def test_v1_files_read_without_checksums(rng, tmp_path):
    p2 = str(tmp_path / "v2.spqf")
    p1 = str(tmp_path / "v1.spqf")
    _write_sample(p2, rng)
    rng2 = np.random.default_rng(7)
    _write_sample(p1, rng2, checksums=False)
    raw = open(p1, "rb").read()
    assert raw.startswith(MAGIC) and raw.endswith(MAGIC)
    with SpatialParquetReader(p1) as r:
        assert r.checksum_algo is None
        g1, _, _ = r.read_columnar()
    with SpatialParquetReader(p2) as r:
        g2, _, _ = r.read_columnar()
    assert np.array_equal(np.sort(g1.x), np.sort(g2.x))


def test_device_path_verifies_checksums(sample):
    jax = pytest.importorskip("jax")
    del jax
    path, (geo, _, _) = sample
    with SpatialParquetReader(path) as r:
        page = r.footer["row_groups"][0]["x_pages"][0]
    blob = bytearray(open(path, "rb").read())
    blob[page["offset"] + 1] ^= 0x10
    corrupt = str(path) + ".bad"
    open(corrupt, "wb").write(bytes(blob))
    with SpatialParquetReader(corrupt) as r:
        with pytest.raises(ChecksumError):
            r.read_columnar(device="jax")
    os.unlink(corrupt)


# ----------------------------------------------------------- degraded scans
@pytest.fixture
def lake(rng, tmp_path):
    n = 8000
    cols = _point_cols(rng, n)
    root = str(tmp_path / "lake")
    os.makedirs(root)
    manifest = write_dataset(root, columns=cols, n_shards=4,
                             page_values=512)
    sc = SpatialDatasetScanner(root)
    clean_geo, _, _ = sc.scan()
    return root, manifest, clean_geo


def _corrupt_shard(root, manifest, i):
    path = os.path.join(root, manifest.shards[i].path)
    with SpatialParquetReader(path) as r:
        page = r.footer["row_groups"][0]["x_pages"][0]
    blob = bytearray(open(path, "rb").read())
    blob[page["offset"]] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    return path


def test_scanner_raise_policy_attributes_shard(lake):
    root, manifest, _ = lake
    _corrupt_shard(root, manifest, 2)
    sc = SpatialDatasetScanner(root, on_error="raise")
    with pytest.raises(ShardReadError) as ei:
        sc.scan()
    assert ei.value.shard_index == 2
    assert isinstance(ei.value.cause, ChecksumError)


def test_scanner_skip_policy_returns_surviving_shards(lake):
    root, manifest, clean_geo = lake
    _corrupt_shard(root, manifest, 1)
    sc = SpatialDatasetScanner(root, on_error="skip", shard_retries=1)
    geo, _, st = sc.scan()
    # bit-identical to the clean scan minus exactly the skipped shard
    lost = manifest.shards[1].n_records
    assert geo.n_records == clean_geo.n_records - lost
    healthy = np.sort(clean_geo.x)
    degraded = np.sort(geo.x)
    assert np.isin(degraded, healthy).all()
    assert st.shards_failed == 1
    assert st.failures[0].shard_index == 1
    assert st.failures[0].error_type == "ChecksumError"
    assert st.failures[0].attempts == 2  # 1 try + 1 shard retry
    assert st.shard_retries == 1
    assert st.shards_read == 3


def test_scanner_retry_policy_heals_transient_shard(lake):
    root, manifest, clean_geo = lake
    # shard 0's server 5xxs long enough to sink the first open (source does
    # 1 try, no retries), then heals: the scanner's shard-level retry wins
    servers = {}

    def factory(path):
        if path not in servers:
            faults = []
            if path.endswith(manifest.shards[0].path):
                faults = [FaultSpec(FAULT_ERROR, times=1)]
            servers[path] = InProcessRangeServer(path, faults=faults)
        return RemoteRangeSource(servers[path], max_retries=0,
                                 backoff_base=0.0, backoff_max=0.0)

    sc = SpatialDatasetScanner(root, on_error="retry", shard_retries=2,
                               source_factory=factory)
    geo, _, st = sc.scan()
    assert geo.n_records == clean_geo.n_records
    assert np.array_equal(np.sort(geo.x), np.sort(clean_geo.x))
    assert st.shard_retries == 1
    assert st.shards_failed == 0


def test_scanner_retry_policy_exhausts_to_error(lake):
    root, manifest, _ = lake
    _corrupt_shard(root, manifest, 0)
    sc = SpatialDatasetScanner(root, on_error="retry", shard_retries=1)
    with pytest.raises(ShardReadError) as ei:
        sc.scan()
    assert ei.value.shard_index == 0


@pytest.mark.parametrize("policy", ["retry", "skip"])
@pytest.mark.parametrize("fault", ["run", "compile"])
def test_scanner_device_errors_bypass_policy(lake, monkeypatch, policy, fault):
    """A device program that fails to compile or run is no fault of the
    shard: under retry/skip it propagates from the first attempt instead
    of turning into a retried or skipped shard and a short answer."""
    jax = pytest.importorskip("jax")
    import repro.kernels.fp_delta as fpd
    from repro.kernels import DeviceCompileError
    from repro.kernels.fp_delta import ops as fpd_ops

    root, _, _ = lake
    calls = []
    if fault == "run":
        def launch(*args, **kw):
            calls.append(1)
            raise jax.errors.JaxRuntimeError("INTERNAL: injected device fault")

        monkeypatch.setattr(fpd, "decode_refine_stream", launch)
        expected = jax.errors.JaxRuntimeError
    else:
        class Refused:  # a lowering refusal surfaces as ValueError
            def lower(self, *args, **kw):
                calls.append(1)
                raise ValueError("injected lowering refusal")

        monkeypatch.setattr(fpd_ops, "_COMPILED", {})
        monkeypatch.setattr(fpd_ops, "_refine_jit", lambda *a: Refused())
        expected = DeviceCompileError
    sc = SpatialDatasetScanner(root, on_error=policy, shard_retries=2)
    with pytest.raises(expected):
        sc.scan(bbox=(-100.0, -100.0, 100.0, 100.0), refine=True,
                device="jax", parallel=False)
    assert len(calls) == 1  # first shard, first attempt: no retry, no skip


def test_scanner_rejects_unknown_policy(lake):
    root, _, _ = lake
    with pytest.raises(ValueError):
        SpatialDatasetScanner(root, on_error="ignore")


# -------------------------------------------------------- manifest hardening
def test_manifest_errors_are_attributed(lake, tmp_path):
    root, manifest, _ = lake
    mp = os.path.join(root, "manifest.json")

    def check(content, needle):
        open(mp, "w").write(content)
        with pytest.raises(DatasetError) as ei:
            DatasetManifest.load(root)
        assert needle in str(ei.value)

    d = manifest.to_dict()
    check('{"format": "spatial-parquet-dataset"', "not valid JSON")
    check('[]', "JSON object")
    check('{"format": "something-else"}', "not a spatial-parquet-dataset")
    check(json.dumps({**d, "version": 99}), "newer than")
    check(json.dumps({k: v for k, v in d.items() if k != "shards"}),
          "missing key 'shards'")
    bad = json.loads(json.dumps(d)); del bad["shards"][0]["mbr"]
    check(json.dumps(bad), "missing key 'mbr'")
    bad = json.loads(json.dumps(d)); bad["shards"][0]["path"] = "../../etc/x"
    check(json.dumps(bad), "escapes the dataset root")
    bad = json.loads(json.dumps(d)); bad["shards"][0]["path"] = "/abs/path"
    check(json.dumps(bad), "escapes the dataset root")
    bad = json.loads(json.dumps(d)); bad["shards"][0]["n_records"] = -3
    check(json.dumps(bad), "non-negative")
    bad = json.loads(json.dumps(d)); bad["shards"].pop()
    check(json.dumps(bad), "partial write")
    missing = str(tmp_path / "nowhere")
    with pytest.raises(DatasetError) as ei:
        DatasetManifest.load(missing)
    assert "no manifest found" in str(ei.value)


def test_good_manifest_roundtrips_after_hardening(lake):
    root, manifest, _ = lake
    loaded = DatasetManifest.load(root)
    assert loaded.to_dict() == manifest.to_dict()


# ------------------------------------------------------------ lifecycle edges
def test_reader_closes_source_when_open_fails(tmp_path):
    p = str(tmp_path / "garbage.spqf")
    open(p, "wb").write(b"not a spatial parquet file at all........")
    src = LocalFileSource(p)
    with pytest.raises(ValueError):
        SpatialParquetReader(source=src)
    assert src.closed


def test_reader_close_is_idempotent(sample):
    path, _ = sample
    r = SpatialParquetReader(path)
    r.read_columnar()
    r.close()
    r.close()
    assert r.closed


def test_crc32c_known_vectors():
    # RFC 3720 / kernel test vectors for Castagnoli CRC
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(bytes(32)) == 0x8A9136AA
