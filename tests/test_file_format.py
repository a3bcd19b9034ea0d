"""Spatial Parquet file format: write/read/filter correctness + the §4 index."""

import os
import tempfile

import numpy as np
import pytest

from repro.core import (
    SpatialParquetReader,
    SpatialParquetWriter,
    write_file,
)
from repro.core.columnar import from_ragged
from repro.core.rle import decode_levels, encode_levels, rle_decode, rle_encode
from repro.core.pages import have_codec
from repro.core.sfc import hilbert_key, z_key
from tests.geom_helpers import random_geometry


def _point_cols(rng, n, spread=100.0):
    pts = np.round(rng.uniform(-spread, spread, (n, 2)), 6)
    return pts, from_ragged(np.ones(n, np.uint8), pts,
                            np.ones(n, np.int64), np.ones(n, np.int64))


@pytest.mark.parametrize("codec", ["none", "gzip", "zstd"])
@pytest.mark.parametrize("encoding", ["fp_delta", "raw"])
def test_roundtrip_codecs(rng, codec, encoding):
    if not have_codec(codec):
        pytest.skip(f"codec {codec!r} unavailable (optional wheel not installed)")
    pts, cols = _point_cols(rng, 5000)
    p = tempfile.mktemp(".spqf")
    write_file(p, columns=cols, codec=codec, encoding=encoding, page_values=1024)
    with SpatialParquetReader(p) as r:
        geo, _, st = r.read_columnar()
    assert geo.n_records == 5000
    assert np.array_equal(np.sort(geo.x), np.sort(pts[:, 0]))
    os.unlink(p)


def test_bbox_filter_equals_bruteforce(rng):
    pts, cols = _point_cols(rng, 20_000)
    p = tempfile.mktemp(".spqf")
    write_file(p, columns=cols, sort="hilbert", page_values=512,
               row_group_records=1 << 20)
    q = (-95.0, -95.0, -70.0, -70.0)
    with SpatialParquetReader(p) as r:
        geo, _, st = r.read_columnar(bbox=q, refine=True)
    inq = ((pts[:, 0] >= q[0]) & (pts[:, 0] <= q[2])
           & (pts[:, 1] >= q[1]) & (pts[:, 1] <= q[3]))
    assert geo.n_records == int(inq.sum())
    assert st.pages_read < st.pages_total, "index should prune pages"
    os.unlink(p)


def test_mixed_geometry_file_roundtrip(rng):
    geoms = [random_geometry(np.random.default_rng(s)) for s in range(200)]
    p = tempfile.mktemp(".spqf")
    codec = "zstd" if have_codec("zstd") else "gzip"
    write_file(p, geometries=geoms, codec=codec, row_group_records=64)
    with SpatialParquetReader(p) as r:
        back, _ = r.read()
    assert back == geoms
    os.unlink(p)


def test_sorted_write_clusters_pages(rng):
    pts, cols = _point_cols(rng, 30_000)
    sizes = {}
    for sort in (None, "hilbert"):
        p = tempfile.mktemp(".spqf")
        write_file(p, columns=cols, sort=sort, page_values=2048)
        with SpatialParquetReader(p) as r:
            # average page bbox area is much tighter when sorted
            areas = [
                max(e.bbox[2] - e.bbox[0], 0) * max(e.bbox[3] - e.bbox[1], 0)
                for e in r.index.entries
            ]
            sizes[sort] = np.mean(areas)
        os.unlink(p)
    assert sizes["hilbert"] < 0.25 * sizes[None]


def test_extra_columns_and_projection(rng):
    pts, cols = _point_cols(rng, 4000)
    ts = rng.integers(0, 1 << 40, 4000)
    p = tempfile.mktemp(".spqf")
    write_file(p, columns=cols, extra={"ts": ts}, extra_schema={"ts": "<i8"},
               sort="z", page_values=512)
    with SpatialParquetReader(p) as r:
        _, extras, _ = r.read_columnar(columns=("ts",))
        assert np.array_equal(np.sort(extras["ts"]), np.sort(ts))
    os.unlink(p)


def test_streaming_writer_multiple_groups(rng):
    p = tempfile.mktemp(".spqf")
    total = 0
    with SpatialParquetWriter(p, row_group_records=1000, sort="hilbert") as w:
        for i in range(5):
            _, cols = _point_cols(np.random.default_rng(i), 700)
            w.write_columns(cols)
            total += 700
    with SpatialParquetReader(p) as r:
        assert r.n_records == total
        assert len(r.footer["row_groups"]) >= 3
        geo, _, _ = r.read_columnar()
        assert geo.n_records == total
    os.unlink(p)


def test_corrupt_magic_rejected(tmp_path):
    p = tmp_path / "bad.spqf"
    p.write_bytes(b"NOTAPARQUETFILE")
    with pytest.raises(ValueError):
        SpatialParquetReader(str(p))


# ----------------------------------------------------------------- RLE / SFC
def test_rle_roundtrip(rng):
    v = np.repeat(rng.integers(0, 7, 50), rng.integers(1, 2000, 50)).astype(np.uint8)
    assert np.array_equal(rle_decode(rle_encode(v)), v)
    assert len(rle_encode(v)) < v.nbytes // 4  # big runs compress hard


def test_levels_roundtrip(rng):
    for vals in (rng.integers(0, 4, 10_000), np.zeros(5000), rng.integers(0, 2, 17)):
        v = vals.astype(np.uint8)
        assert np.array_equal(decode_levels(encode_levels(v)), v)


@pytest.mark.parametrize("top", [1, 3, 7, 15, 200])
@pytest.mark.parametrize("n", [1, 7, 8, 9, 65, 4099])
def test_packed_levels_match_bit_reader(rng, top, n):
    """Every packed width (byte-tiling 1/2/4/8 bits or not) decodes to the
    values the general bit reader finds in the same stream."""
    from repro.core.bitstream import bytes_to_words, unpack_fixed
    from repro.core.rle import MODE_PACKED

    v = rng.integers(0, top + 1, n).astype(np.uint8)
    v[0] = top  # pins the width at top's bit length
    v[1::2] = np.arange(len(v[1::2])) % (top + 1)  # defeats the RLE choice
    buf = encode_levels(v)
    got = decode_levels(buf)
    assert got.dtype == np.uint8 and np.array_equal(got, v)
    if buf[0] == MODE_PACKED:
        width, count = buf[1], len(v)
        want = unpack_fixed(bytes_to_words(buf[6:]), 0, count, width)
        assert np.array_equal(got, want.astype(np.uint8))


def test_hilbert_locality(rng):
    # consecutive hilbert cells are spatial neighbors: d(k, k+1) == 1 step
    order = 6
    n = 1 << order
    keys = hilbert_key(
        np.repeat(np.arange(n), n).astype(np.uint64),
        np.tile(np.arange(n), n).astype(np.uint64),
        order,
    )
    inv = np.argsort(keys)
    xs, ys = inv // n, inv % n
    d = np.abs(np.diff(xs)) + np.abs(np.diff(ys))
    assert d.max() == 1, "hilbert curve must move one cell at a time"


def test_zcurve_bijective(rng):
    xq = rng.integers(0, 2**16, 5000).astype(np.uint64)
    yq = rng.integers(0, 2**16, 5000).astype(np.uint64)
    keys = z_key(xq, yq)
    assert len(np.unique(keys)) == len(np.unique(xq * (1 << 16) + yq))
