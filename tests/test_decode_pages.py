"""Attribute pages decoded a column at a time: ``fp_delta_execute_many`` and
``decode_pages`` against per-page ``fp_delta_execute`` / ``decode_page`` bit
for bit, the shared narrow-page candidate pass of ``fp_delta_plan_many``,
and the reader's batched ``rg.extras`` on the fused, host and whole-row-group
paths of tiny PT-, eB- and MB-like lakes."""

import numpy as np
import pytest

from repro import obs
from repro.core import reader as reader_mod
from repro.core.columnar import from_ragged
from repro.core.filters import Range
from repro.core.fp_delta import (
    _FIXPOINT_MAX_ESCAPES,
    fp_delta_encode,
    fp_delta_execute,
    fp_delta_execute_many,
    fp_delta_plan,
    fp_delta_plan_many,
)
from repro.core.pages import (
    ENC_FP_DELTA,
    ENC_RAW,
    PageMeta,
    compress,
    decode_page,
    decode_pages,
    encode_page,
)
from repro.core.reader import SpatialParquetReader
from repro.core.writer import write_file
from repro.dataset import SpatialDatasetScanner, write_dataset
from repro.io import ChecksumError
from tests.test_fp_delta import assert_plan_is_walk, escape_values

_INT = {32: np.int32, 64: np.int64}
_FLOAT = {32: np.float32, 64: np.float64}


def _values(kind, width, rng):
    """An integer column of one page's shape: ``kind`` names its encoding
    (raw mode; narrow or wide ``n*``; no, few or dense escapes) or size."""
    dt = _INT[width]
    if kind == "raw":
        return rng.integers(np.iinfo(dt).min, np.iinfo(dt).max, 300,
                            dtype=dt), None
    if kind == "empty":
        return np.zeros(0, dt), None
    if kind == "one":
        return np.array([-7], dt), None
    n = 5 if kind.startswith("narrow") else (21 if width == 32 else 39)
    fit = 1 << (n - 2)
    x = np.cumsum(rng.integers(-fit + 1, fit, 700)).astype(np.int64)
    if kind.endswith("few"):  # the fixpoint resolves them
        x[[50, 300, 301]] += np.int64(1) << (width - 2)
    elif kind.endswith("dense"):  # the candidate resolvers do
        return escape_values("mixed", width, n, rng, size=700), n
    return x.astype(dt), n


KINDS = ["raw", "empty", "one", "narrow_none", "narrow_few", "narrow_dense",
         "wide_none", "wide_few", "wide_dense"]


def _encoded(kinds, width, floats, rng):
    """``(payload, values)`` per kind, at the kind's token width."""
    out = []
    for kind in kinds:
        x, n = _values(kind, width, rng)
        payload = fp_delta_encode(x, n_bits=n)[0]
        if kind.endswith("few"):
            assert 0 < fp_delta_plan(payload, len(x), x.dtype).n_escapes \
                <= _FIXPOINT_MAX_ESCAPES
        out.append((payload, x.view(_FLOAT[width]) if floats else x))
    return out


def _assert_bits(got, want):
    assert got.dtype == want.dtype
    assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


def _execute_both(encoded, dtype):
    plans = fp_delta_plan_many([p for p, _ in encoded],
                               [len(x) for _, x in encoded], dtype)
    want = (np.concatenate([fp_delta_execute(p) for p in plans])
            if plans else np.zeros(0, dtype))
    got = np.full(len(want), 99, dtype)
    assert fp_delta_execute_many(plans, got) is got
    return got, want


@pytest.mark.parametrize("floats", [False, True], ids=["int", "float"])
@pytest.mark.parametrize("width", [32, 64])
@pytest.mark.parametrize("kind", KINDS + ["mixed"])
def test_execute_many_is_execute_per_page(kind, width, floats):
    """Three pages of a kind (or every kind, twice, shuffled) executed
    together equal each executed alone, and the values encoded."""
    rng = np.random.default_rng([width, len(kind), floats])
    kinds = [kind] * 3 if kind != "mixed" else list(
        rng.permutation(KINDS + KINDS))
    encoded = _encoded(kinds, width, floats, rng)
    dtype = np.dtype(_FLOAT[width] if floats else _INT[width])
    got, want = _execute_both(encoded, dtype)
    _assert_bits(got, want)
    _assert_bits(got, np.concatenate([x for _, x in encoded]))


@pytest.mark.parametrize("width,n", [(64, 5), (64, 39), (32, 16)])
def test_execute_many_malformed_pages_as_alone(width, n):
    """Truncated payloads (escapes lost from the length, so the fixpoint
    may clip its offsets) beside well-formed ones: still each page's own
    execute, bit for bit."""
    rng = np.random.default_rng([width, n, 3])
    good = escape_values("mixed", width, n, rng, size=400)
    payload = fp_delta_encode(good, n_bits=n)[0]
    few = np.zeros(300, _INT[width])
    few[[40, 41, 200]] = 1 << (width - 2)
    few_payload = fp_delta_encode(few, n_bits=n)[0]
    pages = [(payload, good), (payload[: -width // 8], good),
             (few_payload[: -width // 8], few), (few_payload, few),
             (payload[:-1], good)]
    got, want = _execute_both(pages, np.dtype(_INT[width]))
    _assert_bits(got, want)
    cut = [(few_payload[: -width // 8], few)] * 2  # every page rides along
    got, want = _execute_both(cut, np.dtype(_INT[width]))
    _assert_bits(got, want)


def test_execute_many_groups_and_empty_batches():
    """More values than one group holds, and no plans at all."""
    rng = np.random.default_rng(11)
    x = np.cumsum(rng.integers(-1000, 1000, 30_000))
    encoded = [(fp_delta_encode(x[i:i + 7_000])[0], x[i:i + 7_000])
               for i in range(0, len(x), 7_000)]
    got, want = _execute_both(encoded, np.dtype(np.int64))
    _assert_bits(got, want)
    _assert_bits(got, x)
    out = np.zeros(0, np.float64)
    assert fp_delta_execute_many([], out) is out


def _pages(kinds, width, floats, rng, codec):
    """``(buf, meta)`` pages of the kinds, and their values laid end to
    end; ``raw_page`` is a page of the raw encoding."""
    dt = np.dtype(_FLOAT[width] if floats else _INT[width])
    pages, values = [], []
    for kind in kinds:
        if kind == "raw_page":
            x = rng.integers(-50, 50, 120).astype(_INT[width]).view(dt)
            buf, _ = encode_page(x, ENC_RAW, codec)
            enc = ENC_RAW
        else:
            (payload, x), = _encoded([kind], width, floats, rng)
            buf, enc = compress(payload, codec), ENC_FP_DELTA
        pages.append((buf, PageMeta(0, len(buf), len(x), 0, len(x), 0.0, 0.0,
                                    enc, 0, 0)))
        values.append(x)
    return pages, np.concatenate(values), dt


@pytest.mark.parametrize("codec", ["none", "gzip"])
@pytest.mark.parametrize("floats", [False, True], ids=["int", "float"])
@pytest.mark.parametrize("width", [32, 64])
def test_decode_pages_is_decode_page(width, floats, codec):
    """Raw pages among FP-delta pages of every kind, under a codec: one
    ``decode_pages`` equals ``decode_page`` a page, bit for bit."""
    rng = np.random.default_rng([width, floats, len(codec)])
    kinds = ["raw_page"] + KINDS + ["raw_page", "raw_page"] + KINDS[::-1]
    pages, values, dt = _pages(kinds, width, floats, rng, codec)
    want = np.concatenate([decode_page(b, m, dt, codec) for b, m in pages])
    got = np.full(len(want), 5, dt)
    assert decode_pages(pages, dt, codec, got) is got
    _assert_bits(got, want)
    _assert_bits(got, values)


@pytest.mark.parametrize("bad", ["dtype", "length", "2d", "strided"])
def test_out_contract(bad):
    """``decode_pages`` and ``fp_delta_execute_many`` keep ``decode_page``'s
    strict ``out``: a wrong buffer raises before any byte is parsed."""
    rng = np.random.default_rng(5)
    pages, values, dt = _pages(["narrow_dense", "raw_page"], 64, False, rng,
                               "none")
    n = len(values)
    out = {"dtype": np.empty(n, np.float64), "length": np.empty(n + 1, dt),
           "2d": np.empty((n, 1), dt), "strided": np.empty(2 * n, dt)[::2]}[bad]
    with pytest.raises(ValueError, match="out must be"):
        decode_pages(pages, dt, "none", out)
    plans = fp_delta_plan_many([pages[0][0]], [pages[0][1].count], dt)
    with pytest.raises(ValueError, match="out must be"):
        fp_delta_execute_many(plans, out)


def _tallies(fn):
    obs.enable()
    try:
        plans = fn()
        c = obs.snapshot()["counters"]
    finally:
        obs.disable()
    return plans, {k: c.get(f"fp_delta.escape_pages.{k}", 0)
                   for k in ("closed", "hop", "walk")}


@pytest.mark.parametrize("width", [32, 64])
def test_shared_candidate_pass_keeps_plans_and_tallies(width):
    """Narrow pages of several ``n*`` (a padded one among them, which the
    walk resolves), wide and escape-free pages between them: planned
    together they equal the walk and each page planned alone, with the
    same ``.closed`` / ``.hop`` / ``.walk`` tallies."""
    rng = np.random.default_rng([width, 9])
    payloads, counts = [], []
    for n in (5, 2, 14, 7, 1, 5, 16, 3):
        x = escape_values(["mixed", "back_to_back", "ones_before_marker"][
            n % 3], width, n, rng, size=500)
        payload = fp_delta_encode(x, n_bits=n)[0]
        # n = 3: the length promises one escape more than the payload holds
        payloads.append(payload if n != 3 else payload + bytes(width // 8))
        counts.append(len(x))
        y = np.cumsum(rng.integers(-9, 9, 200)).astype(_INT[width])
        payloads.append(fp_delta_encode(y)[0])
        counts.append(len(y))
    dtype = _INT[width]
    together, t_many = _tallies(
        lambda: fp_delta_plan_many(payloads, counts, dtype))
    alone, t_alone = _tallies(
        lambda: [fp_delta_plan(p, c, dtype) for p, c in zip(payloads, counts)])
    assert t_many == t_alone
    assert t_many["walk"] == 1 and t_many["hop"] >= 6
    assert t_many["closed"] + t_many["hop"] == 7
    for plan, single, payload, count in zip(together, alone, payloads, counts):
        assert_plan_is_walk(plan, payload, count, dtype)
        assert np.array_equal(plan.offsets, single.offsets)
        assert np.array_equal(plan.flags, single.flags)


# ------------------------------------------------------------- the reader
def _per_page(pages, dtype, codec, out):
    """The reference of the batched decode: ``decode_page`` a page."""
    w = 0
    for buf, meta in pages:
        decode_page(buf, meta, dtype, codec, out=out[w : w + meta.count])
        w += meta.count
    return out


@pytest.fixture(scope="module", params=[("pt_taxi", 1500),
                                        ("eb_points", 12_000),
                                        ("mb_buildings", 1500)],
                ids=lambda p: p[0])
def lake(request, tmp_path_factory, bench_data):
    """A tiny lake of a benchmark configuration, from its generator, with
    small pages so that a row group holds many; its scanner, the filter
    column's predicate and a box around the middle of the data."""
    name, n_records = request.param
    cfg, data = bench_data(name, n_records, 2**31 + 5)
    root = tmp_path_factory.mktemp(name)
    write_dataset(str(root), columns=from_ragged(
        data["types"], data["coords"], data["part_sizes"],
        data["parts_per_record"]), extra=data["extras"], n_shards=2,
        sort=cfg["sort"], page_values=256)
    sc = SpatialDatasetScanner(str(root))
    xs, ys = data["coords"][:, 0], data["coords"][:, 1]
    bbox = (float(np.quantile(xs, 0.2)), float(np.quantile(ys, 0.2)),
            float(np.quantile(xs, 0.7)), float(np.quantile(ys, 0.7)))
    flt = cfg["filter"]
    return sc, Range(flt["column"], flt["lo"], flt["hi"]), bbox


def _reads(sc, pred, bbox):
    out = {
        "fused": sc.scan(bbox, refine=True, device="jax"),
        "fused_filter": sc.scan(bbox, refine=True, device="jax", filter=pred),
        "host": sc.scan(bbox, refine=True, device="cpu", filter=pred),
    }
    for s in range(len(sc.manifest.shards)):
        with sc.open_shard(s) as r:
            out[f"row_group_{s}"] = (None, r.read_row_group(0).extras, None)
    return out


def test_reader_extras_are_per_page_decode(lake, monkeypatch):
    """Fused (with and without a filter), host and ``read_row_group`` paths
    return the extras (and geometry) that a page-at-a-time decode gives."""
    sc, pred, bbox = lake
    batched = _reads(sc, pred, bbox)
    monkeypatch.setattr(reader_mod, "decode_pages", _per_page)
    per_page = _reads(sc, pred, bbox)
    for path, (geo, extras, _) in batched.items():
        geo_ref, extras_ref, _ = per_page[path]
        assert extras.keys() == extras_ref.keys()
        assert len(next(iter(extras.values()))) > 0, path
        for k in extras:
            _assert_bits(extras[k], extras_ref[k])
        if geo is not None:
            assert np.array_equal(geo.x, geo_ref.x)
            assert np.array_equal(geo.rep, geo_ref.rep)


def test_obs_off_is_bit_identical(lake):
    sc, pred, bbox = lake
    off = sc.scan(bbox, refine=True, device="jax", filter=pred)
    obs.enable()
    try:
        on = sc.scan(bbox, refine=True, device="jax", filter=pred)
    finally:
        obs.disable()
    assert off[0].n_records == on[0].n_records > 0
    assert np.array_equal(off[0].x, on[0].x)
    for k in off[1]:
        _assert_bits(on[1][k], off[1][k])


def test_extras_span_per_row_group_with_counters(lake):
    """One ``rg.extras`` a row group, under its ``rg.plan``, its page count
    a batch's; the extras' checksums an ``rg.crc`` each under it; counters
    ``reader.extras_pages`` and ``reader.extras_batches`` agree."""
    sc, pred, bbox = lake
    tracer = obs.enable()
    try:
        sc.scan(bbox, refine=True, device="jax", filter=pred)
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
    ids = {e["args"]["span_id"]: e for e in tracer.spans()}
    plans, extras = tracer.spans("rg.plan"), tracer.spans("rg.extras")
    assert plans and len(extras) == len(plans)
    assert sorted(e["args"]["parent_id"] for e in extras) == sorted(
        p["args"]["span_id"] for p in plans)
    n_cols = len(sc.manifest.extra_schema)
    crcs = [e for e in tracer.spans("rg.crc")
            if ids[e["args"]["parent_id"]]["name"] == "rg.extras"]
    assert len(crcs) == n_cols * len(extras)
    pages = sum(e["args"]["pages"] for e in extras)
    assert counters["reader.extras_batches"] == n_cols * len(extras)
    assert counters["reader.extras_pages"] == n_cols * pages
    assert pages > len(extras)  # several pages a batch


def _corrupt_points_file(rng, tmp_path, monkeypatch, locate):
    """A one-row-group point file with two extra columns, a byte of the
    page ``locate(row_group_footer)`` flipped; every host decode and every
    device launch is recorded instead of run."""
    n = 6000
    pts = np.round(rng.uniform(-100, 100, (n, 2)), 6)
    cols = from_ragged(np.ones(n, np.uint8), pts, np.ones(n, np.int64),
                       np.ones(n, np.int64))
    path = str(tmp_path / "x.spqf")
    write_file(path, columns=cols, page_values=512,
               extra={"ts": rng.integers(0, 1 << 40, n),
                      "tag": rng.integers(0, 9, n).astype(np.int32)},
               extra_schema={"ts": "<i8", "tag": "<i4"})
    with SpatialParquetReader(path) as r:
        page = locate(r.footer["row_groups"][0])
    blob = bytearray(open(path, "rb").read())
    blob[page["offset"] + 2] ^= 0x40
    open(path, "wb").write(bytes(blob))
    import repro.kernels.fp_delta as fpd

    calls = []
    record = lambda *a, **k: calls.append(a)  # noqa: E731
    monkeypatch.setattr(reader_mod, "decode_pages", record)
    for name in ("decode_refine_stream", "decode_stream_device"):
        monkeypatch.setattr(fpd, name, record)
    return path, page, calls


def _read_all(path, device):
    with SpatialParquetReader(path) as r:
        with pytest.raises(ChecksumError) as ei:
            r.read_columnar(bbox=(-100.0, -100.0, 100.0, 100.0), refine=True,
                            device=device)
    return ei.value


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_corrupt_attribute_page_raises_before_decode(rng, tmp_path,
                                                     monkeypatch, device):
    """A flipped byte in an attribute page raises a ``ChecksumError`` that
    names the column and the page, before any page of its row group
    decodes (attribute or coordinate) or launches."""
    path, page, calls = _corrupt_points_file(
        rng, tmp_path, monkeypatch, lambda rg: rg["extra"]["tag"][3])
    err = _read_all(path, device)
    assert err.what == "extra column 'tag' page 3"
    assert err.offset == page["offset"]
    assert calls == []


@pytest.mark.parametrize("device", ["cpu", "jax"])
def test_corrupt_coordinate_page_raises_before_decode(rng, tmp_path,
                                                      monkeypatch, device):
    """The same for the row group's last y page: every checksum of a row
    group is verified before any of its pages decodes, on both executors."""
    path, page, calls = _corrupt_points_file(
        rng, tmp_path, monkeypatch, lambda rg: rg["y_pages"][-1])
    with SpatialParquetReader(path) as r:
        last = len(r.footer["row_groups"][0]["y_pages"]) - 1
    err = _read_all(path, device)
    assert err.what == f"y page {last} of row group 0"
    assert err.offset == page["offset"]
    assert calls == []
