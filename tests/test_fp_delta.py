"""FP-delta codec: paper Algorithms 1-3. Property tests via hypothesis.

``hypothesis`` is optional: without it, the property tests run fixed
deterministic samples (seeded numpy rng) instead of being skipped. The
structured/adversarial edge cases live in test_codec_edge.py and never
needed hypothesis.
"""

import numpy as np
import pytest

from repro.core.bitstream import bytes_to_words
from repro.core.fp_delta import (
    _FIXPOINT_MAX_ESCAPES,
    HEADER_BITS,
    _resolve_escapes_fixpoint,
    _resolve_escapes_scan,
    compute_best_delta_bits,
    delta_bit_histogram,
    encoded_size_bits,
    fp_delta_decode,
    fp_delta_encode,
    fp_delta_plan,
    fp_delta_plan_many,
    significant_bits,
    unzigzag,
    zigzag,
)

try:
    from hypothesis import given, settings, strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional wheel
    HAVE_HYPOTHESIS = False

_SEEDS = [0, 1, 7, 42, 1234]


def _ibits(x):
    return x.view(np.int64 if x.dtype.itemsize == 8 else np.int32)


def roundtrip(x, n_bits=None):
    payload, st_ = fp_delta_encode(x, n_bits=n_bits)
    y = fp_delta_decode(payload, len(x), x.dtype)
    assert np.array_equal(_ibits(x), _ibits(y)), "roundtrip not bit-exact"
    return st_


def _random_floats(seed, dtype, max_size=300):
    """Mix of smooth, jumpy, and special-value floats (NaN/Inf included)."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(0, max_size + 1))
    smooth = np.cumsum(rng.normal(0, 1e-4, k))
    if np.dtype(dtype) == np.float32:  # keep wild values f32-representable
        with np.errstate(invalid="ignore"):  # signalling-NaN casts warn
            wild = rng.integers(0, 2**32, k, dtype=np.uint32).view(np.float32).astype(np.float64)
    else:
        wild = rng.integers(0, 2**64, k, dtype=np.uint64).view(np.float64)
    pick = rng.integers(0, 4, k)
    out = np.where(pick == 0, wild, smooth)
    out[pick == 2] = np.nan
    out[pick == 3] = np.inf * rng.choice([-1.0, 1.0], int((pick == 3).sum()))
    return out.astype(dtype)


def _check_nstar_is_optimal(x):
    nstar = compute_best_delta_bits(x)
    sizes = {n: encoded_size_bits(x, n) for n in range(0, 64)}
    assert sizes[nstar] == min(sizes.values())


if HAVE_HYPOTHESIS:
    @given(hyp_st.lists(hyp_st.floats(allow_nan=True, allow_infinity=True, width=64),
                        min_size=0, max_size=300))
    @settings(max_examples=200, deadline=None)
    def test_roundtrip_arbitrary_f64(vals):
        roundtrip(np.array(vals, dtype=np.float64))

    @given(hyp_st.lists(hyp_st.floats(allow_nan=True, allow_infinity=True, width=32),
                        min_size=0, max_size=300))
    @settings(max_examples=100, deadline=None)
    def test_roundtrip_arbitrary_f32(vals):
        roundtrip(np.array(vals, dtype=np.float32))

    @given(hyp_st.lists(hyp_st.integers(-2**63, 2**63 - 1), min_size=1, max_size=200),
           hyp_st.integers(1, 63))
    @settings(max_examples=100, deadline=None)
    def test_roundtrip_forced_width_i64(vals, n):
        roundtrip(np.array(vals, dtype=np.int64), n_bits=n)

    @given(hyp_st.integers(-2**63, 2**63 - 1))
    def test_zigzag_involution(v):
        z = zigzag(np.array([v], np.int64), 64)
        assert unzigzag(z, 64)[0] == v
        # zigzag maps small magnitudes to small unsigned values
        if -(2**30) < v < 2**30:
            assert int(z[0]) <= 2 * abs(v)

    @given(hyp_st.lists(hyp_st.floats(allow_nan=False, allow_infinity=False, width=64),
                        min_size=2, max_size=300))
    @settings(max_examples=100, deadline=None)
    def test_nstar_is_optimal(vals):
        _check_nstar_is_optimal(np.array(vals, dtype=np.float64))

    @given(hyp_st.lists(hyp_st.floats(allow_nan=False, allow_infinity=False, width=64),
                        min_size=2, max_size=200))
    @settings(max_examples=50, deadline=None)
    def test_histogram_totals(vals):
        x = np.array(vals, dtype=np.float64)
        h = delta_bit_histogram(x)
        assert h.sum() == len(x) - 1  # paper: sum h = |X| - 1
else:
    @pytest.mark.parametrize("seed", _SEEDS)
    def test_roundtrip_arbitrary_f64(seed):
        roundtrip(_random_floats(seed, np.float64))

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_roundtrip_arbitrary_f32(seed):
        roundtrip(_random_floats(seed, np.float32))

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_roundtrip_forced_width_i64(seed):
        rng = np.random.default_rng(seed)
        vals = rng.integers(-2**63, 2**63 - 1, 200, dtype=np.int64)
        for n in (1, 2, 7, 21, 40, 63):
            roundtrip(vals, n_bits=n)

    def test_zigzag_involution():
        vals = np.concatenate([
            np.array([0, 1, -1, 2**62, -(2**62), 2**63 - 1, -(2**63)], np.int64),
            np.random.default_rng(0).integers(-2**63, 2**63 - 1, 500, dtype=np.int64),
        ])
        z = zigzag(vals, 64)
        assert np.array_equal(unzigzag(z, 64), vals)
        small = vals[np.abs(vals) < 2**30]
        assert (zigzag(small, 64).astype(np.int64) <= 2 * np.abs(small)).all()

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_nstar_is_optimal(seed):
        rng = np.random.default_rng(seed)
        _check_nstar_is_optimal(np.cumsum(rng.normal(0, 10.0 ** rng.integers(-9, 3), 300)))

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_histogram_totals(seed):
        x = np.random.default_rng(seed).normal(0, 1, 200)
        h = delta_bit_histogram(x)
        assert h.sum() == len(x) - 1  # paper: sum h = |X| - 1


# ---------------------------------------------------------------- structured
def test_gps_like_compresses(rng):
    x = np.round(np.cumsum(rng.normal(0, 1e-4, 50_000)) + 41.15, 6)
    st_ = roundtrip(x)
    assert st_.payload_bits < 0.75 * 64 * len(x), "should beat raw storage"


def test_payload_matches_cost_model(rng):
    x = np.cumsum(rng.normal(0, 1e-5, 10_000)) - 8.6
    n = compute_best_delta_bits(x)
    _, st_ = fp_delta_encode(x)
    assert st_.payload_bits == encoded_size_bits(x, n)


def test_raw_mode_on_random_bits(rng):
    x = rng.integers(-2**63, 2**63 - 1, 4096, dtype=np.int64).view(np.float64)
    st_ = roundtrip(x)
    assert st_.n_bits == 0  # optimizer must choose raw mode


def test_constant_column():
    x = np.full(10_000, -73.98542, dtype=np.float64)
    st_ = roundtrip(x)
    # all-zero deltas pack at n*=1: ~1 bit/value (the paper leaves RLE-after-
    # delta as future work in §5.2; a 64x saving nonetheless)
    assert st_.n_bits == 1
    assert st_.payload_bits < 1.2 * len(x) + 128


def test_significant_bits_exact():
    vals = np.array([0, 1, 2, 3, 4, 255, 256, 2**52, 2**63 - 1], np.uint64)
    exp = [0, 1, 2, 2, 3, 8, 9, 53, 63]
    assert list(significant_bits(vals, 64)) == exp


def test_marker_collision_escapes():
    # craft deltas equal to the all-ones marker at n bits
    n = 5
    marker_delta = unzigzag(np.array([(1 << n) - 1], np.uint64), 64)[0]
    base = np.int64(1000)
    x = np.array([base, base + marker_delta, base], np.int64)
    roundtrip(x, n_bits=n)


# --------------------------------------- escape resolution against the walk
def walk_plan(payload, n_values, dtype):
    """``(n_escapes, offsets, flags)`` as the candidate walk resolves them,
    behind the fixpoint for a handful of escapes: the reference for every
    faster resolver (a plan must equal it bit for bit, malformed payloads
    included)."""
    width = np.dtype(dtype).itemsize * 8
    words = bytes_to_words(payload)
    n = int(words[0]) & 0xFF
    start = HEADER_BITS + width
    n_deltas = n_values - 1
    n_escapes = max(0, min((len(payload) * 8 - start - n * n_deltas) // width,
                           n_deltas))
    resolved = None
    if n_escapes == 0:
        return 0, start + n * np.arange(n_deltas), np.zeros(n_deltas, bool)
    if n_escapes <= _FIXPOINT_MAX_ESCAPES:
        resolved = _resolve_escapes_fixpoint(words, start, n_deltas, n, width,
                                             n_escapes)
    if resolved is None:
        resolved = _resolve_escapes_scan(words, start, n_deltas, n, width,
                                         n_escapes)
    return n_escapes, *resolved


def assert_plan_is_walk(plan, payload, n_values, dtype):
    n_escapes, offs, flags = walk_plan(payload, n_values, dtype)
    assert plan.n_escapes == n_escapes
    assert plan.offsets.dtype == np.int64 and plan.flags.dtype == bool
    assert np.array_equal(plan.offsets, offs)
    assert np.array_equal(plan.flags, flags)


_INT = {64: np.int64, 32: np.int32}


def escape_values(case, width, n, rng, size=600):
    """An integer column that forces escapes at token width ``n``: a walk of
    deltas that fit, jumps that do not, and the case's adversarial shape."""
    fit = 1 << max(n - 2, 0) if n > 1 else 1  # |delta| < fit fits in n bits
    d = rng.integers(-fit + 1, fit, size) if n > 1 else np.zeros(size, np.int64)
    x = np.cumsum(d)
    jump = np.int64(1) << (width - 2)  # zigzag 2**(W-1): escapes at any n
    at = rng.choice(np.arange(2, size - 2), 12, replace=False)
    x[at] += jump  # two escapes each, out and back
    with np.errstate(over="ignore"):  # wrapping deltas are the codec's own
        _shape_case(case, x, at, jump, n)
    return x.astype(_INT[width])


def _shape_case(case, x, at, jump, n):
    if case == "nan_raw":  # escaped raw values of all ones (a NaN's bits)
        x[at[:6] - 1] = jump
        x[at[:6]] = -1
    elif case == "back_to_back":  # a run of consecutive escapes
        x[100:120] = np.where(np.arange(20) % 2, jump, -jump)
    elif case == "ones_before_marker":
        # the largest inline token (all ones but bit 0) right before a
        # marker: a run of 2n - 1 ones that starts a token early
        big = (np.int64(1) << (n - 1)) - 1
        for i in at[:6]:
            x[i - 1] = x[i - 2] + big
            x[i] = x[i - 1] + jump
    elif case == "marker_at_end":
        x[-1] = x[-2] + jump


ESCAPE_CASES = ["mixed", "nan_raw", "back_to_back", "ones_before_marker",
                "marker_at_end"]
ESCAPE_WIDTHS = ([(64, n) for n in (1, 2, 5, 7, 14, 15, 16, 31, 39, 47, 63)]
                 + [(32, n) for n in (1, 2, 5, 7, 14, 15, 16, 31)])


@pytest.mark.parametrize("width,n", ESCAPE_WIDTHS)
@pytest.mark.parametrize("case", ESCAPE_CASES)
def test_plan_matches_candidate_walk(case, width, n):
    x = escape_values(case, width, n, np.random.default_rng([width, n]))
    payload, st_ = fp_delta_encode(x, n_bits=n)
    assert st_.n_resets > _FIXPOINT_MAX_ESCAPES  # a candidate resolver runs
    plan = fp_delta_plan(payload, len(x), x.dtype)
    assert plan.n_escapes == st_.n_resets
    assert_plan_is_walk(plan, payload, len(x), x.dtype)
    assert np.array_equal(fp_delta_decode(payload, len(x), x.dtype), x)


@pytest.mark.parametrize("width,n", [(64, 39), (64, 15), (32, 20)])
def test_plan_many_keeps_pages_apart(width, n):
    """A run of ones that would straddle two pages of a batched run: page A
    ends in an escaped all-ones raw value, page B starts with an all-ones
    first value and an escape."""
    rng = np.random.default_rng(n)
    a = escape_values("mixed", width, n, rng, size=321)  # 320 deltas
    a[-2], a[-1] = 1 << (width - 2), -1
    b = escape_values("mixed", width, n, rng, size=400)
    b[0], b[1] = -1, 1 << (width - 2)
    pa, pb = fp_delta_encode(a, n_bits=n)[0], fp_delta_encode(b, n_bits=n)[0]
    hb = (HEADER_BITS + width) // 8
    assert pa[-1] == 0xFF and set(pb[1:hb]) == {0xFF}
    payloads, counts = [pa, pb, pa, pb], [len(a), len(b), len(a), len(b)]
    plans = fp_delta_plan_many(payloads, counts, a.dtype)
    for plan, payload, count in zip(plans, payloads, counts):
        assert_plan_is_walk(plan, payload, count, a.dtype)
        assert np.array_equal(plan.words, bytes_to_words(payload))
    assert np.array_equal(fp_delta_decode(pa, len(a), a.dtype), a)
    assert np.array_equal(fp_delta_decode(pb, len(b), b.dtype), b)


def _touching_pages(width, n):
    """Pages A and B of one dtype where A's last ``0xFF`` byte sits at the
    byte index just before B's first one past the header: runs of ones that
    touch once each page's bytes count from its own start. B's first escape
    is followed by four zero deltas, and for ``n < 17`` its raw value,
    -65536, holds a run of ones of its own that a marker could be taken
    from at the markers' residue."""
    dtype = _INT[width]
    jump = 1 << (width - 2)
    hb = (HEADER_BITS + width) // 8
    for cut in range(4):  # A's last raw value: its top ``cut`` bytes zero
        a = np.zeros(200, np.int64)
        a[[20, 60, 100, 140, -2]] = jump
        a[-1] = (1 << (width - 8 * cut)) - 1 if cut else -1
        a = a.astype(dtype)
        pa = fp_delta_encode(a, n_bits=n)[0]
        q = np.flatnonzero(np.frombuffer(pa, np.uint8) == 0xFF).max()
        for j0 in range(600):
            b = np.zeros(900, np.int64)
            b[j0 + 1 : j0 + 6] = -65536 if n < 17 else jump
            b[[700, 760, 820]] = jump
            b = b.astype(dtype)
            pb = fp_delta_encode(b, n_bits=n)[0]
            ff = np.flatnonzero(np.frombuffer(pb, np.uint8) == 0xFF)
            if ff[ff >= hb][0] == q + 1:
                return (a, pa), (b, pb)
    raise AssertionError("no touching pages")


@pytest.mark.parametrize("width,n", [(64, 16), (64, 39), (32, 16)])
def test_plan_many_cuts_runs_between_pages(width, n):
    """Runs of ones are grouped a page at a time: B's first marker stays
    B's even where its byte index follows A's last ``0xFF`` byte, and a
    stray run in B's first raw value is not taken for a marker."""
    (a, pa), (b, pb) = _touching_pages(width, n)
    payloads, counts = [pa, pb, pb, pa, pb], [len(a), len(b), len(b),
                                              len(a), len(b)]
    plans = fp_delta_plan_many(payloads, counts, a.dtype)
    for plan, payload, count in zip(plans, payloads, counts):
        assert_plan_is_walk(plan, payload, count, a.dtype)
    assert np.array_equal(fp_delta_decode(pb, len(b), b.dtype), b)


@pytest.mark.parametrize("width,n", [(64, 39), (64, 5), (32, 16)])
@pytest.mark.parametrize("cut", ["byte", "raw", "three_raws"])
def test_truncated_payload_plans_as_before(width, n, cut):
    """A payload cut short loses escapes from its length: the plan is the
    walk's, and no token or escaped raw value lies past the bytes left."""
    x = escape_values("mixed", width, n, np.random.default_rng([width, n, 1]))
    payload, st_ = fp_delta_encode(x, n_bits=n)
    short = payload[: -{"byte": 1, "raw": width // 8,
                        "three_raws": 3 * width // 8}[cut]]
    plan = fp_delta_plan(short, len(x), x.dtype)
    assert 0 < plan.n_escapes < st_.n_resets
    assert_plan_is_walk(plan, short, len(x), x.dtype)
    assert plan.offsets.max() + n <= 8 * len(short)
    assert (plan.offsets[plan.flags] + n + width).max() <= 8 * len(short)
    assert plan.offsets.max() + n <= 64 * (len(plan.words) - 1)


def _lake_pages(root):
    """Every page of a lake's shards: ``(column, dtype, meta, stored bytes)``
    in file order, and the codec."""
    from repro.core.pages import PageMeta
    from repro.dataset import SpatialDatasetScanner

    sc = SpatialDatasetScanner(root)
    for s in range(len(sc.manifest.shards)):
        with sc.open_shard(s) as r:
            with open(r.path, "rb") as f:
                raw = f.read()
            for rg in r.footer["row_groups"]:
                cols = [("x", r.coord_dtype, rg["x_pages"]),
                        ("y", r.coord_dtype, rg["y_pages"])]
                cols += [(k, np.dtype(r.extra_schema[k]), pages)
                         for k, pages in rg["extra"].items()]
                for k, dtype, pages in cols:
                    for d in pages:
                        m = PageMeta.from_dict(d)
                        yield (k, dtype, m, raw[m.offset : m.offset + m.nbytes],
                               r.codec)


@pytest.mark.parametrize("config,n_records", [("pt_taxi", 4000),
                                              ("eb_points", 150_000)])
def test_lake_pages_plan_as_the_walk(config, n_records, tmp_path, bench_data):
    """Every coordinate and attribute page of a small PT and eB lake written
    from the benchmark's generators: each column's pages planned together
    and one at a time, and the x and y pages of a row group as one stream
    (``page_stream_plans``), all equal to the walk."""
    from repro.core.columnar import from_ragged
    from repro.core.pages import decompress, page_stream_plans
    from repro.dataset import write_dataset

    cfg, data = bench_data(config, n_records, 2**31 + 97)
    write_dataset(str(tmp_path), columns=from_ragged(
        data["types"], data["coords"], data["part_sizes"],
        data["parts_per_record"]), extra=data["extras"],
        n_shards=int(cfg["n_shards"]), sort=cfg["sort"],
        page_values=int(cfg["page_values"]))
    by_column: dict = {}
    for k, dtype, meta, blob, codec in _lake_pages(str(tmp_path)):
        assert meta.encoding == "fp_delta"
        by_column.setdefault((k, dtype), []).append((meta, blob, codec))
    f64 = np.dtype(np.float64)
    escaped = 0
    for (k, dtype), pages in by_column.items():
        payloads = [decompress(blob, codec) for _, blob, codec in pages]
        counts = [meta.count for meta, _, _ in pages]
        together = fp_delta_plan_many(payloads, counts, dtype)
        for plan, payload, count in zip(together, payloads, counts):
            assert_plan_is_walk(plan, payload, count, dtype)
            assert_plan_is_walk(fp_delta_plan(payload, count, dtype), payload,
                                count, dtype)
            escaped += plan.n_escapes > _FIXPOINT_MAX_ESCAPES
    xs, ys = by_column[("x", f64)], by_column[("y", f64)]
    assert escaped >= len(xs)
    stream = page_stream_plans(
        [(blob, meta) for x, y in zip(xs, ys) for meta, blob, _ in (x, y)],
        f64, xs[0][2])
    for plan, (meta, blob, codec) in zip(
            stream, [p for pair in zip(xs, ys) for p in pair]):
        assert_plan_is_walk(plan, decompress(blob, codec), meta.count, f64)
