"""Differential/property suite for the on-device FP-delta page decode.

Three independent implementations must agree **bit-for-bit** on every
stream:

* host ``fp_delta_decode`` (numpy; the paper-exact oracle),
* ``decode_stream_ref`` (pure jnp; one flat global segmented scan),
* the Pallas kernel ``decode_stream_blocks`` in interpret mode (block-local
  scans + associative carry stitch — structurally different from the ref).

The grid covers token widths, escape densities (none / sparse / dense /
every-delta), page sizes around the kernel's STREAM_BLOCK, reset-segment
layouts, and multi-page streams mixing raw-mode pages in. Property tests
follow the PR 1 optional-deps convention: with ``hypothesis`` installed
they generate adversarial floats; without it they run fixed seeded samples
instead of being skipped.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.fp_delta import (
    fp_delta_decode,
    fp_delta_encode,
    fp_delta_execute,
    fp_delta_plan,
)
from repro.core.pages import PageMeta, page_stream_plans
from repro.kernels.fp_delta import (
    STREAM_BLOCK,
    build_page_stream,
    chunk_plan_pairs,
    decode_page_stream,
)

try:
    from hypothesis import given, settings, strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional wheel
    HAVE_HYPOTHESIS = False

_SEEDS = [0, 1, 7, 42, 1234]


def _ibits(x):
    return x.view(np.int64 if x.dtype.itemsize == 8 else np.int32)


def tri_decode(pages, n_bits=None):
    """Encode pages, then decode them through all three back ends and
    assert bitwise agreement. ``pages``: list of 1-D arrays (one stream)."""
    dtype = pages[0].dtype
    enc = [fp_delta_encode(p, n_bits=n_bits)[0] for p in pages]
    plans = [fp_delta_plan(e, len(p), dtype) for e, p in zip(enc, pages)]
    host = [fp_delta_decode(e, len(p), dtype) for e, p in zip(enc, pages)]
    for p, h in zip(pages, host):  # host decode must already round-trip
        assert np.array_equal(_ibits(p), _ibits(h))
    stream = build_page_stream(plans)
    ref_out = decode_page_stream(stream, use_pallas=False)
    pal_out = decode_page_stream(stream, use_pallas=True, interpret=True)
    got_ref = np.split(ref_out, np.cumsum(stream.counts)[:-1])
    got_pal = np.split(pal_out, np.cumsum(stream.counts)[:-1])
    for h, r_, k_ in zip(host, got_ref, got_pal):
        assert np.array_equal(_ibits(h), _ibits(r_)), "jnp oracle != host"
        assert np.array_equal(_ibits(h), _ibits(k_)), "Pallas kernel != host"
    return plans


def _page(rng, n, density, dtype):
    """One page of ``n`` values with the requested escape density."""
    x = (np.cumsum(rng.normal(0, 1e-4, n)) + 40.7).astype(dtype)
    if density == "none":
        return x
    if density == "sparse":
        hits = rng.integers(0, n, max(n // 500, 2))
        x[hits] = rng.normal(0, 1e30, len(hits)).astype(dtype)
        return x
    # "dense": wild bit patterns force an escape on nearly every delta
    if np.dtype(dtype) == np.float32:
        return rng.integers(0, 2**32, n, dtype=np.uint32).view(np.float32)
    return rng.integers(0, 2**64, n, dtype=np.uint64).view(np.float64)


# ------------------------------------------------------------ the main grid
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("density", ["none", "sparse", "dense"])
@pytest.mark.parametrize(
    "n", [1, 2, STREAM_BLOCK - 1, STREAM_BLOCK, STREAM_BLOCK + 1, 3000]
)
def test_stream_grid(rng, dtype, density, n):
    tri_decode([_page(rng, n, density, dtype)])


@pytest.mark.parametrize("dtype,n_bits", [
    (np.float32, 1), (np.float32, 5), (np.float32, 13), (np.float32, 31),
    (np.float64, 1), (np.float64, 13), (np.float64, 43), (np.float64, 63),
])
def test_stream_forced_widths(rng, dtype, n_bits):
    x = _page(rng, 2050, "sparse", dtype)
    plans = tri_decode([x], n_bits=n_bits)
    assert plans[0].n == n_bits


def test_stream_raw_mode(rng):
    # n_bits=0 is raw mode: no delta tokens, every value a W-bit anchor
    for dtype in (np.float32, np.float64):
        plans = tri_decode([_page(rng, 700, "dense", dtype)], n_bits=0)
        assert plans[0].n == 0 and plans[0].n_escapes == 0


# ----------------------------------------------------- reset-segment layouts
def _with_jumps(n, where, dtype=np.float64):
    x = np.linspace(1.0, 2.0, n).astype(dtype)
    x[np.asarray(where)] = np.asarray(
        [(-1e308 if i % 2 else 1e308) for i in range(len(where))], dtype)
    return x


@pytest.mark.parametrize("layout", [
    [1],                         # escape on the very first delta
    [2049],                      # escape on the last delta
    [700, 701, 702, 703],        # consecutive escapes (zero-length segments)
    list(range(1, 2050, 2)),     # alternating: every other delta escapes
    [1023, 1024, 1025],          # escapes straddling a kernel block boundary
])
def test_reset_segment_layouts(layout):
    x = _with_jumps(2050, layout)
    # force a real token width: dense layouts would otherwise make the n*
    # optimizer fall back to raw mode and sidestep the escape machinery
    plans = tri_decode([x], n_bits=13)
    assert plans[0].n_escapes >= len(layout) // 2


def test_marker_collision_escapes():
    # a delta whose zigzag equals the all-ones marker must escape (the
    # classic FP-delta corner); device decode must reproduce it exactly
    from repro.core.fp_delta import unzigzag
    n = 13
    marker_delta = unzigzag(np.array([(1 << n) - 1], np.uint64), 64)[0]
    base = np.int64(1 << 40)
    vals = np.empty(600, np.int64)
    vals[0::2] = base
    vals[1::2] = base + marker_delta
    plans = tri_decode([vals.view(np.float64)], n_bits=n)
    assert plans[0].n_escapes >= 250


def test_constant_run_and_single_segment(rng):
    tri_decode([np.full(2100, -17.25, np.float64)])
    tri_decode([np.full(STREAM_BLOCK * 2, 3.5, np.float32)])


# ----------------------------------------------------------- batched streams
def test_multi_page_stream_mixed(rng):
    """One launch over pages with different n*, raw-mode and empty pages."""
    pages, n_bits = [], []
    for k, nb in [(1, None), (STREAM_BLOCK - 1, None), (0, None),
                  (STREAM_BLOCK + 1, 0), (3000, 7), (2, None)]:
        pages.append(_page(rng, k, "sparse" if k > 2 else "none", np.float64))
        n_bits.append(nb)
    dtype = np.float64
    enc = [fp_delta_encode(p, n_bits=nb)[0] for p, nb in zip(pages, n_bits)]
    plans = [fp_delta_plan(e, len(p), dtype) for e, p in zip(enc, pages)]
    host = [fp_delta_decode(e, len(p), dtype) for e, p in zip(enc, pages)]
    stream = build_page_stream(plans)
    for use_pallas in (False, True):
        vals = decode_page_stream(stream, use_pallas=use_pallas, interpret=True)
        outs = np.split(vals, np.cumsum(stream.counts)[:-1])
        assert len(outs) == len(plans)
        for h, o in zip(host, outs):
            assert np.array_equal(_ibits(h), _ibits(o))


def test_launch_chunking_and_oversized_page_fallback(rng, monkeypatch):
    """With a tiny launch cap, chunk_plan_pairs must split x/y page pairs
    across launches and send a pair too large for one to the host — same
    bits either way."""
    import repro.kernels.fp_delta.ops as fpd_ops

    sizes = (1200, 600, 2000, 1500, 900, 900)  # x, y pages of three pairs
    pages = [_page(rng, n, "sparse", np.float64) for n in sizes]
    enc = [fp_delta_encode(p)[0] for p in pages]
    plans = [fp_delta_plan(e, len(p), np.float64) for e, p in zip(enc, pages)]
    # cap below the largest page: forces multi-launch + the host fallback
    cap = (len(plans[2].words) - 1) * 64 - 1
    monkeypatch.setattr(fpd_ops, "_MAX_LAUNCH_BITS", cap)
    with pytest.raises(ValueError, match="per-launch cap"):
        build_page_stream([plans[2]])
    pairs = [(0, 1), (1, 2), (2, 3)]  # one record a pair
    outs, kinds = [], []
    for kind, cplans, _, _ in chunk_plan_pairs(plans, pairs):
        kinds.append(kind)
        if kind == "host":
            outs += [fp_delta_execute(p) for p in cplans]
            continue
        stream = build_page_stream(cplans)
        vals = decode_page_stream(stream, use_pallas=True, interpret=True)
        outs += np.split(vals, np.cumsum(stream.counts)[:-1])
    assert kinds == ["dev", "host", "dev"]
    for p, o in zip(pages, outs, strict=True):
        assert np.array_equal(_ibits(p), _ibits(o))


def test_mixed_width_stream_rejected(rng):
    p32 = fp_delta_plan(fp_delta_encode(_page(rng, 50, "none", np.float32))[0],
                        50, np.float32)
    p64 = fp_delta_plan(fp_delta_encode(_page(rng, 50, "none", np.float64))[0],
                        50, np.float64)
    with pytest.raises(ValueError, match="mixed widths"):
        build_page_stream([p32, p64])


# ----------------------------------------------------------------- plan API
def test_plan_matches_encoder_stats(rng):
    x = _page(rng, 4000, "sparse", np.float64)
    payload, st = fp_delta_encode(x)
    plan = fp_delta_plan(payload, len(x), np.float64)
    assert plan.n == st.n_bits
    assert plan.n_escapes == st.n_resets == int(plan.flags.sum())
    assert plan.n_values == len(x)
    # offsets strictly increase, and every escaped token is followed by a
    # W-bit raw value before the next token starts
    assert (np.diff(plan.offsets) > 0).all()
    gaps = np.diff(np.append(plan.offsets, st.payload_bits))
    assert (gaps[plan.flags] >= plan.n + 64).all()
    y = fp_delta_execute(plan)
    assert np.array_equal(_ibits(x), _ibits(y))


def test_page_stream_plans_raw_and_unknown_encodings(rng):
    """A raw page becomes a synthetic raw-mode plan (n* = 0) that decodes to
    its stored bits; an unknown encoding is refused."""
    x = _page(rng, 300, "dense", np.float64)
    meta = PageMeta(0, x.nbytes, len(x), 0, 1, 0.0, 0.0, "raw", 0, 0)
    (plan,) = page_stream_plans([(x.tobytes(), meta)], np.float64, "none")
    assert plan.n == 0 and plan.n_escapes == 0
    assert np.array_equal(_ibits(x), _ibits(fp_delta_execute(plan)))
    bad = PageMeta(0, 8, 1, 0, 1, 0.0, 0.0, "dict", 0, 0)
    with pytest.raises(ValueError, match="unknown encoding"):
        page_stream_plans([(b"\x00" * 8, bad)], np.float64, "none")


# -------------------------------------------------------- reader-level diff
def test_reader_device_bit_identical_pt025(tmp_path):
    """Acceptance: read_columnar(device="jax") == host path on PT @ 0.25."""
    from repro.core.reader import SpatialParquetReader
    from repro.core.writer import write_file
    from repro.data.synthetic import DATASETS

    cols = DATASETS["PT"](n_traj=2000)  # PT @ 0.25 (SCALE_1 is 8000)
    path = tmp_path / "pt025.spqf"
    # small pages: the bbox below can prune, and one row group batches many
    # pages into a single device launch
    write_file(path, columns=cols, codec="none", sort="hilbert",
               page_values=4096)
    with SpatialParquetReader(path) as r:
        g0, e0, s0 = r.read_columnar()
        g1, e1, s1 = r.read_columnar(device="jax")
        assert np.array_equal(_ibits(g0.x), _ibits(g1.x))
        assert np.array_equal(_ibits(g0.y), _ibits(g1.y))
        assert np.array_equal(g0.types, g1.types)
        assert s0 == s1
        # pruned bbox read: device path must agree page-for-page
        x0, y0 = float(g0.x.min()), float(g0.y.min())
        bbox = (x0, y0, float(np.median(g0.x)), float(np.median(g0.y)))
        g2, _, s2 = r.read_columnar(bbox=bbox)
        g3, _, s3 = r.read_columnar(bbox=bbox, device="jax")
        assert s2.pages_read < s2.pages_total  # the bbox actually pruned
        assert np.array_equal(_ibits(g2.x), _ibits(g3.x))
        assert np.array_equal(_ibits(g2.y), _ibits(g3.y))
        assert s2 == s3
        with pytest.raises(ValueError, match="device"):
            r.read_columnar(device="tpu")


def test_reader_device_raw_and_float32(tmp_path):
    """Raw-encoded pages and float32 coords through the device path."""
    from repro.core.reader import SpatialParquetReader
    from repro.core.writer import write_file
    from repro.data.synthetic import DATASETS

    import dataclasses

    cols = DATASETS["eB"](n_points=3000)
    cols32 = dataclasses.replace(
        cols, x=cols.x.astype(np.float32), y=cols.y.astype(np.float32))
    for enc, dtype in [("raw", np.float64), ("fp_delta", np.float32)]:
        c = cols if dtype == np.float64 else cols32
        path = tmp_path / f"{enc}_{np.dtype(dtype).name}.spqf"
        write_file(path, columns=c, codec="none", encoding=enc)
        with SpatialParquetReader(path) as r:
            g0, _, _ = r.read_columnar()
            g1, _, _ = r.read_columnar(device="jax")
            assert np.array_equal(_ibits(g0.x), _ibits(g1.x))
            assert np.array_equal(_ibits(g0.y), _ibits(g1.y))


def test_dataset_scanner_device(tmp_path):
    from repro.data.synthetic import DATASETS
    from repro.dataset import SpatialDatasetScanner, write_dataset

    cols = DATASETS["PT"](n_traj=120)
    root = tmp_path / "ds"
    write_dataset(root, columns=cols, n_shards=3, sort="hilbert", codec="none")
    sc = SpatialDatasetScanner(root, max_workers=3)
    g0, _, s0 = sc.scan()
    g1, _, s1 = sc.scan(device="jax")
    assert np.array_equal(_ibits(g0.x), _ibits(g1.x))
    assert np.array_equal(_ibits(g0.y), _ibits(g1.y))
    assert s0 == s1
    x0, y0, x1, y1 = sc.manifest.mbr
    bbox = (x0, y0, x0 + (x1 - x0) / 3, y0 + (y1 - y0) / 3)
    g2, _, _ = sc.scan(bbox=bbox, parallel=False)
    g3, _, _ = sc.scan(bbox=bbox, device="jax")
    if g2 is not None:
        assert np.array_equal(_ibits(g2.x), _ibits(g3.x))


# ------------------------------------------------- adversarial property tests
def _device_roundtrip(x):
    payload, _ = fp_delta_encode(x)
    plan = fp_delta_plan(payload, len(x), x.dtype)
    host = fp_delta_decode(payload, len(x), x.dtype)
    assert np.array_equal(_ibits(x), _ibits(host))
    stream = build_page_stream([plan])
    for use_pallas in (False, True):
        dev = decode_page_stream(stream, use_pallas=use_pallas, interpret=True)
        assert np.array_equal(_ibits(x), _ibits(dev))


def _adversarial(seed, dtype, max_size=400):
    """NaN payloads, signed zeros/infs, denormals, constant runs,
    alternating-sign coordinates — the worst floats we can think of."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, max_size + 1))
    info = np.finfo(dtype)
    bits = np.uint32 if np.dtype(dtype) == np.float32 else np.uint64
    w = np.dtype(dtype).itemsize * 8
    nan_payload = (rng.integers(0, 2**w, k, dtype=bits)
                   | bits((2 ** (w - np.finfo(dtype).nmant - 1) - 1)
                          << np.finfo(dtype).nmant)).view(dtype)
    denorm = (rng.integers(0, 2 ** info.nmant, k, dtype=bits)).view(dtype)
    alt = (np.cumsum(rng.normal(0, 1e-3, k)) *
           np.where(np.arange(k) % 2 == 0, 1.0, -1.0)).astype(dtype)
    pool = np.stack([
        nan_payload, denorm, alt,
        np.full(k, rng.choice([0.0, -0.0, np.inf, -np.inf, 2.5])).astype(dtype),
    ])
    pick = rng.integers(0, pool.shape[0], k)
    return pool[pick, np.arange(k)]


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        seed=hyp_st.integers(0, 2**32 - 1),
        dtype=hyp_st.sampled_from([np.float32, np.float64]),
    )
    def test_property_adversarial_roundtrip(seed, dtype):
        _device_roundtrip(_adversarial(seed, dtype))

    @settings(max_examples=15, deadline=None)
    @given(
        vals=hyp_st.lists(
            hyp_st.floats(width=64, allow_nan=True, allow_infinity=True,
                          allow_subnormal=True),
            min_size=1, max_size=200,
        )
    )
    def test_property_hypothesis_floats(vals):
        _device_roundtrip(np.array(vals, np.float64))

else:  # deterministic fallback, PR 1 convention: run, don't skip

    @pytest.mark.parametrize("seed", _SEEDS)
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_property_adversarial_roundtrip(seed, dtype):
        _device_roundtrip(_adversarial(seed, dtype))
