"""Bit-stream pack/unpack invariants.

``hypothesis`` is optional: without it, the property tests run fixed
deterministic samples (seeded numpy rng) instead of being skipped.
"""

import numpy as np
import pytest

from repro.core.bitstream import (
    bytes_to_words,
    marker_candidates,
    pack_tokens,
    unpack_at,
    unpack_fixed,
    width_mask,
    words_to_bytes,
)

try:
    from hypothesis import given, settings, strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional wheel
    HAVE_HYPOTHESIS = False

_SEEDS = [0, 1, 7, 42, 1234]


def _random_tokens(seed, max_size=200):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(0, max_size + 1))
    vals = rng.integers(0, 2**63, k, dtype=np.uint64) * 2 + rng.integers(0, 2, k).astype(np.uint64)
    widths = rng.integers(1, 65, k)
    return [(int(v), int(w)) for v, w in zip(vals, widths)]


def _check_pack_then_sequential_read(tokens):
    vals = np.array([t[0] for t in tokens], np.uint64)
    widths = np.array([t[1] for t in tokens], np.int64)
    words, total = pack_tokens(vals, widths)
    assert total == int(widths.sum())
    off = 0
    for v, w in tokens:
        got = int(unpack_fixed(words, off, 1, w)[0])
        assert got == (v & int(width_mask(w))), (v, w)
        off += w


def _check_fixed_width_vector_roundtrip(width, vals):
    vals = np.array(vals, np.uint64) & width_mask(width)
    words, total = pack_tokens(vals, np.full(len(vals), width, np.int64))
    got = unpack_fixed(words, 0, len(vals), width)
    assert np.array_equal(got, vals)


def _check_bytes_serialization_roundtrip(tokens):
    vals = np.array([t[0] for t in tokens], np.uint64)
    widths = np.array([t[1] for t in tokens], np.int64)
    words, total = pack_tokens(vals, widths)
    buf = words_to_bytes(words, total)
    assert len(buf) == (total + 7) // 8
    words2 = bytes_to_words(buf)
    off = 0
    for v, w in tokens:
        assert int(unpack_fixed(words2, off, 1, w)[0]) == (v & int(width_mask(w)))
        off += w


if HAVE_HYPOTHESIS:
    @given(hyp_st.lists(hyp_st.tuples(hyp_st.integers(0, 2**64 - 1), hyp_st.integers(1, 64)),
                        min_size=0, max_size=200))
    @settings(max_examples=200, deadline=None)
    def test_pack_then_sequential_read(tokens):
        _check_pack_then_sequential_read(tokens)

    @given(hyp_st.integers(1, 64),
           hyp_st.lists(hyp_st.integers(0, 2**64 - 1), min_size=1, max_size=300))
    @settings(max_examples=100, deadline=None)
    def test_fixed_width_vector_roundtrip(width, vals):
        _check_fixed_width_vector_roundtrip(width, vals)

    @given(hyp_st.lists(hyp_st.tuples(hyp_st.integers(0, 2**64 - 1), hyp_st.integers(1, 64)),
                        min_size=1, max_size=100))
    @settings(max_examples=100, deadline=None)
    def test_bytes_serialization_roundtrip(tokens):
        _check_bytes_serialization_roundtrip(tokens)
else:
    @pytest.mark.parametrize("seed", _SEEDS)
    def test_pack_then_sequential_read(seed):
        _check_pack_then_sequential_read(_random_tokens(seed))

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_fixed_width_vector_roundtrip(seed):
        rng = np.random.default_rng(seed)
        for width in (1, 2, 7, 31, 32, 33, 63, 64):
            vals = rng.integers(0, 2**63, 300, dtype=np.uint64) * 2 + 1
            _check_fixed_width_vector_roundtrip(width, list(vals))

    @pytest.mark.parametrize("seed", _SEEDS)
    def test_bytes_serialization_roundtrip(seed):
        toks = _random_tokens(seed)
        if not toks:
            toks = [(5, 8)]
        _check_bytes_serialization_roundtrip(toks)


def test_mixed_stream_alignment():
    # header(8) + raw(64) + many 7-bit values (the fp-delta layout)
    vals = [5, 0xDEADBEEFCAFEF00D] + list(range(100))
    widths = [8, 64] + [7] * 100
    words, total = pack_tokens(np.array(vals, np.uint64), np.array(widths, np.int64))
    assert int(unpack_fixed(words, 0, 1, 8)[0]) == 5
    assert int(unpack_fixed(words, 8, 1, 64)[0]) == 0xDEADBEEFCAFEF00D
    got = unpack_fixed(words, 72, 100, 7)
    assert np.array_equal(got, np.arange(100, dtype=np.uint64))


def test_unpack_at_arbitrary_offsets(rng):
    vals = rng.integers(0, 2**64, 500, dtype=np.uint64)
    widths = rng.integers(1, 65, 500)
    words, total = pack_tokens(vals, widths)
    offs = np.cumsum(widths) - widths
    # gather every token individually at its exact (unsorted) offset
    perm = rng.permutation(500)
    for w in np.unique(widths):
        sel = perm[widths[perm] == w]
        got = unpack_at(words, offs[sel], int(w))
        assert np.array_equal(got, vals[sel] & width_mask(int(w)))


@pytest.mark.parametrize("n", [1, 2, 3, 7, 17, 33, 64])
def test_marker_candidates_exact(n):
    # build a stream with known runs of ones at known bit positions
    rng = np.random.default_rng(n)
    total_bits = 4096
    bits = np.zeros(total_bits, dtype=np.uint8)
    planted = sorted(rng.choice(total_bits - 2 * n, 8, replace=False).tolist())
    for p in planted:
        bits[p : p + n] = 1
    words = np.zeros(total_bits // 64 + 1, dtype=np.uint64)
    packed = np.packbits(bits, bitorder="little")
    words[: len(packed) // 8] = packed.view("<u8")
    got = set(marker_candidates(words, n).tolist())
    # brute force: every position where n consecutive ones start
    want = {
        i for i in range(total_bits - n + 1) if bits[i : i + n].all()
    }
    assert got == want
