"""FP-delta: lossless delta encoding for floating-point coordinates.

Paper-exact implementation of Spatial Parquet §3 (Algorithms 1, 2 and 3):

1. Reinterpret each IEEE-754 value as a two's-complement integer
   (``cast-long``); delta consecutive values with wrapping arithmetic.
2. Zigzag-encode: ``(delta >> W-1) ^ (delta << 1)`` (arithmetic shift).
3. Choose the storage-optimal delta width ``n*`` from the exact cost model
   ``S(n) = n * (|X|-1) + W * sum_{i>n} h[i]`` over the histogram ``h`` of
   significant-bit counts (Algorithm 3, suffix sums).
4. Emit: 8-bit header ``n*``, the first value raw (W bits), then per delta
   either its zigzag in ``n*`` bits, or the all-ones *reset marker* followed by
   the raw W-bit value when the zigzag does not fit (or collides with the
   marker).

``n* == 0`` signals raw mode (the paper's "skip the algorithm altogether" path
when the computed saving is nil): every value is stored raw at W bits.

The codec is width-parametric: ``W=64`` covers float64/int64 (the paper's
default), ``W=32`` covers float32/int32 (paper footnote 1; also the variant our
TPU Pallas kernels implement, and the one used for checkpoint compression).

Hot-path structure (this module is the decode-CPU bottleneck of the whole
read path, so every stage is one numpy pass):

* **Encode** computes the zigzag deltas and the significant-bit histogram
  exactly once and shares them between the ``n*`` optimizer and the token
  emitter (:func:`fp_delta_encode`); :func:`fp_delta_encode_pages`
  batch-encodes every page of a column from a single column-wide delta pass.
* **Decode** (:func:`fp_delta_decode`) has no per-segment Python loop; work
  never scales with the value count outside whole-array vector ops. The
  exact escape count is recovered from the payload length (W >= 32 > 7 bits
  of byte padding, so the division is exact), then marker positions are
  resolved one of two ways. Sparse streams (a handful of escapes) use a
  vectorized fixpoint: token offsets are guessed assuming no escapes,
  markers found, offsets re-derived from the escape cumsum, repeated until
  stable (typically <= 2 rounds; a stable assignment is necessarily the
  unique correct one — token 0's offset is known, and by induction every
  later offset is determined by the flags before it). Denser streams use
  marker candidates, the positions where ``n`` consecutive ones start. For
  ``n* >= 15`` every run of that many ones covers a whole ``0xFF`` byte, so
  one byte scan over all pages of a run finds the runs, and a page with one
  run per escape takes its markers in closed form (marker ``k`` in run
  ``k``); other pages hop from marker to marker over the candidates (for
  ``n* < 15`` a log-shift AND ladder over the words finds them,
  ``marker_candidates``), and Python runs once per escape. The candidate
  walk that visits every candidate stays as the reference and the fallback
  for malformed payloads. Either way, reconstruction is ONE
  segmented cumsum over all reset segments at once: cumsum the inline deltas
  with escapes zeroed, then add a per-segment correction (raw value minus
  the running sum at the escape) spread with ``np.repeat``.
* ``out=`` lets callers (the coalesced reader) decode straight into a slice
  of a preallocated coordinate array, eliminating list-append +
  ``np.concatenate`` from the read path.
* **Decode is split into plan + execute.** :func:`fp_delta_plan` performs
  the only inherently sequential part of Algorithm 2 — header parsing and
  escape resolution, i.e. locating every token once reset markers shift
  later offsets — and returns an :class:`FPDeltaPlan` holding the packed
  words plus the resolved ``(offsets, flags)``. :func:`fp_delta_execute`
  finishes on the host (gather, un-zigzag, segmented cumsum);
  ``repro.kernels.fp_delta`` consumes the very same plans to run that
  second half on the accelerator (Pallas page-stream decode), so the two
  back ends can never disagree about the format. :func:`fp_delta_decode`
  is plan + host execute and stays the oracle.
  :func:`fp_delta_execute_many` finishes many plans of one column at once
  (the reader's attribute pages), bit-identical to executing each.
"""

from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass

import numpy as np

from repro import obs

from .bitstream import (
    bytes_to_words,
    marker_candidates,
    pack_tokens,
    unpack_at,
    unpack_fixed,
    width_mask,
    words_to_bytes,
)

_SIGNED = {32: np.int32, 64: np.int64}
_UNSIGNED = {32: np.uint32, 64: np.uint64}

HEADER_BITS = 8

_FIXPOINT_MAX_ROUNDS = 10
# sparse/dense resolver switch: the fixpoint needs ~E+1 rounds, so beyond a
# handful of escapes the candidate resolvers are strictly better
_FIXPOINT_MAX_ESCAPES = 4


def _as_int_bits(x: np.ndarray) -> tuple[np.ndarray, int]:
    """View the input as signed two's-complement ints; return (ints, W)."""
    x = np.ascontiguousarray(x)
    if x.dtype in (np.float64, np.int64, np.uint64):
        return x.view(np.int64), 64
    if x.dtype in (np.float32, np.int32, np.uint32):
        return x.view(np.int32), 32
    raise TypeError(f"fp_delta supports 32/64-bit element types, got {x.dtype}")


def zigzag(delta: np.ndarray, width: int) -> np.ndarray:
    """Zigzag-encode signed deltas to unsigned (paper Alg. 1 line 9)."""
    s = _SIGNED[width]
    d = delta.astype(s, copy=False)
    return ((d >> s(width - 1)) ^ (d << s(1))).view(_UNSIGNED[width])


def unzigzag(z: np.ndarray, width: int) -> np.ndarray:
    """Inverse zigzag (paper Alg. 2 line 9): (z >>> 1) ^ -(z & 1)."""
    u = _UNSIGNED[width]
    z = z.astype(u, copy=False)
    neg = u(0) - (z & u(1))  # wraps to all-ones when LSB set
    return ((z >> u(1)) ^ neg).view(_SIGNED[width])


def significant_bits(z: np.ndarray, width: int) -> np.ndarray:
    """Number of significant bits of each unsigned value (0 for value 0).

    One pass via the float64 exponent field, with an exact fix-up for the
    one case float rounding can overshoot (values just below a power of
    two round up, inflating the exponent by one).
    """
    z64 = np.asarray(z).astype(np.uint64, copy=False)
    f = z64.astype(np.float64)
    e = ((f.view(np.uint64) >> np.uint64(52)) & np.uint64(0x7FF)).astype(np.int64)
    e -= 1022  # unbias: e = #bits of the rounded float (f in [2^(e-1), 2^e))
    es = np.clip(e - 1, 0, 63).astype(np.uint64)
    over = (z64 >> es) == 0  # z < 2^(e-1): rounding overshot, e is one high
    sig = np.minimum(np.where(over, e - 1, e), 64)
    return np.where(z64 == 0, 0, sig)


def _zigzag_deltas(x: np.ndarray) -> tuple[np.ndarray, int]:
    xi, width = _as_int_bits(x)
    delta = xi[1:] - xi[:-1]  # wrapping two's-complement subtraction
    return zigzag(delta, width), width


def delta_bit_histogram(x: np.ndarray) -> np.ndarray:
    """Histogram h[n] = #deltas needing exactly n significant bits (Fig 8)."""
    xi, width = _as_int_bits(x)
    if len(xi) < 2:
        return np.zeros(width + 1, dtype=np.int64)
    z, width = _zigzag_deltas(x)
    nbits = significant_bits(z, width)
    return np.bincount(nbits, minlength=width + 1).astype(np.int64)


def best_bits_from_histogram(h: np.ndarray, n_deltas: int, width: int) -> int:
    """Paper Algorithm 3 from a precomputed histogram: exact argmin_n S(n)."""
    if n_deltas <= 0:
        return 0
    suffix = np.cumsum(h[::-1])[::-1]  # suffix[n] = #deltas needing >= n bits
    s_all = np.arange(width + 1, dtype=np.int64) * n_deltas
    s_all[:-1] += width * suffix[1:]
    s_all[0] = width * n_deltas  # n=0 == raw mode: every value raw
    return int(np.argmin(s_all[:width]))  # n in [0, width)


def compute_best_delta_bits(x: np.ndarray) -> int:
    """Paper Algorithm 3: exact argmin_n S(n) via suffix-summed histogram."""
    xi, width = _as_int_bits(x)
    n_deltas = len(xi) - 1
    if n_deltas <= 0:
        return 0
    return best_bits_from_histogram(delta_bit_histogram(x), n_deltas, width)


@dataclass(frozen=True)
class FPDeltaStats:
    """Encoder-side accounting (feeds benchmarks and page metadata)."""

    n_values: int
    n_bits: int          # chosen n*
    n_resets: int        # deltas escaped via reset marker
    payload_bits: int    # total encoded bits incl. header


def _encode_tokens(
    raw_bits: np.ndarray, z: np.ndarray, width: int, n: int
) -> tuple[bytes, FPDeltaStats]:
    """Emit the token stream for one page from precomputed zigzag deltas.

    ``raw_bits``: every value's W-bit pattern as uint64; ``z``: the page's
    zigzag deltas as uint64 (``len(z) == len(raw_bits) - 1``).
    """
    n_values = len(raw_bits)
    if n_values == 0:
        return b"", FPDeltaStats(0, 0, 0, 0)

    if n == 0 or n_values == 1:
        # Raw mode: header n=0, then every value raw at W bits.
        vals = np.concatenate([[np.uint64(0)], raw_bits])
        widths = np.concatenate([[HEADER_BITS], np.full(n_values, width, np.int64)])
        words, total = pack_tokens(vals, widths)
        return words_to_bytes(words, total), FPDeltaStats(n_values, 0, 0, total)

    marker = np.uint64((1 << n) - 1)
    overflow = z >= marker  # any significant bit above n-1, or == marker

    n_deltas = n_values - 1
    n_over = int(overflow.sum())
    n_tokens = 2 + n_deltas + n_over  # header, first value, deltas (+escapes)
    vals = np.empty(n_tokens, dtype=np.uint64)
    widths = np.empty(n_tokens, dtype=np.int64)
    vals[0], widths[0] = np.uint64(n), HEADER_BITS
    vals[1], widths[1] = raw_bits[0], width
    # Position of each delta's first token: one extra slot per prior escape.
    pos = 2 + np.arange(n_deltas, dtype=np.int64) + np.cumsum(overflow) - overflow
    vals[pos] = np.where(overflow, marker, z)
    widths[pos] = n
    if n_over:
        esc = pos[overflow] + 1
        vals[esc] = raw_bits[1:][overflow]
        widths[esc] = width
    words, total = pack_tokens(vals, widths)
    return words_to_bytes(words, total), FPDeltaStats(n_values, n, n_over, total)


def fp_delta_encode(x: np.ndarray, n_bits: int | None = None) -> tuple[bytes, FPDeltaStats]:
    """Encode a 1-D array of 32/64-bit values. Returns (payload, stats).

    One-pass: the zigzag deltas are computed once and shared between the
    ``n*`` optimizer (Algorithm 3) and the token emitter. The default path is
    the single-page case of :func:`fp_delta_encode_pages` so the two can
    never diverge.
    """
    xi, width = _as_int_bits(x)
    if n_bits is None:
        return fp_delta_encode_pages(xi, [(0, len(xi))])[0]

    n = int(n_bits)
    if not (0 <= n < width):
        raise ValueError(f"n_bits must be in [0, {width}), got {n}")
    n_values = len(xi)
    if n_values == 0:
        return b"", FPDeltaStats(0, 0, 0, 0)
    raw_bits = xi.view(_UNSIGNED[width]).astype(np.uint64)
    if n_values >= 2:
        z = zigzag(xi[1:] - xi[:-1], width).astype(np.uint64)
    else:
        z = np.zeros(0, dtype=np.uint64)
    return _encode_tokens(raw_bits, z, width, n)


def fp_delta_encode_pages(
    x: np.ndarray, bounds: list[tuple[int, int]]
) -> list[tuple[bytes, FPDeltaStats]]:
    """Batch-encode value ranges ``[v0, v1)`` of one column as independent pages.

    The column-wide zigzag deltas and significant-bit counts are computed in a
    single pass; each page then only pays for its own histogram (``bincount``
    over a slice) and token packing. Page ``[v0, v1)`` uses column deltas
    ``d[v0 : v1-1]`` — the cross-page delta at ``v1-1`` is never encoded, so
    the output is byte-identical to encoding each slice separately.
    """
    xi, width = _as_int_bits(x)
    u = _UNSIGNED[width]
    raw_bits = xi.view(u).astype(np.uint64)
    if len(xi) >= 2:
        z = zigzag(xi[1:] - xi[:-1], width).astype(np.uint64)
        nbits = significant_bits(z, width)
    else:
        z = np.zeros(0, dtype=np.uint64)
        nbits = np.zeros(0, dtype=np.int64)

    out = []
    for v0, v1 in bounds:
        cnt = v1 - v0
        if cnt <= 0:
            out.append((b"", FPDeltaStats(0, 0, 0, 0)))
            continue
        zp = z[v0 : v1 - 1]
        h = np.bincount(nbits[v0 : v1 - 1], minlength=width + 1).astype(np.int64)
        n = best_bits_from_histogram(h, cnt - 1, width)
        out.append(_encode_tokens(raw_bits[v0:v1], zp, width, n))
    return out


def _to_signed_scalar(base: np.uint64, width: int):
    return np.uint64(base).astype(_UNSIGNED[width]).view(_SIGNED[width])


def _resolve_escapes_fixpoint(
    words: np.ndarray, start_bit: int, n_deltas: int, n: int, width: int, n_escapes: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """Vectorized fixpoint: find each delta token's bit offset and marker flag.

    Token ``j`` starts at ``start_bit + n*j + width*E_j`` where ``E_j`` is the
    number of escapes among deltas ``< j``. Guess ``E = 0``, unpack, flag
    markers, recompute ``E`` as the (clipped) exclusive cumsum, repeat until
    stable. A stable assignment is the unique correct one (token 0's offset
    is known; each later offset is determined by the flags before it). Each
    round locks in at least one more escape, so sparse streams converge in
    about ``n_escapes + 1`` rounds — typically <= 2. Returns
    ``(offsets, flags)`` or None when not converged (denser streams use
    the marker candidates instead).
    """
    marker = np.uint64((1 << n) - 1)
    idx = np.arange(n_deltas, dtype=np.int64) * np.int64(n) + np.int64(start_bit)
    esc_before = np.zeros(n_deltas, dtype=np.int64)
    w64 = np.int64(width)
    for _ in range(_FIXPOINT_MAX_ROUNDS):
        offs = idx + w64 * esc_before
        tok = unpack_at(words, offs, n)
        flags = tok == marker
        # clip keeps every offset inside the payload even mid-fixpoint
        new_esc = np.minimum(np.cumsum(flags) - flags, n_escapes)
        if np.array_equal(new_esc, esc_before):
            return offs, flags
        esc_before = new_esc
    return None


def _resolve_escapes_scan(
    words: np.ndarray, start_bit: int, n_deltas: int, n: int, width: int, n_escapes: int
) -> tuple[np.ndarray, np.ndarray]:
    """The candidate walk: escape resolution for any marker density, exact.
    It is the reference of the faster candidate resolvers, and their
    fallback where a malformed payload leaves them short of markers.

    A reset marker is ``n`` consecutive set bits at a token-aligned offset.
    :func:`marker_candidates` finds every bit position where ``n`` ones start
    (one vectorized log-shift ladder over the packed words); an inline token
    can never equal the marker, so a *token-aligned* candidate inside the
    token region is always a real escape. The walk below consumes candidates
    left to right — skipping unaligned ones (run spill from neighbouring
    token/raw bits) — and jumps ``n + W`` bits past each confirmed marker.
    Work is proportional to escapes found plus stray candidates, never to
    the value count.
    """
    cands = marker_candidates(words, n)
    esc_tok = np.empty(n_escapes, dtype=np.int64)
    found = 0
    pos = start_bit  # bit offset of the current segment's first token
    j0 = 0           # token index of the current segment's first token
    for c in cands.tolist():
        if found == n_escapes:
            break
        if c < pos:
            continue
        d, r = divmod(c - pos, n)
        if r:
            continue  # candidate not token-aligned: spill from data bits
        j = j0 + d
        if j >= n_deltas:
            break
        esc_tok[found] = j
        found += 1
        pos = c + n + width  # skip the marker and its raw value
        j0 = j + 1
    flags = np.zeros(n_deltas, dtype=bool)
    flags[esc_tok[:found]] = True
    esc_before = np.cumsum(flags) - flags
    offs = (
        np.int64(start_bit)
        + np.int64(n) * np.arange(n_deltas, dtype=np.int64)
        + np.int64(width) * esc_before
    )
    return offs, flags


def _dense(start: int, width: int, n: np.ndarray, n_deltas: np.ndarray,
           n_escapes: np.ndarray, esc_tok: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """Token bit offsets and marker flags of pages laid end to end.

    ``n``, ``n_deltas`` and ``n_escapes`` hold each page's token width,
    token count and escape count; ``esc_tok`` the escaped tokens' indices
    within their pages, page after page. One cumsum: a token sits ``n`` bits
    past the one before, ``n + W`` past a marker, and a page's first token
    at ``start``.
    """
    first = np.cumsum(n_deltas) - n_deltas
    total = int(first[-1] + n_deltas[-1])
    marks = np.repeat(first, n_escapes) + esc_tok
    offs = np.repeat(n, n_deltas)  # each token's step from the one before
    # a page's first token steps back to ``start`` from the page before's
    # last one, at start + n(D-1) + W*E; where that one is a marker, its
    # ``+ W`` below lands on this token and the step still comes out right
    offs[first] = start
    offs[first[1:]] -= (start + n * (n_deltas - 1) + width * n_escapes)[:-1]
    after = marks + 1
    offs[after[after < total]] += width
    np.cumsum(offs, out=offs)
    flags = np.zeros(total, dtype=bool)
    flags[marks] = True
    return offs, flags


def _hop_markers(cands: np.ndarray, start: int, n: int, width: int,
                 n_escapes: int) -> np.ndarray:
    """The markers :func:`_resolve_escapes_scan` takes from ``cands``
    (sorted bit positions), without visiting the stray candidates.

    After a marker at ``c`` the walk takes the first candidate at or past
    ``c + n + W`` in that position's residue mod ``n``: so each marker
    depends on the one before alone. One sort by (residue, position) and
    one ``searchsorted`` give every candidate's successor, and Python runs
    once per escape.
    """
    m = len(cands)
    if not m:
        return cands
    span = max(int(cands[-1]) + n + width, start) + 1  # past every target
    keys = np.sort(cands % n * span + cands)
    tgt = keys % span + (n + width)
    nxt = np.searchsorted(keys, tgt % n * span + tgt).tolist()
    keys = keys.tolist()
    hops = []
    pos = start
    q = bisect_left(keys, pos % n * span + pos)
    while q < m and len(hops) < n_escapes:
        c = keys[q] - pos % n * span
        if c >= span:  # no candidate left in this residue
            break
        hops.append(c)
        pos = c + n + width
        q = nxt[q]
    return np.array(hops, dtype=np.int64)


def _hop_tokens(cands, start, n_deltas, n, width, n_escapes):
    """The escaped tokens' indices that :func:`_resolve_escapes_scan` finds,
    by candidate hops; None where the hops find fewer markers than the
    payload length promises (a malformed payload: the walk itself runs)."""
    pos = _hop_markers(cands, start, n, width, n_escapes)
    esc_tok = (pos - start - width * np.arange(len(pos))) // n
    if len(esc_tok) == n_escapes and esc_tok[-1] < n_deltas:
        return esc_tok
    return None


def _resolve_hops(cands, words, start, n_deltas, n, width, n_escapes,
                  tally) -> tuple[np.ndarray, np.ndarray]:
    """:func:`_resolve_escapes_scan`'s offsets and flags by candidate hops
    (:func:`_hop_tokens`), else by the walk."""
    esc_tok = _hop_tokens(cands, start, n_deltas, n, width, n_escapes)
    if esc_tok is not None:
        tally[1] += 1
        return _dense(start, width, np.array([n]), np.array([n_deltas]),
                      np.array([n_escapes]), esc_tok)
    tally[2] += 1
    return _resolve_escapes_scan(words, start, n_deltas, n, width, n_escapes)


def _fill_dense(rows: list, start: int, width: int, n_p, d_p, e_p,
                esc_tok: np.ndarray) -> None:
    """Fill the offsets and flags of the plans of ``rows`` (each row's last
    item) in one :func:`_dense` pass: pages of token widths ``n_p``, token
    counts ``d_p``, escape counts ``e_p``, escaped tokens ``esc_tok``."""
    offs, flags = _dense(start, width, n_p, d_p, e_p, esc_tok)
    ends = np.cumsum(d_p).tolist()
    for row, lo, hi in zip(rows, [0] + ends[:-1], ends):
        row[-1][4:6] = offs[lo:hi], flags[lo:hi]


# _LOW_ONES[b] / _HIGH_ONES[b]: the set bits at the bottom / top of byte b,
# which a run of ones from the byte below / above continues into
_LOW_ONES = np.array([((~b) & (b + 1)).bit_length() - 1 for b in range(256)],
                     dtype=np.int64)
_HIGH_ONES = np.array([8 - (255 - b).bit_length() for b in range(256)],
                      dtype=np.int64)
# a run of n >= 15 ones covers at least (n - 7) // 8 >= 1 whole 0xFF bytes
_BYTE_RUN_MIN_BITS = 15


def _resolve_closed(buf: np.ndarray, wide: list, start: int, width: int,
                    tally: list) -> None:
    """Resolve the escapes of many ``n* >= 15`` pages with one byte scan.

    ``buf`` holds every page's bytes; ``wide`` holds ``[byte offset in buf,
    byte length of the page's words (spill word included), n, n_deltas,
    n_escapes, words, plan]`` per page, and each
    plan's offsets and flags are filled in. The scan finds each maximal run
    of ones over a whole ``0xFF`` byte of a page's token region and extends
    it bitwise into the bytes beside it: with ``n >= 15`` these are all the
    runs a marker can start in.

    A page with exactly ``n_escapes`` such runs, none long enough for two
    markers (``2n + W`` bits), has marker ``k`` in run ``k``, at its first
    position ``≡ start + W*k (mod n)``: the walk reaches run ``k`` at that
    residue, past every candidate of run ``k - 1``, and takes the first
    aligned one. Each page's assignment is checked (inside its run, at
    least ``n + W`` bits past the marker before, a token index below
    ``n_deltas``); a page that fails takes the candidate hops over its runs.
    """
    hb = start // 8  # header and first value: whole bytes for W = 32, 64
    info = np.array([w[:5] for w in wide], dtype=np.int64)
    base, size, n_p, d_p, e_p = info.T
    pos = np.flatnonzero(buf == 0xFF)
    pg = np.searchsorted(base, pos, side="right") - 1
    pos -= base[pg]  # page-local byte positions
    inside = (pg >= 0) & (pos >= hb) & (pos < size[pg])
    if not inside.all():
        pos, pg = pos[inside], pg[inside]
    s_at = e_at = pos  # no 0xFF byte: no runs
    if len(pos):  # runs of consecutive 0xFF bytes, none across two pages
        cut = np.flatnonzero((pos[1:] != pos[:-1] + 1)
                             | (pg[1:] != pg[:-1])) + 1
        s_at = np.concatenate(([0], cut))
        e_at = np.append(cut - 1, len(pos) - 1)
    s, e, pg = pos[s_at], pos[e_at] + 1, pg[s_at]
    a = 8 * s - np.where(s > hb, _HIGH_ONES[buf[base[pg] + s - 1]], 0)
    b = 8 * e + _LOW_ONES[buf[base[pg] + e]]
    nn = n_p[pg]
    keep = b - a >= nn
    a, b, pg, nn = a[keep], b[keep], pg[keep], nn[keep]

    n_pages = len(wide)
    cnt = np.bincount(pg, minlength=n_pages)
    run0 = np.cumsum(cnt) - cnt
    lag = start + width * (np.arange(len(a)) - run0[pg])
    m = a + (lag - a) % nn
    j = (m - lag) // nn
    ok = (m + nn <= b) & (b - a < 2 * nn + width) & (j < d_p[pg])
    ok[1:] &= (m[1:] - m[:-1] >= nn[1:] + width) | (pg[1:] != pg[:-1])
    good = (cnt == e_p) & (np.bincount(pg[~ok], minlength=n_pages) == 0)

    gi = np.flatnonzero(good)
    if len(gi):  # the good pages' offsets and flags in one pass
        _fill_dense([wide[q] for q in gi.tolist()], start, width, n_p[gi],
                    d_p[gi], e_p[gi], j[good[pg]])
        tally[0] += len(gi)
    for q in np.flatnonzero(~good).tolist():
        _, _, n, n_deltas, n_escapes, words, plan = wide[q]
        lo, hi = run0[q], run0[q] + cnt[q]
        ra, rb = a[lo:hi], b[lo:hi]
        lens = rb - ra - (n - 1)  # candidates of a run: starts a .. b - n
        ends = np.cumsum(lens)
        cands = (np.repeat(ra - (ends - lens), lens)
                 + np.arange(ends[-1] if len(ends) else 0, dtype=np.int64))
        plan[4:6] = _resolve_hops(cands, words, start, n_deltas, n, width,
                                  n_escapes, tally)


def _resolve_narrow(buf_words: np.ndarray, narrow: list, start: int,
                    width: int, tally: list) -> None:
    """Resolve the escapes of many ``n* < 15`` pages by candidate hops, with
    one :func:`marker_candidates` pass over the words from the first such
    page to the last.

    ``narrow`` holds ``[word offset in buf_words, word count (spill word
    included), n, n_deltas, n_escapes, words, plan]`` per page, and each
    plan's offsets and flags are filled in. A page ends in its zero spill
    word, so no run of ones crosses a page. The pass looks for runs of the
    smallest ``n``; a page of a larger ``n`` keeps the candidates with that
    many ones, those at least ``n - n_min`` bits before the end of their
    run. One ``searchsorted`` splits the candidates by page, and the hopped
    pages' offsets and flags come from one :func:`_dense` pass.
    """
    info = np.array([w[:3] for w in narrow], dtype=np.int64)
    base, size, n_p = 64 * info[:, 0], 64 * info[:, 1], info[:, 2]
    lo = int(info[0, 0])
    n_min = int(n_p.min())
    cands = marker_candidates(buf_words[lo : lo + int(info[-1, :2].sum())],
                              n_min) + 64 * lo
    pg = np.searchsorted(base, cands, side="right") - 1
    keep = cands < base[pg] + size[pg]  # not in a page between them
    if len(cands) and (n_p != n_min).any():
        brk = np.flatnonzero(np.diff(cands) != 1) + 1  # where runs start
        run = np.zeros(len(cands), dtype=np.int64)
        run[brk] = 1
        run_end = cands[np.append(brk - 1, len(cands) - 1)][np.cumsum(run)]
        keep &= run_end - cands >= n_p[pg] - n_min
    cands, pg = cands[keep], pg[keep]
    cuts = np.searchsorted(pg, np.arange(len(narrow) + 1)).tolist()
    good, toks = [], []
    for q, (_, _, n, n_deltas, n_escapes, words, plan) in enumerate(narrow):
        esc_tok = _hop_tokens(cands[cuts[q] : cuts[q + 1]] - base[q], start,
                              n_deltas, n, width, n_escapes)
        if esc_tok is None:
            tally[2] += 1
            plan[4:6] = _resolve_escapes_scan(words, start, n_deltas, n,
                                              width, n_escapes)
            continue
        good.append(narrow[q])
        toks.append(esc_tok)
    if good:
        info = np.array([row[2:5] for row in good], dtype=np.int64)
        _fill_dense(good, start, width, *info.T, np.concatenate(toks))
        tally[1] += len(good)


@dataclass(frozen=True)
class FPDeltaPlan:
    """Host-resolved decode plan for one page (the device-decode contract).

    The only inherently sequential part of Algorithm 2 — locating every token
    once reset markers shift later offsets — is resolved here on the host.
    What remains (fixed-width gather, escape injection, segmented cumsum,
    un-zigzag, float bitcast) is embarrassingly parallel; it is executed
    either by :func:`fp_delta_execute` (host numpy) or by the Pallas
    page-stream kernel in :mod:`repro.kernels.fp_delta`, which batches many
    plans into one launch.

    ``offsets[j]``/``flags[j]`` describe delta token ``j`` (``n_values - 1``
    entries): its absolute bit offset in ``words`` and whether it is the
    reset marker (the escaped raw W-bit value then sits at ``offsets[j] +
    n``). Raw mode (``n == 0``) has no delta tokens: every value is stored
    raw at ``width`` bits starting from bit ``HEADER_BITS``.
    """

    dtype: np.dtype
    width: int            # 32 or 64
    n: int                # token width n* (0 => raw mode)
    n_values: int
    first: int            # raw W-bit pattern of value 0 (0 when empty/raw)
    words: np.ndarray     # uint64 packed stream incl. trailing spill word
    offsets: np.ndarray   # (n_deltas,) int64 token bit offsets
    flags: np.ndarray     # (n_deltas,) bool: True where token is a marker
    n_escapes: int        # escape count recovered from the payload length


def _check_out(out: np.ndarray | None, n_values: int, dtype: np.dtype) -> None:
    if out is None:
        return
    if out.dtype != dtype or out.ndim != 1 or len(out) != n_values:
        raise ValueError("out must be a 1-D array of n_values elements of dtype")
    if not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous")


_EMPTY_OFFS = np.zeros(0, dtype=np.int64)
_EMPTY_FLAGS = np.zeros(0, dtype=bool)


def fp_delta_plan(payload, n_values: int, dtype) -> FPDeltaPlan:
    """Parse a payload's header and resolve every escape (Algorithm 2 front
    half). ``payload`` may be any bytes-like buffer (``bytes``,
    ``memoryview``)."""
    return fp_delta_plan_many([payload], [n_values], dtype)[0]


def fp_delta_plan_many(payloads, counts, dtype) -> list[FPDeltaPlan]:
    """:func:`fp_delta_plan` of each of many pages of one dtype, in order.

    The pages' words are views into one buffer. Each page's path follows
    from its header and length: no escapes; the fixpoint for a handful;
    otherwise the marker candidates, and there every ``n* >= 15`` page is
    resolved with the others in one byte scan (:func:`_resolve_closed`),
    while ``n* < 15`` pages hop over their :func:`marker_candidates`. The
    plans equal resolving each page alone with the candidate walk. Counters
    ``fp_delta.escape_pages.closed``, ``.hop`` and ``.walk`` count the pages
    each candidate path resolved.
    """
    dtype = np.dtype(dtype)
    width = dtype.itemsize * 8
    if width not in (32, 64):
        raise TypeError(f"unsupported dtype {dtype}")
    start = HEADER_BITS + width
    first_mask = (1 << width) - 1
    # the words of every page, laid out as bytes_to_words lays out one
    sizes = [len(p) if c else 0 for p, c in zip(payloads, counts)]
    n_words = [(size + 7) // 8 + 1 for size in sizes]
    buf_words = np.zeros(sum(n_words), dtype=np.uint64)
    buf = buf_words.view(np.uint8)
    plans = []  # FPDeltaPlan fields per page, from n on
    wide = []   # [byte offset, byte size, n, n_deltas, n_escapes, words, plan]
    narrow = []  # [word offset, word count, n, n_deltas, n_escapes, words, plan]
    tally = [0, 0, 0]  # pages resolved closed, by hops, by the walk
    w0 = 0
    for payload, n_values, size, nw in zip(payloads, counts, sizes, n_words):
        words = buf_words[w0 : w0 + nw]
        if size:
            buf[8 * w0 : 8 * w0 + size] = np.frombuffer(payload, np.uint8,
                                                        count=size)
        w0 += nw
        n_deltas = n_values - 1
        head = int.from_bytes(words[:2].tobytes(), "little") if size else 0
        n = head & 0xFF
        if n == 0 or n_deltas <= 0:  # empty, raw mode, or a lone value
            first = (head >> HEADER_BITS) & first_mask if n else 0
            plans.append([n, n_values, first, words, _EMPTY_OFFS,
                          _EMPTY_FLAGS, 0])
            continue
        # Exact escape count from the payload length: total bits are
        # HEADER + W + n*D + W*E plus < 8 bits of byte padding, and
        # W >= 32 > 7, so the integer division is exact for well-formed
        # payloads.
        n_escapes = (size * 8 - start - n * n_deltas) // width
        n_escapes = max(0, min(n_escapes, n_deltas))
        plan = [n, n_values, (head >> HEADER_BITS) & first_mask, words,
                None, None, n_escapes]
        plans.append(plan)
        if n_escapes == 0:
            plan[4] = start + np.arange(0, n * n_deltas, n, dtype=np.int64)
            plan[5] = np.zeros(n_deltas, dtype=bool)
            continue
        if n_escapes <= _FIXPOINT_MAX_ESCAPES:
            resolved = _resolve_escapes_fixpoint(
                words, start, n_deltas, n, width, n_escapes)
            if resolved is not None:
                plan[4:6] = resolved
                continue
        if n >= _BYTE_RUN_MIN_BITS:
            wide.append([8 * (w0 - nw), 8 * nw, n, n_deltas, n_escapes,
                         words, plan])
            continue
        narrow.append([w0 - nw, nw, n, n_deltas, n_escapes, words, plan])
    if wide:
        _resolve_closed(buf, wide, start, width, tally)
    if narrow:
        _resolve_narrow(buf_words, narrow, start, width, tally)
    if obs.enabled():
        for name, k in zip(("closed", "hop", "walk"), tally):
            if k:
                obs.count(f"fp_delta.escape_pages.{name}", k)
    return [FPDeltaPlan(dtype, width, *plan) for plan in plans]


def fp_delta_execute(plan: FPDeltaPlan, out: np.ndarray | None = None) -> np.ndarray:
    """Finish a resolved plan on the host (Algorithm 2 back half).

    This is the oracle the accelerator path must match bit-for-bit.
    """
    dtype, width = plan.dtype, plan.width
    s, u = _SIGNED[width], _UNSIGNED[width]
    _check_out(out, plan.n_values, dtype)
    if plan.n_values == 0:
        return out if out is not None else np.zeros(0, dtype=dtype)

    out_arr = out if out is not None else np.empty(plan.n_values, dtype=dtype)
    out_int = out_arr.view(s)
    words = plan.words

    if plan.n == 0:
        raws = unpack_fixed(words, HEADER_BITS, plan.n_values, width)
        out_int[:] = raws.astype(u).view(s)
        return out_arr

    out_int[0] = _to_signed_scalar(np.uint64(plan.first), width)
    n_deltas = plan.n_values - 1
    if n_deltas == 0:
        return out_arr

    n, offs, flags = plan.n, plan.offsets, plan.flags
    if plan.n_escapes == 0:
        z = unpack_at(words, offs, n)
        deltas = unzigzag(z.astype(u), width)
        out_int[1:] = out_int[0] + np.cumsum(deltas, dtype=s)
        return out_arr

    tok = unpack_at(words, offs, n)
    # One segmented cumsum over all reset segments at once: cumsum the inline
    # deltas (escapes contribute 0), then add a per-segment correction so each
    # escape restarts the running sum at its raw value.
    deltas = np.where(flags, s(0), unzigzag(tok.astype(u), width))
    running = out_int[0] + np.cumsum(deltas, dtype=s)
    esc_idx = np.flatnonzero(flags)
    if not len(esc_idx):  # malformed payload claimed escapes; decode best-effort
        out_int[1:] = running
        return out_arr
    raws = unpack_at(words, offs[esc_idx] + n, width)
    raw_signed = raws.astype(u).view(s)
    corr = raw_signed - running[esc_idx]
    reps = np.diff(np.append(esc_idx, n_deltas))
    out_int[1 : 1 + esc_idx[0]] = running[: esc_idx[0]]
    out_int[1 + esc_idx[0] :] = running[esc_idx[0] :] + np.repeat(corr, reps)
    return out_arr


_U1, _U63 = np.uint64(1), np.uint64(63)
# pages executed together, at most this many values at a time (a page more
# is its own group): a group's temporaries stay in the cache, and on the
# heap, where a fresh large array would cost a page fault per 4 KiB
_GROUP_VALUES = 1 << 15


def fp_delta_execute_many(plans, out: np.ndarray) -> np.ndarray:
    """:func:`fp_delta_execute` of many plans of one dtype into ``out``, the
    pages laid end to end (``out`` holds the sum of their ``n_values``).

    Bit-identical to executing each plan into its slice, in a fixed number
    of whole-array passes over a group of consecutive FP-delta pages
    (:func:`_execute_group`). A raw-mode page, and a group of one page, is
    executed alone.
    """
    plans = list(plans)
    dtype = plans[0].dtype if plans else out.dtype
    if any(p.dtype != dtype for p in plans):
        raise ValueError("plans must share one dtype")
    _check_out(out, sum(p.n_values for p in plans), dtype)
    plans = [p for p in plans if p.n_values]
    o = q = 0
    while q < len(plans):
        g, size = q + 1, plans[q].n_values
        while (plans[q].n and g < len(plans) and plans[g].n
               and size + plans[g].n_values <= _GROUP_VALUES):
            size += plans[g].n_values
            g += 1
        if g - q == 1:
            fp_delta_execute(plans[q], out=out[o : o + size])
        else:
            for k, o_k in _execute_group(plans[q:g], out[o : o + size]):
                plan = plans[q + k]
                fp_delta_execute(plan, out=out[o + o_k :
                                               o + o_k + plan.n_values])
        o, q = o + size, g
    return out


def _execute_group(plans, out: np.ndarray) -> list:
    """Execute non-empty FP-delta plans (``n > 0``) laid end to end in
    ``out``; return ``(index, first value)`` of each plan left to execute
    alone: one whose offsets the counts cannot rebuild, which rides along
    as zero deltas (:func:`_group_escapes`).

    Every value gets a slot, a page's first value too. One segmented cumsum
    finishes, where page starts (restarting at the page's ``first``) and
    escapes (restarting at their raw value) are the resets: a reset's slot
    takes the value that lands the running sum on its target, from each
    segment's sum (``np.add.reduceat``). The resets come in slot order from
    the counts, with no sort, and give every slot's bit offset too: ``n``
    bits a slot within a page, plus ``W`` past each marker.
    """
    width = plans[0].width
    nv = np.array([p.n_values for p in plans], dtype=np.int64)
    nn = np.array([p.n for p in plans], dtype=np.int64)
    n_slots = int(nv.sum())
    slot0 = np.cumsum(nv) - nv  # each page's first value
    alone: list = []
    pg, tok = _group_escapes(plans, nv - 1, nn, alone)

    # slot s of page p reads at n*s + const, the const growing by W past
    # each marker; a page's first slot reads inside its first value
    n_words = np.array([len(p.words) for p in plans], dtype=np.int64)
    words = np.concatenate([p.words for p in plans])
    const = (64 * (np.cumsum(n_words) - n_words) + HEADER_BITS + width
             - nn * (slot0 + 1))
    firsts = np.array([p.first for p in plans], dtype=np.uint64).view(np.int64)
    if len(pg):
        # resets in slot order: page p's start after the escapes of pages
        # < p, each escape after its own page's start
        n_esc = np.bincount(pg, minlength=len(plans))
        esc0 = np.cumsum(n_esc) - n_esc  # each page's first escape
        at_start = np.arange(len(plans)) + esc0
        at_esc = np.arange(len(pg)) + pg + 1
        resets = np.empty(len(plans) + len(pg), dtype=np.int64)
        resets[at_start] = slot0
        resets[at_esc] = slot0[pg] + 1 + tok
        seg_start = resets.copy()
        seg_start[at_esc] += 1  # the marker itself reads before its raw value
        seg_const = np.empty(len(resets), dtype=np.int64)
        seg_const[at_start] = const
        seg_const[at_esc] = const[pg] + width * (at_esc - pg - esc0[pg])
        target = np.empty(len(resets), dtype=np.int64)
        target[at_start] = firsts
    else:
        resets = seg_start = slot0
        seg_const, target = const, firsts
    uniform = bool((nn == nn[0]).all())
    if uniform and nn[0]:  # (all 0 where every page rides along)
        offs = np.arange(0, int(nn[0]) * n_slots, int(nn[0]), dtype=np.int64)
    else:
        offs = np.repeat(nn, nv)
        offs *= np.arange(n_slots, dtype=np.int64)
    offs += np.repeat(seg_const, np.append(seg_start[1:], n_slots) - seg_start)
    if len(pg):
        target[at_esc] = unpack_at(words, offs[resets[at_esc]] + nn[pg],
                                   width).view(np.int64)

    # one gather of every slot's token, un-zigzagged in place
    wi = offs >> 6
    sh = np.bitwise_and(offs, 63, out=offs).view(np.uint64)
    z = np.take(words, wi)
    z >>= sh
    wi += 1
    hi = np.take(words, wi)
    hi <<= _U1
    hi <<= np.subtract(_U63, sh, out=sh)  # 64 - shift, without a shift by 64
    z |= hi
    masks = width_mask(nn)
    z &= masks[0] if uniform else np.repeat(masks, nv)
    odd = np.bitwise_and(z, _U1, out=hi)
    z >>= _U1
    z ^= np.negative(odd, out=odd)
    d = z.view(np.int64)

    d[resets] = 0
    seg = np.add.reduceat(d, resets)  # each segment's deltas
    jump = target.copy()
    jump[1:] -= target[:-1] + seg[:-1]
    d[resets] = jump
    np.cumsum(d, dtype=_SIGNED[width], out=out.view(_SIGNED[width]))
    return [(q, int(slot0[q])) for q in alone]


def _group_escapes(plans, nd: np.ndarray, nn: np.ndarray, alone: list):
    """Page and token index of every marker flag of ``plans`` (token counts
    ``nd``), page after page. A plan with more flags than its escape count
    (a malformed payload's fixpoint clipped its offsets there, so the
    counts cannot rebuild them) joins ``alone``, its ``nn`` set to 0."""
    esc_pages = [q for q, p in enumerate(plans) if p.n_escapes]
    if not esc_pages:
        return _EMPTY_OFFS, _EMPTY_OFFS
    ep = np.array(esc_pages, dtype=np.int64)
    flat = np.flatnonzero(np.concatenate([plans[q].flags for q in esc_pages]))
    head = np.cumsum(nd[ep]) - nd[ep]
    at = np.searchsorted(head, flat, side="right") - 1
    pg, tok = ep[at], flat - head[at]
    limit = np.array([plans[q].n_escapes for q in esc_pages])
    bad = ep[np.bincount(at, minlength=len(ep)) > limit]
    if len(bad):
        alone += bad.tolist()
        nn[bad] = 0
        keep = nn[pg] > 0
        pg, tok = pg[keep], tok[keep]
    return pg, tok


def fp_delta_decode(
    payload, n_values: int, dtype, out: np.ndarray | None = None
) -> np.ndarray:
    """Decode ``n_values`` elements of ``dtype`` (paper Algorithm 2).

    ``payload`` may be any bytes-like buffer (``bytes``, ``memoryview``).
    ``out``, if given, must be a contiguous 1-D array of exactly ``n_values``
    elements of ``dtype``; the decode writes into it and returns it, letting
    callers fill slices of a preallocated column without a concat pass.
    Wrong-dtype/wrong-length/non-contiguous buffers raise ``ValueError``
    before any byte of the payload is parsed.
    """
    dtype = np.dtype(dtype)
    if dtype.itemsize * 8 not in (32, 64):
        raise TypeError(f"unsupported dtype {dtype}")
    _check_out(out, n_values, dtype)
    return fp_delta_execute(fp_delta_plan(payload, n_values, dtype), out=out)


def encoded_size_bits(x: np.ndarray, n: int) -> int:
    """Exact S(n) for diagnostics (Equation 2 plus header/first-value cost)."""
    xi, width = _as_int_bits(x)
    if len(xi) < 2:
        return HEADER_BITS + width * len(xi)
    if n == 0:
        return HEADER_BITS + width * len(xi)
    h = delta_bit_histogram(x)
    suffix = np.cumsum(h[::-1])[::-1]
    over = int(suffix[n + 1]) if n + 1 <= width else 0
    return HEADER_BITS + width + n * (len(xi) - 1) + width * over
