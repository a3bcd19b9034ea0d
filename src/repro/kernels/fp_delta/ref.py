"""Pure-jnp oracle for the TPU miniblock FP-delta codec (v2: patched coding).

Semantics (the TPU adaptation of paper §3 — see DESIGN.md §5):

* The stream is split into *miniblocks* of ``MINIBLOCK`` (1024) float32
  values. Each miniblock is **self-contained**: a raw int32 *anchor* (its
  first value), a *width* ``w ∈ {0,1,2,4,8,16,32}``, its 1024 zigzag deltas
  (``delta[0] := 0``) packed at ``w`` bits into ``1024*w/32`` int32 words,
  plus up to ``MAX_EXC`` *exceptions* — (position u16, full zigzag u32)
  pairs for deltas that do not fit ``w`` bits (FastPFOR-style patching).
* ``w`` minimizes the exact per-block cost ``1024*w + 48*n_over(w)`` over
  the lane-aligned widths, subject to ``n_over(w) <= MAX_EXC``. v1 (no
  exceptions) paid a whole block of w=32 for a single outlier — a 214%
  size regression vs the paper-exact stream on multi-record pages;
  patching restores <~15% (measured in benchmarks/bench_kernels.py).
* Exception extraction/injection is scatter-free: a (MAX_EXC, 1024) one-hot
  contraction against iota (VPU-friendly; no dynamic memory ops), so the
  Pallas kernel lowers with data-independent control flow.
* Block anchoring costs ~48 bits / 1024 values and buys embarrassingly-
  parallel decode — there is no cross-block carry at all.

This file is the *oracle*: straightforward vectorized jnp, no Pallas. The
kernel must match it bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import math

MINIBLOCK = 1024
# Lane-aligned widths: ANY w packs g = 32/gcd(w,32) values into g*w/32 whole
# words with static shift patterns (v3 — the pow2-only lattice of v2
# bracketed the typical geo n* ~ 10 badly: w=8 overflowed MAX_EXC, w=16
# wasted 6 bits/value). Chosen set keeps the candidate count modest while
# never being more than ~15% above the paper-exact n*.
WIDTHS = (1, 2, 3, 4, 6, 8, 10, 12, 16, 20, 24, 32)
MAX_EXC = 64          # exception capacity per block (static shapes)
EXC_BITS = 16 + 32    # stored cost of one exception (position + raw zigzag)


def significant_bits_u32(z: jnp.ndarray) -> jnp.ndarray:
    """Bits needed for each uint32 value (0 for value 0); exact ladder."""
    z = z.astype(jnp.uint32)
    out = jnp.zeros(z.shape, jnp.int32)
    v = z
    for s in (16, 8, 4, 2, 1):
        big = v >= (jnp.uint32(1) << jnp.uint32(s))
        out = out + jnp.where(big, jnp.int32(s), jnp.int32(0))
        v = jnp.where(big, v >> jnp.uint32(s), v)
    return out + (z != jnp.uint32(0)).astype(jnp.int32)


def zigzag_i32(delta: jnp.ndarray) -> jnp.ndarray:
    d = delta.astype(jnp.int32)
    return ((d >> jnp.int32(31)) ^ (d << jnp.int32(1))).astype(jnp.uint32)


def unzigzag_u32(z: jnp.ndarray) -> jnp.ndarray:
    z = z.astype(jnp.uint32)
    neg = jnp.uint32(0) - (z & jnp.uint32(1))
    return ((z >> jnp.uint32(1)) ^ neg).astype(jnp.int32)


def _mask(w: int) -> jnp.uint32:
    return jnp.uint32(0xFFFFFFFF) if w >= 32 else jnp.uint32((1 << w) - 1)


def _group_geometry(w: int) -> tuple[int, int]:
    """(values per group g, words per group k) for lane-aligned packing."""
    g = 32 // math.gcd(w, 32)
    return g, g * w // 32


def pack_candidate(vals_u32: jnp.ndarray, w: int) -> jnp.ndarray:
    """Pack (..., M) uint32 values at static width w -> (..., M) words
    (first M*w/32 valid, rest zero).

    Group packing: g = 32/gcd(w,32) values occupy exactly k = g*w/32 words;
    every (value i -> word j) shift is a compile-time constant, so the whole
    thing is static shifts + masked sums (VPU-clean, any w)."""
    m = vals_u32.shape[-1]
    g, k = _group_geometry(w)
    v = (vals_u32 & _mask(w)).reshape(*vals_u32.shape[:-1], m // g, g)
    words = []
    for j in range(k):
        acc = jnp.zeros(v.shape[:-1], jnp.uint32)
        for i in range(g):
            s = i * w - j * 32
            if s <= -w or s >= 32:
                continue
            if s >= 0:
                acc = acc + ((v[..., i] << jnp.uint32(s)) & jnp.uint32(0xFFFFFFFF))
            else:
                acc = acc + (v[..., i] >> jnp.uint32(-s))
        words.append(acc)
    packed = jnp.stack(words, axis=-1).reshape(*vals_u32.shape[:-1], m * w // 32)
    padding = [(0, 0)] * (packed.ndim - 1) + [(0, m - packed.shape[-1])]
    return jnp.pad(packed, padding)


def unpack_candidate(words_u32: jnp.ndarray, w: int) -> jnp.ndarray:
    """Inverse of pack_candidate: (..., M) words -> (..., M) values."""
    m = words_u32.shape[-1]
    g, k = _group_geometry(w)
    wv = words_u32[..., : m * w // 32].reshape(*words_u32.shape[:-1], -1, k)
    vals = []
    for i in range(g):
        s = i * w
        j0, s0 = s // 32, s % 32
        v = wv[..., j0] >> jnp.uint32(s0)
        if s0 + w > 32:
            v = v | (wv[..., j0 + 1] << jnp.uint32(32 - s0))
        vals.append(v & _mask(w))
    out = jnp.stack(vals, axis=-1)
    return out.reshape(*words_u32.shape[:-1], m)


def choose_width(nbits: jnp.ndarray):
    """nbits: (..., M) per-value significant bits -> (width, n_over).

    Exact per-block argmin of M*w + EXC_BITS*n_over(w) over WIDTHS with
    feasibility n_over <= MAX_EXC (w=32 always feasible)."""
    m = nbits.shape[-1]
    best_w = jnp.full(nbits.shape[:-1], 32, jnp.int32)
    best_cost = jnp.full(nbits.shape[:-1], m * 32, jnp.int32)
    # ascending scan with strict improvement: ties keep the smaller width
    for w in (0,) + WIDTHS[:-1]:  # w=32 handled by init
        n_over = jnp.sum((nbits > w).astype(jnp.int32), axis=-1)
        cost = m * w + EXC_BITS * n_over
        ok = (n_over <= MAX_EXC) & (cost < best_cost)
        best_w = jnp.where(ok, jnp.int32(w), best_w)
        best_cost = jnp.where(ok, cost, best_cost)
    return best_w, best_cost


def extract_exceptions(zig: jnp.ndarray, width: jnp.ndarray):
    """Scatter-free exception compaction for one block.

    zig: (M,) uint32; width: scalar. Returns (exc_idx (MAX_EXC,) i32,
    exc_val (MAX_EXC,) u32, count scalar i32). Slot j holds the (j+1)-th
    overflowing position via a one-hot contraction with iota."""
    m = zig.shape[0]
    nbits = significant_bits_u32(zig)
    over = nbits > width                      # (M,) bool
    rank = jnp.cumsum(over.astype(jnp.int32))  # inclusive
    slots = jnp.arange(MAX_EXC, dtype=jnp.int32)
    onehot = (over[None, :] & (rank[None, :] == (slots[:, None] + 1)))
    iota = jnp.arange(m, dtype=jnp.int32)
    exc_idx = jnp.sum(onehot * iota[None, :], axis=1).astype(jnp.int32)
    exc_val = jnp.sum(onehot.astype(jnp.uint32) * zig[None, :], axis=1)
    count = jnp.minimum(jnp.sum(over.astype(jnp.int32)), MAX_EXC)
    return exc_idx, exc_val, count


def inject_exceptions(vals: jnp.ndarray, exc_idx, exc_val, count):
    """Inverse of extract_exceptions (scatter-free overwrite)."""
    m = vals.shape[0]
    slots = jnp.arange(MAX_EXC, dtype=jnp.int32)
    live = slots < count                       # (E,)
    iota = jnp.arange(m, dtype=jnp.int32)
    onehot = (iota[None, :] == exc_idx[:, None]) & live[:, None]  # (E, M)
    patch = jnp.sum(onehot.astype(jnp.uint32) * exc_val[:, None], axis=0)
    hit = jnp.any(onehot, axis=0)
    return jnp.where(hit, patch, vals)


def _select_by_width(width: jnp.ndarray, candidates: list[jnp.ndarray]) -> jnp.ndarray:
    """Sum-of-masked-candidates select (guaranteed vector lowering)."""
    out = jnp.zeros_like(candidates[0])
    for w, c in zip(WIDTHS, candidates):
        out = out + jnp.where((width == w)[..., None], c, 0)
    return out


def _encode_one_block(x: jnp.ndarray):
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    prev = jnp.concatenate([xi[:1], xi[:-1]])
    zig = zigzag_i32(xi - prev)  # delta[0] == 0
    nbits = significant_bits_u32(zig)
    width, _ = choose_width(nbits[None, :])
    width = width[0]
    exc_idx, exc_val, count = extract_exceptions(zig, width)
    packed = jnp.zeros(MINIBLOCK, jnp.uint32)
    for w in WIDTHS:
        packed = packed + jnp.where(width == w, pack_candidate(zig, w), jnp.uint32(0))
    return (packed.astype(jnp.int32), width, xi[0],
            exc_idx, exc_val.astype(jnp.int32), count)


def encode_blocks_ref(x: jnp.ndarray):
    """(n_blocks, MINIBLOCK) f32 -> (packed i32 (n,M), widths (n,), anchors
    (n,), exc_idx (n,E), exc_val (n,E), exc_count (n,))."""
    assert x.ndim == 2 and x.shape[1] == MINIBLOCK, x.shape
    return jax.vmap(_encode_one_block)(x)


def _decode_one_block(packed, width, anchor, exc_idx, exc_val, count):
    words = packed.astype(jnp.uint32)
    zig = jnp.zeros(MINIBLOCK, dtype=jnp.uint32)
    for w in WIDTHS:
        zig = zig + jnp.where(width == w, unpack_candidate(words, w), jnp.uint32(0))
    zig = inject_exceptions(zig, exc_idx, exc_val.astype(jnp.uint32), count)
    delta = unzigzag_u32(zig)
    xi = anchor + jnp.cumsum(delta, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(xi, jnp.float32)


def decode_blocks_ref(packed, widths, anchors, exc_idx, exc_val, exc_count):
    """Inverse of encode_blocks_ref -> (n_blocks, MINIBLOCK) float32."""
    return jax.vmap(_decode_one_block)(packed, widths, anchors,
                                       exc_idx, exc_val, exc_count)


# --------------------------------------------------------------- page stream
# The second codec in this package: on-device execution of the *paper-exact*
# FP-delta format (core/fp_delta.py), as opposed to the TPU-native miniblock
# format above. The host resolves escapes into an FPDeltaPlan; many pages are
# then concatenated into one value stream where every value is either an
# *anchor* (a raw W-bit pattern: a page's first value, an escaped reset
# value, or every value of a raw-mode page) or an inline n-bit zigzag delta.
# Decode = fixed-width gather + escape injection + segmented cumsum over the
# anchor-delimited segments + un-zigzag + float bitcast. All arithmetic is
# uint32 *limb pairs* (lo, hi) so W=64 streams decode without 64-bit lanes
# (TPUs have none; interpret mode needs no jax_enable_x64).

STREAM_BLOCK = 1024  # values per grid step of the stream kernel, one VPU tile


def gather_words(words_u32: jnp.ndarray, offs: jnp.ndarray):
    """The three-word window ``w0i .. w0i+2`` (``w0i = offs >> 5``) that
    holds each token, as uint32 arrays shaped like ``offs``.

    ``words_u32`` must carry >= 2 trailing spill words so the window is
    always in bounds. This is the stream's only data-dependent gather; the
    decode kernel receives its result, since Mosaic lowers no 1-D gather.
    """
    words = words_u32.astype(jnp.uint32)
    w0i = offs >> 5
    return tuple(jnp.take(words, w0i + k, mode="clip") for k in range(3))


def extract_tokens(w0, w1, w2, offs: jnp.ndarray, nbits: jnp.ndarray):
    """Token bits ``[offs, offs+nbits)`` from each token's three-word window.

    Returns ``(lo, hi)`` uint32 limbs; ``nbits`` must be in [1, 64].
    """
    s = (offs & 31).astype(jnp.uint32)
    inv = (jnp.uint32(32) - s) & jnp.uint32(31)  # shift-by-32 is UB: mask + select
    lo = (w0 >> s) | jnp.where(s == 0, jnp.uint32(0), w1 << inv)
    hi = (w1 >> s) | jnp.where(s == 0, jnp.uint32(0), w2 << inv)
    full = jnp.uint32(0xFFFFFFFF)
    nlo = jnp.clip(nbits, 1, 32).astype(jnp.uint32)
    mask_lo = full >> (jnp.uint32(32) - nlo)  # exponent in [0, 31]: safe
    nhi = jnp.clip(nbits - 32, 0, 32).astype(jnp.uint32)
    mask_hi = jnp.where(
        nhi == 0, jnp.uint32(0), full >> ((jnp.uint32(32) - nhi) & jnp.uint32(31))
    )
    return lo & mask_lo, hi & mask_hi


def gather_tokens(words_u32: jnp.ndarray, offs: jnp.ndarray, nbits: jnp.ndarray):
    """Gather token bits ``[offs, offs+nbits)`` from the LE word stream
    (:func:`gather_words` then :func:`extract_tokens`)."""
    return extract_tokens(*gather_words(words_u32, offs), offs, nbits)


def unzigzag_limbs(lo: jnp.ndarray, hi: jnp.ndarray):
    """64-bit unzigzag ``(z >>> 1) ^ -(z & 1)`` on uint32 limb pairs."""
    neg = jnp.uint32(0) - (lo & jnp.uint32(1))  # all-ones when LSB set
    zlo = (lo >> jnp.uint32(1)) | (hi << jnp.uint32(31))
    zhi = hi >> jnp.uint32(1)
    return zlo ^ neg, zhi ^ neg


def add_limbs(alo, ahi, blo, bhi):
    """Wrapping 64-bit add with carry propagation between uint32 limbs."""
    slo = alo + blo
    carry = (slo < blo).astype(jnp.uint32)
    return slo, ahi + bhi + carry


def seg_combine(a, b):
    """Associative combine of the segmented cumsum; ``b`` is the *later*
    operand: an anchor in ``b`` blocks ``a``'s contribution entirely.
    Elements are ``(lo, hi, is_anchor)``; identity is ``(0, 0, False)``."""
    alo, ahi, af = a
    blo, bhi, bf = b
    slo, shi = add_limbs(alo, ahi, blo, bhi)
    return jnp.where(bf, blo, slo), jnp.where(bf, bhi, shi), af | bf


def stream_values(lo: jnp.ndarray, hi: jnp.ndarray, anchor: jnp.ndarray):
    """Escape injection + un-zigzag: anchors keep their raw gathered bits,
    inline tokens become signed deltas (wrapping uint32 limbs)."""
    dlo, dhi = unzigzag_limbs(lo, hi)
    return jnp.where(anchor, lo, dlo), jnp.where(anchor, hi, dhi)


def segmented_scan(vlo, vhi, flag):
    """Inclusive Hillis–Steele segmented scan over the last axis (log-step
    shifted combines; identity-padded on the left)."""
    n = vlo.shape[-1]
    f = flag
    shift = 1
    while shift < n:
        z32 = jnp.zeros(vlo.shape[:-1] + (shift,), jnp.uint32)
        zb = jnp.zeros(vlo.shape[:-1] + (shift,), jnp.bool_)
        prev = (
            jnp.concatenate([z32, vlo[..., :-shift]], axis=-1),
            jnp.concatenate([z32, vhi[..., :-shift]], axis=-1),
            jnp.concatenate([zb, f[..., :-shift]], axis=-1),
        )
        vlo, vhi, f = seg_combine(prev, (vlo, vhi, f))
        shift *= 2
    return vlo, vhi, f


def decode_stream_limbs_ref(words_u32, tok_off, nbits, anchor):
    """Flat-scan oracle returning the decoded patterns as uint32 limb pairs
    (the fused refine chain's input form; ``hi`` is zero for 32-bit)."""
    offs = tok_off.reshape(-1)
    nb = nbits.reshape(-1)
    anc = anchor.reshape(-1) != 0
    lo, hi = gather_tokens(words_u32, offs, nb)
    vlo, vhi = stream_values(lo, hi, anc)
    flo, fhi, _ = segmented_scan(vlo, vhi, anc)
    return flo, fhi


def decode_stream_ref(words_u32, tok_off, nbits, anchor, *, width: int):
    """Pure-jnp oracle for the page-stream decode: one flat global segmented
    scan (structurally unlike the kernel's block-local scans + carry stitch,
    which is what makes the differential test meaningful).

    Returns float32 values for ``width == 32``, or ``(lo, hi)`` int32 limb
    arrays for ``width == 64`` (the float64 bitcast is a host-side view).
    """
    flo, fhi = decode_stream_limbs_ref(words_u32, tok_off, nbits, anchor)
    if width == 32:
        return jax.lax.bitcast_convert_type(flo.astype(jnp.int32), jnp.float32)
    return flo.astype(jnp.int32), fhi.astype(jnp.int32)


def payload_words(widths: jnp.ndarray) -> jnp.ndarray:
    """Valid packed word count per block (for stream compaction)."""
    return (widths.astype(jnp.int32) * MINIBLOCK) // 32


def stream_size_bits(widths: jnp.ndarray, exc_count: jnp.ndarray) -> jnp.ndarray:
    """Total compacted stream: payloads + exceptions + anchors/widths/counts."""
    per_block_fixed = 32 + 8 + 8  # anchor + width byte + exception count byte
    return (jnp.sum(payload_words(widths)) * 32
            + jnp.sum(exc_count) * EXC_BITS
            + widths.shape[0] * per_block_fixed)
