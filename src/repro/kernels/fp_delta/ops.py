"""Jit'd user-facing wrappers around the miniblock FP-delta kernels (v2).

Handles arbitrary-length inputs (padding with the last element — zero deltas
are free), Pallas/ref dispatch, and host-side stream compaction to a compact
byte format (used by checkpoint compression, :mod:`repro.train.checkpoint`).

Also home of the *page-stream* decode entry points (:func:`build_page_stream`,
:func:`chunk_plan_pairs`, :func:`decode_stream_device`): batched on-device
execution of the paper-exact FP-delta page format, consumed by
``SpatialParquetReader.read_columnar(device="jax")`` — and of the **fused
decode→refine** entry point
(:func:`decode_refine_stream`), which chains the page-stream decode with the
segmented per-record min/max of :mod:`repro.kernels.minmax` and a bbox
survivor test in one launch chain, so only surviving records (or just the
record mask) ever cross back to the host.

Every device callable goes through a process-wide AOT compile cache
(:func:`_aot`): shapes are pow2-bucketed upstream, and a lock serializes
tracing so concurrent shard-reader threads (``SpatialDatasetScanner``) trace
each shape bucket exactly once instead of racing to retrace per shard.
"""

from __future__ import annotations

import functools
import struct
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.columnar import DeviceCoords, ragged_ranges
from repro.core.fp_delta import HEADER_BITS, FPDeltaPlan

from .. import DeviceCompileError, default_interpret
from . import kernel, ref
from .ref import EXC_BITS, MAX_EXC, MINIBLOCK, STREAM_BLOCK

_MAGIC = b"FPD2"  # FP-Delta Miniblock v2 (patched)


# --------------------------------------------------------- AOT compile cache
# One compiled executable per (callable, shape-bucket, statics) key, shared
# process-wide. The double-checked lock means N scanner worker threads
# hitting the same bucket concurrently cost one trace+compile, not N.
_COMPILE_LOCK = threading.Lock()
_COMPILED: dict[tuple, object] = {}


def _aot(key: tuple, jitted, args: tuple, statics: dict | None = None):
    """Return the compiled executable for ``jitted`` at ``args``' shapes.

    A failed compile raises :class:`~repro.kernels.DeviceCompileError`.
    Compile-vs-execute attribution: a cache miss traces+compiles inside a
    ``jit.compile`` span (cat ``jit``) and bumps the ``jit.compiles``
    counter; a hit bumps ``jit.cache_hits`` — so a trace separates one-time
    compilation cost from steady-state launch cost per shape bucket.
    """
    fn = _COMPILED.get(key)
    if fn is None:
        with _COMPILE_LOCK:
            fn = _COMPILED.get(key)
            if fn is None:
                with obs.span("jit.compile", cat="jit", key=repr(key)):
                    shapes = tuple(
                        jax.ShapeDtypeStruct(np.shape(a), a.dtype) for a in args
                    )
                    try:
                        fn = jitted.lower(*shapes, **(statics or {})).compile()
                    except Exception as exc:
                        raise DeviceCompileError(
                            f"compiling {key!r} failed: {exc}") from exc
                obs.count("jit.compiles")
                _COMPILED[key] = fn
                return fn
    obs.count("jit.cache_hits")
    return fn


def compile_cache_stats() -> dict:
    """Introspection for tests/diagnostics: which buckets have compiled."""
    return {"count": len(_COMPILED), "keys": sorted(map(repr, _COMPILED))}


@dataclass
class MiniblockStream:
    """Device-resident encoded stream (dense, pre-compaction)."""

    packed: jnp.ndarray     # (n_blocks, MINIBLOCK) int32, first w*32 words valid
    widths: jnp.ndarray     # (n_blocks,) int32 in {0} | WIDTHS
    anchors: jnp.ndarray    # (n_blocks,) int32
    exc_idx: jnp.ndarray    # (n_blocks, MAX_EXC) int32
    exc_val: jnp.ndarray    # (n_blocks, MAX_EXC) int32 (raw zigzag)
    exc_count: jnp.ndarray  # (n_blocks,) int32
    n_values: int           # unpadded element count

    @property
    def n_blocks(self) -> int:
        return int(self.packed.shape[0])

    def compact_bits(self) -> int:
        """Size of the compacted stream in bits."""
        return int(ref.stream_size_bits(self.widths, self.exc_count))


def _pad_to_blocks(x) -> tuple[jnp.ndarray, int]:
    x = jnp.asarray(x).reshape(-1)
    if x.dtype == jnp.int32:
        x = jax.lax.bitcast_convert_type(x, jnp.float32)
    if x.dtype != jnp.float32:
        raise TypeError(f"miniblock codec is 32-bit only, got {x.dtype}")
    n = x.shape[0]
    padded = ((n + MINIBLOCK - 1) // MINIBLOCK) * MINIBLOCK
    if padded == 0:
        padded = MINIBLOCK
        x = jnp.zeros(MINIBLOCK, jnp.float32)
    elif padded != n:
        x = jnp.concatenate([x, jnp.broadcast_to(x[-1:], (padded - n,))])
    return x.reshape(-1, MINIBLOCK), n


def encode(x, *, use_pallas: bool = True, interpret: bool | None = None) -> MiniblockStream:
    blocks, n = _pad_to_blocks(x)
    if use_pallas:
        interp = default_interpret() if interpret is None else interpret
        outs = kernel.encode_blocks(blocks, interpret=interp)
    else:
        outs = jax.jit(ref.encode_blocks_ref)(blocks)
    return MiniblockStream(*outs, n)


def decode(stream: MiniblockStream, *, use_pallas: bool = True,
           interpret: bool | None = None, out_dtype=jnp.float32) -> jnp.ndarray:
    args = (stream.packed, stream.widths, stream.anchors,
            stream.exc_idx, stream.exc_val, stream.exc_count)
    if use_pallas:
        interp = default_interpret() if interpret is None else interpret
        x = kernel.decode_blocks(*args, interpret=interp)
    else:
        x = jax.jit(ref.decode_blocks_ref)(*args)
    flat = x.reshape(-1)[: stream.n_values]
    if out_dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(flat, jnp.int32)
    return flat


# ------------------------------------------------------------- host streaming
def to_bytes(stream: MiniblockStream) -> bytes:
    """Compact the dense device stream into contiguous bytes (host side)."""
    packed = np.asarray(stream.packed)
    widths = np.asarray(stream.widths).astype(np.uint8)
    anchors = np.asarray(stream.anchors)
    counts = np.asarray(stream.exc_count).astype(np.uint8)
    exc_idx = np.asarray(stream.exc_idx).astype(np.uint16)
    exc_val = np.asarray(stream.exc_val).astype("<i4")
    n_blocks = len(widths)
    valid = (widths.astype(np.int64) * MINIBLOCK) // 32
    mask = np.arange(MINIBLOCK)[None, :] < valid[:, None]
    payload = packed[mask]  # row-major → block order preserved
    emask = np.arange(MAX_EXC)[None, :] < counts[:, None].astype(np.int64)
    eidx = exc_idx[emask]
    eval_ = exc_val[emask]
    head = _MAGIC + struct.pack("<QI", stream.n_values, n_blocks)
    return (head + widths.tobytes() + counts.tobytes()
            + anchors.astype("<i4").tobytes()
            + eidx.astype("<u2").tobytes() + eval_.tobytes()
            + payload.astype("<i4").tobytes())


def from_bytes(buf: bytes) -> MiniblockStream:
    if buf[:4] != _MAGIC:
        raise ValueError("not an FPD2 stream")
    n_values, n_blocks = struct.unpack_from("<QI", buf, 4)
    off = 4 + 12
    widths = np.frombuffer(buf, np.uint8, n_blocks, off).astype(np.int32)
    off += n_blocks
    counts = np.frombuffer(buf, np.uint8, n_blocks, off).astype(np.int32)
    off += n_blocks
    anchors = np.frombuffer(buf, "<i4", n_blocks, off).astype(np.int32)
    off += 4 * n_blocks
    n_exc = int(counts.sum())
    eidx = np.frombuffer(buf, "<u2", n_exc, off)
    off += 2 * n_exc
    eval_ = np.frombuffer(buf, "<i4", n_exc, off)
    off += 4 * n_exc
    valid = (widths.astype(np.int64) * MINIBLOCK) // 32
    payload = np.frombuffer(buf, "<i4", int(valid.sum()), off)
    packed = np.zeros((n_blocks, MINIBLOCK), np.int32)
    mask = np.arange(MINIBLOCK)[None, :] < valid[:, None]
    packed[mask] = payload
    exc_idx = np.zeros((n_blocks, MAX_EXC), np.int32)
    exc_val = np.zeros((n_blocks, MAX_EXC), np.int32)
    emask = np.arange(MAX_EXC)[None, :] < counts[:, None]
    exc_idx[emask] = eidx
    exc_val[emask] = eval_
    return MiniblockStream(
        jnp.asarray(packed), jnp.asarray(widths), jnp.asarray(anchors),
        jnp.asarray(exc_idx), jnp.asarray(exc_val), jnp.asarray(counts),
        n_values,
    )


# ------------------------------------------------------ page-stream decoding
# Batched on-device execution of host-resolved FPDeltaPlans (the paper-exact
# page format of core/fp_delta.py). The host has already done the sequential
# part — escape resolution — so many pages concatenate into one flat value
# stream: per-value token bit offsets, token widths, and anchor flags, with
# the anchor flags doubling as the segment-id boundaries of the device-side
# segmented cumsum. One launch decodes a whole row group.

# Per-launch cap on packed payload bits: 2^26 bits = 8 MiB of words in HBM.
# Token offsets are int32 bit addresses (< 2^31), and the cap bounds the pow2
# shape buckets a launch can compile to. The kernel itself streams (8, 128)
# blocks, so VMEM does not grow with the launch. Typical row groups are far
# smaller and decode in a single launch; a single page above the cap decodes
# on the host and counts in ``device.host_fallback_pages``.
_MAX_LAUNCH_BITS = 1 << 26


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pow2_bucket(x: int, floor: int) -> int:
    """Next power of two >= max(x, floor): stabilizes jit cache shapes."""
    n = max(int(x), int(floor))
    return 1 << (n - 1).bit_length()


@dataclass
class PageStream:
    """Many pages concatenated into one device-decodable value stream."""

    words32: np.ndarray   # (n_words,) int32, n_words % 128 == 0, >= 2 spill words
    tok_off: np.ndarray   # (n_blocks, STREAM_BLOCK) int32 token bit offsets
    nbits: np.ndarray     # (n_blocks, STREAM_BLOCK) int32 token widths [1, 64]
    anchor: np.ndarray    # (n_blocks, STREAM_BLOCK) int32 0/1 (padding = 1)
    width: int            # 32 or 64 (uniform across the stream)
    counts: tuple[int, ...]  # per-page value counts (output split points)

    @property
    def n_values(self) -> int:
        return sum(self.counts)


def build_page_stream(plans) -> PageStream:
    """Concatenate resolved plans into one :class:`PageStream`.

    Page payloads are placed word-aligned in a shared uint32 buffer; each
    value becomes either an *anchor* (page first value, escaped raw value,
    or any raw-mode value — token width W, starts a segment) or an inline
    n-bit delta token. Total payload must stay under ``_MAX_LAUNCH_BITS``
    (use :func:`chunk_plan_pairs`, which chunks automatically). The counters
    ``launch.values`` and ``launch.values_padded`` add the stream's values
    and the decode lanes it launches (``n_blocks * STREAM_BLOCK``).
    """
    plans = list(plans)
    widths = {p.width for p in plans if p.n_values}
    if len(widths) > 1:
        raise ValueError(f"mixed widths in one page stream: {sorted(widths)}")
    width = widths.pop() if widths else 32

    word_base = 0  # uint64 words placed so far
    wparts: list[np.ndarray] = []
    offp: list[np.ndarray] = []
    nbp: list[np.ndarray] = []
    anchp: list[np.ndarray] = []
    counts: list[int] = []
    for p in plans:
        counts.append(p.n_values)
        if p.n_values == 0:
            continue
        base_bit = word_base * 64
        w = p.words[:-1]  # drop the all-zero spill word; re-guarded globally
        cnt, W = p.n_values, p.width
        if p.n == 0:  # raw mode: every value a W-bit anchor
            off = base_bit + HEADER_BITS + W * np.arange(cnt, dtype=np.int64)
            nb = np.full(cnt, W, np.int64)
            an = np.ones(cnt, np.int64)
        else:
            off = np.empty(cnt, np.int64)
            nb = np.empty(cnt, np.int64)
            an = np.zeros(cnt, np.int64)
            off[0], nb[0], an[0] = base_bit + HEADER_BITS, W, 1
            if cnt > 1:
                # escaped deltas read the raw value after the marker
                off[1:] = base_bit + np.where(p.flags, p.offsets + p.n, p.offsets)
                nb[1:] = np.where(p.flags, W, p.n)
                an[1:] = p.flags
        offp.append(off)
        nbp.append(nb)
        anchp.append(an)
        word_base += len(w)
        wparts.append(w)

    total_bits = word_base * 64
    if total_bits > _MAX_LAUNCH_BITS:
        raise ValueError(
            f"page stream of {total_bits} bits exceeds the per-launch cap "
            f"of {_MAX_LAUNCH_BITS}; use chunk_plan_pairs, which chunks page "
            "pairs across launches and sends oversized pairs to the host")

    words64 = np.concatenate(wparts) if wparts else np.zeros(0, np.uint64)
    # LE uint32 view keeps the bit layout: stream bit b = bit b%32 of word b//32
    words32 = np.ascontiguousarray(words64).view("<u4")
    nw = _pow2_bucket(_round_up(len(words32) + 2, 128), 128)
    wbuf = np.zeros(nw, np.uint32)
    wbuf[: len(words32)] = words32

    n = int(sum(counts))
    n_blocks = _pow2_bucket(-(-max(n, 1) // STREAM_BLOCK), 1)
    pad = n_blocks * STREAM_BLOCK
    obs.count("launch.values", n)
    obs.count("launch.values_padded", pad)
    off_a = np.zeros(pad, np.int64)
    nb_a = np.full(pad, width, np.int64)   # padding: W-bit anchors at bit 0
    an_a = np.ones(pad, np.int64)
    if n:
        off_a[:n] = np.concatenate(offp)
        nb_a[:n] = np.concatenate(nbp)
        an_a[:n] = np.concatenate(anchp)
    shape = (n_blocks, STREAM_BLOCK)
    return PageStream(
        wbuf.view(np.int32),
        off_a.astype(np.int32).reshape(shape),
        nb_a.astype(np.int32).reshape(shape),
        an_a.astype(np.int32).reshape(shape),
        width, tuple(counts),
    )


@functools.lru_cache(maxsize=None)
def _limbs_jit(use_pallas: bool, interpret: bool):
    """Jitted page-stream decode returning uint32 limb pairs."""
    if use_pallas:
        def fn(words32, tok_off, nbits, anchor):
            return kernel.decode_stream_limbs(
                words32, tok_off, nbits, anchor, interpret=interpret)
    else:
        def fn(words32, tok_off, nbits, anchor):
            return ref.decode_stream_limbs_ref(words32, tok_off, nbits, anchor)
    return jax.jit(fn)


def _stream_args(stream: PageStream) -> tuple:
    return (stream.words32, stream.tok_off, stream.nbits, stream.anchor)


def _decode_launch(stream: PageStream, use_pallas: bool,
                   interpret: bool | None):
    """The compiled stream decode and its arguments."""
    interp = default_interpret() if interpret is None else interpret
    args = _stream_args(stream)
    key = ("limbs", stream.words32.shape[0], stream.tok_off.shape[0],
           use_pallas, interp)
    return _aot(key, _limbs_jit(use_pallas, interp), args), args


def _launch_span(stream: PageStream):
    return obs.span("device.decode_launch", cat="device",
                    values=stream.n_values, width=stream.width)


def decode_stream_device(stream: PageStream, *, use_pallas: bool = True,
                         interpret: bool | None = None):
    """Decode a built stream, keeping the result device-resident.

    Returns ``(lo, hi)`` uint32 device arrays of length
    ``n_blocks * STREAM_BLOCK`` (tail is padding; ``hi`` is zero for 32-bit
    streams). The bit patterns equal the host decode exactly.
    """
    fn, args = _decode_launch(stream, use_pallas, interpret)
    with _launch_span(stream):
        return fn(*args)


def decode_page_stream(stream: PageStream, *, use_pallas: bool = True,
                       interpret: bool | None = None) -> np.ndarray:
    """Decode a built stream; returns the concatenated values (float32 for
    W=32, float64 for W=64 — the f64 bitcast is a host-side view of the
    device-produced limbs). Bit-identical to the host ``fp_delta_decode``."""
    n = stream.n_values
    dtype = np.float32 if stream.width == 32 else np.float64
    if n == 0:
        return np.zeros(0, dtype)
    fn, args = _decode_launch(stream, use_pallas, interpret)
    with _launch_span(stream):
        lo, hi = fn(*args)
        with obs.span("device.wait", cat="device"):
            # trim on the host: a device slice compiles one program per length
            return DeviceCoords(lo, hi if stream.width == 64 else None,
                                np.dtype(dtype)).to_numpy()[:n]


def _plan_bits(p: FPDeltaPlan) -> int:
    """Packed payload bits a plan occupies in a page stream (spill word
    excluded — the single source of the launch-cap accounting)."""
    return (len(p.words) - 1) * 64


def chunk_plan_pairs(plans, pairs):
    """Group x/y page-pair plans into fused launches under the launch cap.

    ``plans[2i]``/``plans[2i+1]`` are the x/y plans of pair ``i``;
    ``pairs[i] = (rec_lo, rec_hi)`` its record range. Yields ``("dev",
    plan_list, pair_list, (rec_lo, rec_hi))`` per launch chunk, or
    ``("host", (plan_x, plan_y), None, (rec_lo, rec_hi))`` for a pair whose
    packed payload alone exceeds the cap (the caller host-decodes it via
    ``fp_delta_execute`` — records never straddle pages, so chunk masks
    concatenate exactly; both pages count in ``device.host_fallback_pages``).
    The one launch chunker: it lives next to :data:`_MAX_LAUNCH_BITS`, so
    the cap accounting has a single owner.
    """
    cur_plans: list = []
    cur_pairs: list = []
    bits = 0
    for i, (r0, r1) in enumerate(pairs):
        px, py = plans[2 * i], plans[2 * i + 1]
        pbits = _plan_bits(px) + _plan_bits(py)
        if pbits > _MAX_LAUNCH_BITS:
            if cur_plans:
                yield ("dev", cur_plans, cur_pairs,
                       (cur_pairs[0][0], cur_pairs[-1][1]))
                cur_plans, cur_pairs, bits = [], [], 0
            obs.count("device.host_fallback_pages", 2)
            yield ("host", (px, py), None, (r0, r1))
            continue
        if cur_plans and bits + pbits > _MAX_LAUNCH_BITS:
            yield ("dev", cur_plans, cur_pairs,
                   (cur_pairs[0][0], cur_pairs[-1][1]))
            cur_plans, cur_pairs, bits = [], [], 0
        cur_plans += [px, py]
        cur_pairs.append((r0, r1))
        bits += pbits
    if cur_plans:
        yield ("dev", cur_plans, cur_pairs, (cur_pairs[0][0], cur_pairs[-1][1]))


# ------------------------------------------------------ fused decode→refine
# The device half of ``read_columnar(device="jax", refine=True)``: one jit'd
# chain runs page-stream decode (Pallas), the order-key transform, the
# segmented per-record min/max (repro.kernels.minmax), and the bbox survivor
# test. Decoded coordinates stay device-resident; the host receives the
# record mask (n_records bools) and then gathers only surviving values with
# :func:`gather_stream_values`. Pruned records never materialize off-device.


@dataclass
class RefineAux:
    """Host-built segmentation of a :class:`PageStream` into record slices.

    A record's x values occupy one contiguous slice of the stream and its y
    values another (pages are record-aligned and interleave x,y per page).
    ``seg_flag`` marks slice starts (padding tail flagged, mirroring the
    anchor-padding rule of the decode kernel); ``end_pos[r] = (x_end,
    y_end)`` is where the inclusive segmented scan holds record ``r``'s
    reduction. ``x_start``/``y_start``/``counts`` are the slice geometry the
    host uses to build survivor gather indices. ``edge_off``/``edge_poly``
    (exact scans only) are the records' ring and line-string edges over
    their values in record order, from the rep levels
    (:func:`repro.core.exact.edge_partners`).
    """

    seg_flag: np.ndarray   # (n_blocks, STREAM_BLOCK) int32, 1 at slice starts
    end_pos: np.ndarray    # (n_rec_pad, 2) int32
    valid: np.ndarray      # (n_rec_pad,) bool — records with >= 1 value
    n_records: int
    x_start: np.ndarray    # (n_records,) int64 stream offset of x slice
    y_start: np.ndarray    # (n_records,) int64
    counts: np.ndarray     # (n_records,) int64 values per record (per axis)
    edge_off: np.ndarray | None = None    # (sum(counts),) int32
    edge_poly: np.ndarray | None = None   # (sum(counts),) bool


def build_refine_aux(stream: PageStream, pairs, rec_vcounts,
                     edges=None) -> RefineAux:
    """Segment a stream built from interleaved x,y page pairs by record.

    ``pairs[i] = (r0, r1)``: the record range covered by the i-th x/y page
    pair (``stream.counts[2i]``/``[2i+1]`` are its value counts); records are
    indexed locally and contiguously across pairs. ``rec_vcounts[r]`` is the
    per-axis value count of record ``r``. ``edges = (off, poly)``, for exact
    scans, carries the records' edges from their rep levels.
    """
    counts = np.ascontiguousarray(rec_vcounts, dtype=np.int64)
    if edges is not None and any(len(a) != counts.sum() for a in edges):
        raise ValueError("edges must hold one entry per record value")
    n_rec = len(counts)
    total = stream.n_values
    n_pad_vals = stream.tok_off.size
    flag = np.zeros(n_pad_vals, np.int32)
    flag[total:] = 1  # isolate padding into its own throwaway segments
    x_start = np.zeros(n_rec, np.int64)
    y_start = np.zeros(n_rec, np.int64)
    off = 0
    for i, (r0, r1) in enumerate(pairs):
        c = counts[r0:r1]
        nz = c > 0
        starts = off + np.cumsum(c) - c
        x_start[r0:r1] = starts
        flag[starts[nz]] = 1
        off += int(stream.counts[2 * i])
        starts = off + np.cumsum(c) - c
        y_start[r0:r1] = starts
        flag[starts[nz]] = 1
        off += int(stream.counts[2 * i + 1])
    if off != total:
        raise ValueError(f"refine aux covers {off} values, stream has {total}")
    n_rec_pad = _pow2_bucket(max(n_rec, 1), 8)
    end = np.zeros((n_rec_pad, 2), np.int32)
    end[:n_rec, 0] = x_start + np.maximum(counts - 1, 0)
    end[:n_rec, 1] = y_start + np.maximum(counts - 1, 0)
    valid = np.zeros(n_rec_pad, bool)
    valid[:n_rec] = counts > 0
    return RefineAux(flag.reshape(stream.tok_off.shape), end, valid, n_rec,
                     x_start, y_start, counts,
                     *(edges if edges is not None else ()))


@functools.lru_cache(maxsize=None)
def _refine_jit(width: int, use_pallas: bool, interpret: bool):
    """Jitted fused chain: decode limbs → order keys → segmented min/max →
    bbox survivor mask. Returns (lo, hi, keep)."""
    from repro.kernels.minmax import (
        float_order_keys,
        inf_keys,
        lex_ge,
        lex_le,
        segment_minmax,
    )

    (neg_lo, neg_hi), (pos_lo, pos_hi) = inf_keys(width)

    def fn(words32, tok_off, nbits, anchor, seg_flag, end_pos, valid, qkeys):
        if use_pallas:
            flo, fhi = kernel.decode_stream_limbs(
                words32, tok_off, nbits, anchor, interpret=interpret)
        else:
            flo, fhi = ref.decode_stream_limbs_ref(words32, tok_off, nbits, anchor)
        klo, khi = float_order_keys(flo, fhi, width)
        n_blocks = tok_off.shape[0]
        mnlo, mnhi, mxlo, mxhi = segment_minmax(
            klo.astype(jnp.int32).reshape(n_blocks, STREAM_BLOCK),
            khi.astype(jnp.int32).reshape(n_blocks, STREAM_BLOCK),
            seg_flag, use_pallas=use_pallas, interpret=interpret)
        ex, ey = end_pos[:, 0], end_pos[:, 1]

        def stat(a, i):
            return jnp.take(a, i, mode="clip")

        q = qkeys.astype(jnp.uint32)
        kneg = (jnp.uint32(neg_lo), jnp.uint32(neg_hi))
        kpos = (jnp.uint32(pos_lo), jnp.uint32(pos_hi))
        xmn = (stat(mnlo, ex), stat(mnhi, ex))
        xmx = (stat(mxlo, ex), stat(mxhi, ex))
        ymn = (stat(mnlo, ey), stat(mnhi, ey))
        ymx = (stat(mxlo, ey), stat(mxhi, ey))
        keep = (
            valid
            # the bbox intersection test, in key space
            & lex_le(*xmn, q[1, 0], q[1, 1]) & lex_ge(*xmx, q[0, 0], q[0, 1])
            & lex_le(*ymn, q[3, 0], q[3, 1]) & lex_ge(*ymx, q[2, 0], q[2, 1])
            # NaN fence: any NaN keys strictly outside [-inf, +inf], and the
            # host oracle (NaN-propagating minimum.reduceat) drops the record
            & lex_le(*xmx, *kpos) & lex_ge(*xmn, *kneg)
            & lex_le(*ymx, *kpos) & lex_ge(*ymn, *kneg)
        )
        return flo, fhi, keep

    return jax.jit(fn)


@dataclass
class RefineResult:
    """Fused-launch output: device-resident limbs + the host record mask."""

    lo: object            # (n_pad,) uint32 device array (None when skipped)
    hi: object            # (n_pad,) uint32 device array (None when skipped)
    keep: np.ndarray      # (n_records,) bool — the only mandatory transfer


def decode_refine_stream(stream: PageStream, aux: RefineAux, bbox, *,
                         use_pallas: bool = True,
                         interpret: bool | None = None) -> RefineResult:
    """Fused decode→bbox-refine over one built page stream.

    Decodes the stream on-device, reduces per-record [min,max] of x and y in
    key space, and tests each record against ``bbox`` — all in one jit'd
    launch chain. Only the record mask crosses back to the host here; pull
    surviving coordinates afterwards with :func:`gather_stream_values`.
    The surviving record set is **bit-identical** to the host refine
    (NaN-propagating ``minimum.reduceat`` + float compares).
    """
    from repro.kernels.minmax import bbox_query_keys

    interp = default_interpret() if interpret is None else interpret
    dtype = np.float32 if stream.width == 32 else np.float64
    qkeys = bbox_query_keys(bbox, dtype)
    if qkeys is None:  # NaN bound: the host compare keeps nothing
        return RefineResult(None, None, np.zeros(aux.n_records, bool))
    args = _stream_args(stream) + (aux.seg_flag, aux.end_pos, aux.valid, qkeys)
    key = ("refine", stream.words32.shape[0], stream.tok_off.shape[0],
           aux.end_pos.shape[0], stream.width, use_pallas, interp)
    fn = _aot(key, _refine_jit(stream.width, use_pallas, interp), args)
    with obs.span("device.refine_launch", cat="device",
                  values=stream.n_values, records=aux.n_records,
                  width=stream.width):
        lo, hi, keep = fn(*args)
        with obs.span("device.wait", cat="device"):
            keep = np.asarray(keep)[: aux.n_records]
    return RefineResult(lo, hi, keep)


# ------------------------------------------------- multi-query refinement
# The serve-tier variant of the fused chain (repro.serve.query_scheduler):
# one decode + segmented min/max launch answers Q in-flight bbox queries at
# once by stacking the queries' order-key bounds into a (Q, 4, 2) operand
# and broadcasting the NaN-fenced survivor test over the new bbox axis.
# The per-record min/max key stack is also returned device-resident, so a
# decoded-row-group cache can answer *later* query waves with a compare-only
# launch (refine_minmax_multi) instead of re-decoding.


def _keep_from_minmax(mm, valid, qkeys, width):
    """(8, R) per-record min/max key limbs × (Q, 4, 2) query keys → (Q, R).

    ``mm`` rows: x (min_lo, min_hi, max_lo, max_hi) then y, taken at each
    record's scan end position. The test is :func:`_refine_jit`'s compare
    verbatim, broadcast over the query axis — each row is bit-identical to a
    solo refine of that query.
    """
    from repro.kernels.minmax import inf_keys, lex_ge, lex_le

    (neg_lo, neg_hi), (pos_lo, pos_hi) = inf_keys(width)
    kneg = (jnp.uint32(neg_lo), jnp.uint32(neg_hi))
    kpos = (jnp.uint32(pos_lo), jnp.uint32(pos_hi))
    q = qkeys.astype(jnp.uint32)
    xmn = (mm[0][None], mm[1][None])
    xmx = (mm[2][None], mm[3][None])
    ymn = (mm[4][None], mm[5][None])
    ymx = (mm[6][None], mm[7][None])

    def qb(row, limb):  # one query-bound limb as a (Q, 1) column
        return q[:, row, limb][:, None]

    return (
        valid[None]
        # the bbox intersection test, in key space, per query row
        & lex_le(*xmn, qb(1, 0), qb(1, 1)) & lex_ge(*xmx, qb(0, 0), qb(0, 1))
        & lex_le(*ymn, qb(3, 0), qb(3, 1)) & lex_ge(*ymx, qb(2, 0), qb(2, 1))
        # NaN fence, identical to the solo refine
        & lex_le(*xmx, *kpos) & lex_ge(*xmn, *kneg)
        & lex_le(*ymx, *kpos) & lex_ge(*ymn, *kneg)
    )


@functools.lru_cache(maxsize=None)
def _refine_multi_jit(width: int, use_pallas: bool, interpret: bool):
    """Jitted fused chain with a bbox-count axis: decode limbs → order keys
    → segmented min/max → per-record key stack → (Q, R) survivor masks."""
    from repro.kernels.minmax import float_order_keys, segment_minmax

    def fn(words32, tok_off, nbits, anchor, seg_flag, end_pos, valid, qkeys):
        if use_pallas:
            flo, fhi = kernel.decode_stream_limbs(
                words32, tok_off, nbits, anchor, interpret=interpret)
        else:
            flo, fhi = ref.decode_stream_limbs_ref(words32, tok_off, nbits, anchor)
        klo, khi = float_order_keys(flo, fhi, width)
        n_blocks = tok_off.shape[0]
        mnlo, mnhi, mxlo, mxhi = segment_minmax(
            klo.astype(jnp.int32).reshape(n_blocks, STREAM_BLOCK),
            khi.astype(jnp.int32).reshape(n_blocks, STREAM_BLOCK),
            seg_flag, use_pallas=use_pallas, interpret=interpret)
        ex, ey = end_pos[:, 0], end_pos[:, 1]

        def stat(a, i):
            return jnp.take(a, i, mode="clip")

        mm = jnp.stack([
            stat(mnlo, ex), stat(mnhi, ex), stat(mxlo, ex), stat(mxhi, ex),
            stat(mnlo, ey), stat(mnhi, ey), stat(mxlo, ey), stat(mxhi, ey),
        ])
        return flo, fhi, mm, _keep_from_minmax(mm, valid, qkeys, width)

    return jax.jit(fn)


@dataclass
class MultiRefineResult:
    """Fused multi-query launch output.

    ``lo``/``hi`` are the decoded stream limbs and ``minmax`` the (8,
    n_rec_pad) per-record min/max key stack — all device-resident and
    cacheable; ``keep`` is the (Q, n_records) host survivor matrix.
    """

    lo: object
    hi: object
    minmax: object
    keep: np.ndarray


def _pad_query_keys(qkeys) -> tuple[np.ndarray, int]:
    nq = len(qkeys)
    qp = _pow2_bucket(max(nq, 1), 4)
    qpad = np.zeros((qp, 4, 2), np.uint32)
    qpad[:nq] = qkeys
    return qpad, qp


def decode_refine_stream_multi(stream: PageStream, aux: RefineAux, qkeys,
                               qvalid, *, use_pallas: bool = True,
                               interpret: bool | None = None) -> MultiRefineResult:
    """Fused decode→refine answering Q stacked bbox queries in one launch.

    ``qkeys``/``qvalid`` come from
    :func:`repro.kernels.minmax.stack_bbox_query_keys`. Each query's
    survivor row is bit-identical to a solo :func:`decode_refine_stream`
    over the same stream; invalid (NaN-bound) queries get all-False rows.
    The query axis is pow2-padded so the compiled shape is shared across
    nearby wave sizes.
    """
    interp = default_interpret() if interpret is None else interpret
    nq = len(qkeys)
    qpad, qp = _pad_query_keys(qkeys)
    args = _stream_args(stream) + (aux.seg_flag, aux.end_pos, aux.valid, qpad)
    key = ("refine_multi", stream.words32.shape[0], stream.tok_off.shape[0],
           aux.end_pos.shape[0], qp, stream.width, use_pallas, interp)
    fn = _aot(key, _refine_multi_jit(stream.width, use_pallas, interp), args)
    with obs.span("device.refine_multi_launch", cat="device",
                  values=stream.n_values, records=aux.n_records,
                  queries=nq, width=stream.width):
        lo, hi, mm, keep = fn(*args)
        with obs.span("device.wait", cat="device"):
            keep = np.asarray(keep)[:nq, : aux.n_records].copy()
    keep[~np.asarray(qvalid, bool)] = False
    return MultiRefineResult(lo, hi, mm, keep)


@functools.lru_cache(maxsize=None)
def _minmax_keep_jit(width: int):
    return jax.jit(
        lambda mm, valid, qkeys: _keep_from_minmax(mm, valid, qkeys, width))


def refine_minmax_multi(minmax, valid, qkeys, qvalid, *, width: int,
                        n_records: int) -> np.ndarray:
    """Re-test a cached per-record min/max key stack against Q new bboxes.

    The cache-hit half of the serve tier: no decode, no scan — one tiny
    compare launch over the stored ``(8, n_rec_pad)`` stack from
    :class:`MultiRefineResult`. Same compare as the fused miss path, so hit
    and miss survivor rows are bit-identical. Returns (Q, n_records) bool.
    """
    nq = len(qkeys)
    qpad, qp = _pad_query_keys(qkeys)
    args = (minmax, valid, qpad)
    key = ("minmax_keep", int(minmax.shape[1]), qp, width)
    fn = _aot(key, _minmax_keep_jit(width), args)
    with obs.span("device.refine_cached", cat="device",
                  records=n_records, queries=nq, width=width):
        keep = fn(*args)
        with obs.span("device.wait", cat="device"):
            keep = np.array(np.asarray(keep)[:nq, :n_records])
    keep[~np.asarray(qvalid, bool)] = False
    return keep


_take_limbs_jit = jax.jit(
    lambda lo, hi, idx: (jnp.take(lo, idx, mode="clip"),
                         jnp.take(hi, idx, mode="clip")))


def gather_stream_values(lo, hi, idx: np.ndarray, width: int, dtype,
                         *, keep_on_device: bool = False):
    """Compact survivor values out of a decoded stream by position.

    ``idx`` (host int array) selects stream positions; the gather runs
    on-device through a pow2-bucketed compiled take, so the host transfer is
    bounded by the survivor count (never the full column). Returns a numpy
    array of ``dtype`` — or a :class:`~repro.core.columnar.DeviceCoords`
    when ``keep_on_device`` (zero host transfer).
    """
    dtype = np.dtype(dtype)
    n = len(idx)
    if n == 0:
        if keep_on_device:
            return DeviceCoords(jnp.zeros(0, jnp.uint32),
                                jnp.zeros(0, jnp.uint32) if width == 64 else None,
                                dtype)
        return np.zeros(0, dtype)
    size = _pow2_bucket(n, 8)
    idx_pad = np.zeros(size, np.int32)
    idx_pad[:n] = idx
    key = ("take", int(lo.shape[0]), size)
    fn = _aot(key, _take_limbs_jit, (lo, hi, idx_pad))
    with obs.span("device.gather", cat="transfer", values=n,
                  on_device=bool(keep_on_device)):
        glo, ghi = fn(lo, hi, idx_pad)
        ghi = ghi if width == 64 else None
        if not keep_on_device:
            # trim on the host: a device slice compiles one program per n
            with obs.span("device.wait", cat="device"):
                return DeviceCoords(glo, ghi, dtype).to_numpy()[:n]
        coords = DeviceCoords(glo[:n], None if ghi is None else ghi[:n], dtype)
    return coords


def compress_array(x: np.ndarray, **kw) -> bytes:
    """One-shot lossless compression of a float32/int32 array (any shape)."""
    return to_bytes(encode(np.asarray(x).reshape(-1), **kw))


def decompress_array(buf: bytes, shape, dtype=np.float32, **kw) -> np.ndarray:
    stream = from_bytes(buf)
    want_i32 = np.dtype(dtype) == np.int32
    flat = decode(stream, out_dtype=jnp.int32 if want_i32 else jnp.float32, **kw)
    return np.asarray(flat).reshape(shape).view(dtype)
