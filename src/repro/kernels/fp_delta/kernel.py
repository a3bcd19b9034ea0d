"""Pallas TPU kernels for miniblock FP-delta encode/decode (v2: patched).

TPU adaptation of Spatial Parquet §3 (see DESIGN.md §5 and ref.py for the
format contract). Each grid step processes one miniblock of 1024 float32
values — exactly one (8, 128) VPU tile — entirely in VMEM:

* encode: bitcast → in-block delta (the anchor makes ``delta[0] = 0``, so no
  cross-block carry exists) → zigzag → exact significant-bit ladder →
  cost-optimal lane-aligned width → all six packings computed with static
  shapes and combined with a masked sum; exceptions (FastPFOR-style patches
  for deltas wider than w) are compacted with a (MAX_EXC, 1024) one-hot
  contraction against iota — data-independent control flow, no scatter.
* decode: the mirror image; exceptions re-injected with the same one-hot
  trick, and the sequential prefix sum replaced by a log2(1024) = 10-step
  shifted-add scan (VPU-parallel).

Grid iteration over miniblocks is embarrassingly parallel.

The module also hosts the *page-stream* decode kernel
(:func:`decode_stream_blocks`): on-device execution of the paper-exact
FP-delta page format from host-resolved ``FPDeltaPlan``s — see the
"page stream" section of ref.py for the format math and ops.py for the
batching layer that feeds it. It is the lake's device decode and compiles
for the TPU (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import tile_scan
from .ref import (
    MAX_EXC,
    MINIBLOCK,
    STREAM_BLOCK,
    WIDTHS,
    choose_width,
    extract_exceptions,
    extract_tokens,
    gather_words,
    inject_exceptions,
    pack_candidate,
    seg_combine,
    significant_bits_u32,
    stream_values,
    unpack_candidate,
    unzigzag_u32,
    zigzag_i32,
)

_BLOCK_2D = (8, 128)  # 1024 values as one VPU tile


def _encode_kernel(x_ref, packed_ref, width_ref, anchor_ref,
                   exc_idx_ref, exc_val_ref, count_ref):
    x = x_ref[...].reshape(MINIBLOCK)
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    prev = jnp.concatenate([xi[:1], xi[:-1]])
    zig = zigzag_i32(xi - prev)  # delta[0] == 0 by construction
    nbits = significant_bits_u32(zig)
    width, _ = choose_width(nbits[None, :])
    width = width[0]
    exc_idx, exc_val, count = extract_exceptions(zig, width)
    packed = jnp.zeros(MINIBLOCK, dtype=jnp.uint32)
    for w in WIDTHS:  # static unroll; masked sum select (fields disjoint)
        packed = packed + jnp.where(width == w, pack_candidate(zig, w), jnp.uint32(0))
    packed_ref[...] = packed.astype(jnp.int32).reshape(1, *_BLOCK_2D)
    width_ref[0, 0] = width
    anchor_ref[0, 0] = xi[0]
    exc_idx_ref[...] = exc_idx.reshape(1, MAX_EXC)
    exc_val_ref[...] = exc_val.astype(jnp.int32).reshape(1, MAX_EXC)
    count_ref[0, 0] = count


def _decode_kernel(packed_ref, width_ref, anchor_ref,
                   exc_idx_ref, exc_val_ref, count_ref, x_ref):
    words = packed_ref[...].reshape(MINIBLOCK).astype(jnp.uint32)
    width = width_ref[0, 0]
    anchor = anchor_ref[0, 0]
    zig = jnp.zeros(MINIBLOCK, dtype=jnp.uint32)
    for w in WIDTHS:
        zig = zig + jnp.where(width == w, unpack_candidate(words, w), jnp.uint32(0))
    zig = inject_exceptions(
        zig, exc_idx_ref[...].reshape(MAX_EXC),
        exc_val_ref[...].reshape(MAX_EXC).astype(jnp.uint32), count_ref[0, 0],
    )
    delta = unzigzag_u32(zig)
    # log-step inclusive prefix sum (10 shifted adds on the VPU)
    acc = delta
    shift = 1
    while shift < MINIBLOCK:
        shifted = jnp.concatenate([jnp.zeros(shift, jnp.int32), acc[:-shift]])
        acc = acc + shifted
        shift *= 2
    xi = anchor + acc
    x_ref[...] = jax.lax.bitcast_convert_type(xi, jnp.float32).reshape(1, *_BLOCK_2D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_blocks(x: jnp.ndarray, *, interpret: bool = True):
    """x: (n_blocks, MINIBLOCK) float32 -> (packed, widths, anchors, exc_idx,
    exc_val, exc_count). Bit-identical to ref.encode_blocks_ref."""
    n_blocks = x.shape[0]
    assert x.shape == (n_blocks, MINIBLOCK), x.shape
    x2 = x.reshape(n_blocks, *_BLOCK_2D)
    outs = pl.pallas_call(
        _encode_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
            pl.BlockSpec((1, MAX_EXC), lambda b: (b, 0)),
            pl.BlockSpec((1, MAX_EXC), lambda b: (b, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, *_BLOCK_2D), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, MAX_EXC), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, MAX_EXC), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x2)
    packed, widths, anchors, exc_idx, exc_val, count = outs
    return (packed.reshape(n_blocks, MINIBLOCK), widths[:, 0], anchors[:, 0],
            exc_idx, exc_val, count[:, 0])


# --------------------------------------------------------------- page stream
# Decode kernel for the paper-exact FP-delta page format (see ref.py "page
# stream" section for the math). The data-dependent part — fetching each
# token's three-word window from the packed words — is one XLA gather ahead
# of the kernel (Mosaic lowers no 1-D gather). Each grid step then decodes
# one STREAM_BLOCK of the concatenated value stream as one (8, 128) tile:
# token extraction, escape injection, un-zigzag, and a block-local segmented
# scan. Cross-block carries are stitched afterwards with one tiny
# associative scan over per-block summaries — the grid stays embarrassingly
# parallel, like the miniblock codec above.


def _stream_decode_kernel(w0_ref, w1_ref, w2_ref, off_ref, nbits_ref,
                          anch_ref, lo_ref, hi_ref, seen_ref):
    anc = anch_ref[0] != 0
    lo, hi = extract_tokens(w0_ref[0], w1_ref[0], w2_ref[0], off_ref[0],
                            nbits_ref[0])
    vlo, vhi = stream_values(lo, hi, anc)
    flo, fhi, seen = tile_scan(seg_combine, (vlo, vhi, anc), (0, 0, False))
    lo_ref[0] = flo
    hi_ref[0] = fhi
    seen_ref[0] = seen.astype(jnp.int32)


def decode_stream_limbs(words32, tok_off, nbits, anchor, *, interpret: bool = True):
    """Page-stream decode returning the raw W-bit patterns as uint32 limbs.

    Same contract as :func:`decode_stream_blocks` but without the final
    bitcast/limb-split: returns ``(lo, hi)`` uint32 arrays flattened to
    ``(n_blocks*STREAM_BLOCK,)`` (``hi`` is all-zero for 32-bit streams).
    This is the form the fused decode→refine chain consumes — the order-key
    transform and segmented bbox reduction run directly on the limbs.
    """
    n_blocks = tok_off.shape[0]
    tile = (n_blocks, *_BLOCK_2D)
    windows = [w.reshape(tile) for w in gather_words(words32, tok_off)]
    spec = pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0))
    outs = pl.pallas_call(
        _stream_decode_kernel,
        grid=(n_blocks,),
        in_specs=[spec] * 6,
        out_specs=[spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(tile, jnp.uint32),
            jax.ShapeDtypeStruct(tile, jnp.uint32),
            jax.ShapeDtypeStruct(tile, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*windows, tok_off.reshape(tile), nbits.reshape(tile),
      anchor.reshape(tile))
    lo = outs[0].reshape(n_blocks, STREAM_BLOCK)
    hi = outs[1].reshape(n_blocks, STREAM_BLOCK)
    seen = outs[2].reshape(n_blocks, STREAM_BLOCK) != 0
    # Carry stitch: block b inherits the running value of the last anchor
    # segment before it — an exclusive segmented combine of the per-block
    # summaries (each block's last scanned element + "block saw an anchor").
    ilo, ihi, _ = jax.lax.associative_scan(
        seg_combine, (lo[:, -1], hi[:, -1], seen[:, -1]))
    clo = jnp.concatenate([jnp.zeros(1, jnp.uint32), ilo[:-1]])
    chi = jnp.concatenate([jnp.zeros(1, jnp.uint32), ihi[:-1]])
    slo = lo + clo[:, None]
    carry = (slo < lo).astype(jnp.uint32)
    shi = hi + chi[:, None] + carry
    flo = jnp.where(seen, lo, slo).reshape(-1)
    fhi = jnp.where(seen, hi, shi).reshape(-1)
    return flo, fhi


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def decode_stream_blocks(words32, tok_off, nbits, anchor, *,
                         width: int, interpret: bool = True):
    """Batched page-stream decode (one launch for many concatenated pages).

    ``words32``: (n_words,) int32 — LE uint32 view of the packed streams,
    ``n_words % 128 == 0`` with >= 2 trailing spill words. ``tok_off`` /
    ``nbits`` / ``anchor``: (n_blocks, STREAM_BLOCK) int32; padding tail
    elements must be anchors so they cannot leak into real segments.
    Returns the decoded W-bit patterns flattened to (n_blocks*STREAM_BLOCK,):
    float32 (bitcast on-device) for ``width == 32``, else (lo, hi) int32
    limbs. Bit-identical to ``ref.decode_stream_ref``.
    """
    flo, fhi = decode_stream_limbs(words32, tok_off, nbits, anchor,
                                   interpret=interpret)
    if width == 32:
        return jax.lax.bitcast_convert_type(flo.astype(jnp.int32), jnp.float32)
    return flo.astype(jnp.int32), fhi.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_blocks(packed, widths, anchors, exc_idx, exc_val, exc_count,
                  *, interpret: bool = True):
    """Inverse of encode_blocks -> (n_blocks, MINIBLOCK) float32."""
    n_blocks = packed.shape[0]
    assert packed.shape == (n_blocks, MINIBLOCK), packed.shape
    p2 = packed.reshape(n_blocks, *_BLOCK_2D)
    x = pl.pallas_call(
        _decode_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
            pl.BlockSpec((1, MAX_EXC), lambda b: (b, 0)),
            pl.BlockSpec((1, MAX_EXC), lambda b: (b, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, *_BLOCK_2D), jnp.float32),
        interpret=interpret,
    )(p2, widths.reshape(n_blocks, 1), anchors.reshape(n_blocks, 1),
      exc_idx, exc_val, exc_count.reshape(n_blocks, 1))
    return x.reshape(n_blocks, MINIBLOCK)
