"""Pallas TPU kernels: per-page [min,max] statistics (paper §4 index build)
and the segmented per-record min/max scan of the fused decode→refine path.

``minmax``: grid is (n_pages, page_tiles): the page dimension is parallel,
the tile dimension is sequential, accumulating per-lane extrema in the
page's lane-dense ``(1, 128)`` output block — pages of any size stream
through a fixed ``(16, 128)`` VMEM block, so the working set is constant
regardless of page size.

``segminmax_blocks``: the record-granular sibling, structured exactly like
the page-stream decode kernel in ``repro.kernels.fp_delta``: each grid step
runs a block-local segmented min/max scan (log-step rotate-and-combine on
the VPU, :func:`repro.kernels.tile_scan.tile_scan`) over one ``(8, 128)``
tile of 1024 order-key limb pairs; cross-block carries are
stitched afterwards with one tiny associative scan over per-block summaries,
keeping the grid embarrassingly parallel. The scan state per element is
``(min_lo, min_hi, max_lo, max_hi, seen_flag)`` with lexicographic uint32
limb compares — see ref.py for the order-key math and the flat oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import tile_scan
from .ref import _MAX_IDENT, _MIN_IDENT, minmax_seg_combine

_TILE = 2048  # values per grid step of the page min/max: one (16, 128) block
_TILE_ROWS = _TILE // 128

SEG_BLOCK = 1024  # values per grid step of the segmented scan, one VPU tile
_BLOCK_2D = (8, 128)


def _minmax_kernel(x_ref, min_ref, max_ref):
    # per-lane partial extrema of the page, accumulated over its tiles in
    # the lane-dense output block; the final 128-lane reduce is XLA's
    t = pl.program_id(1)
    x = x_ref[0]
    tile_min = jnp.min(x, axis=0, keepdims=True)
    tile_max = jnp.max(x, axis=0, keepdims=True)

    @pl.when(t == 0)
    def _init():
        min_ref[0] = tile_min
        max_ref[0] = tile_max

    @pl.when(t > 0)
    def _acc():
        min_ref[0] = jnp.minimum(min_ref[0], tile_min)
        max_ref[0] = jnp.maximum(max_ref[0], tile_max)


@functools.partial(jax.jit, static_argnames=("interpret",))
def minmax(x: jnp.ndarray, *, interpret: bool = True):
    """x: (n_pages, page_size) -> ((n_pages,) min, (n_pages,) max).

    page_size must be a multiple of _TILE; ops.py pads with edge values.
    """
    n_pages, page_size = x.shape
    assert page_size % _TILE == 0, page_size
    tiles = page_size // _TILE
    x3 = x.reshape(n_pages, page_size // 128, 128)
    out_spec = pl.BlockSpec((1, 1, 128), lambda p, t: (p, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_pages, 1, 128), x.dtype)
    mins, maxs = pl.pallas_call(
        _minmax_kernel,
        grid=(n_pages, tiles),
        in_specs=[pl.BlockSpec((1, _TILE_ROWS, 128), lambda p, t: (p, t, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x3)
    return jnp.min(mins, axis=(1, 2)), jnp.max(maxs, axis=(1, 2))


# ---------------------------------------------------------- segmented minmax
def _segminmax_kernel(klo_ref, khi_ref, flag_ref,
                      mnlo_ref, mnhi_ref, mxlo_ref, mxhi_ref, seen_ref):
    klo = klo_ref[0]
    khi = khi_ref[0]
    mnlo, mnhi, mxlo, mxhi, seen = tile_scan(
        minmax_seg_combine, (klo, khi, klo, khi, flag_ref[0] != 0),
        (_MIN_IDENT, _MIN_IDENT, _MAX_IDENT, _MAX_IDENT, False))
    mnlo_ref[0] = mnlo
    mnhi_ref[0] = mnhi
    mxlo_ref[0] = mxlo
    mxhi_ref[0] = mxhi
    seen_ref[0] = seen.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def segminmax_blocks(key_lo, key_hi, flag, *, interpret: bool = True):
    """Batched segmented min/max over order keys (one launch per stream).

    ``key_lo``/``key_hi``: (n_blocks, SEG_BLOCK) int32 order-key limbs;
    ``flag``: (n_blocks, SEG_BLOCK) int32, 1 at segment starts (padding tail
    elements must be flagged so they cannot leak into real segments).
    Returns ``(min_lo, min_hi, max_lo, max_hi)`` uint32 arrays flattened to
    (n_blocks*SEG_BLOCK,): the inclusive segmented scan, so the value at a
    segment's last position is that segment's reduction. Bit-identical to
    ``ref.segment_minmax_ref``.
    """
    n_blocks = key_lo.shape[0]
    tile = (n_blocks, *_BLOCK_2D)
    spec = pl.BlockSpec((1, *_BLOCK_2D), lambda b: (b, 0, 0))
    key = jax.ShapeDtypeStruct(tile, jnp.uint32)
    outs = pl.pallas_call(
        _segminmax_kernel,
        grid=(n_blocks,),
        in_specs=[spec, spec, spec],
        out_specs=[spec] * 5,
        out_shape=[key] * 4 + [jax.ShapeDtypeStruct(tile, jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(key_lo.astype(jnp.uint32).reshape(tile),
      key_hi.astype(jnp.uint32).reshape(tile), flag.reshape(tile))
    mnlo, mnhi, mxlo, mxhi = (o.reshape(n_blocks, SEG_BLOCK) for o in outs[:4])
    seen = outs[4].reshape(n_blocks, SEG_BLOCK) != 0
    # Carry stitch: block b inherits the running min/max of the last open
    # segment before it — an exclusive segmented combine of the per-block
    # summaries (each block's last scanned element + "block saw a flag").
    summ = (mnlo[:, -1], mnhi[:, -1], mxlo[:, -1], mxhi[:, -1], seen[:, -1])
    inc = jax.lax.associative_scan(minmax_seg_combine, summ)
    ident = (
        jnp.full(1, _MIN_IDENT, jnp.uint32), jnp.full(1, _MIN_IDENT, jnp.uint32),
        jnp.full(1, _MAX_IDENT, jnp.uint32), jnp.full(1, _MAX_IDENT, jnp.uint32),
        jnp.zeros(1, jnp.bool_),
    )
    carry = tuple(
        jnp.concatenate([i, s[:-1]])[:, None] for i, s in zip(ident, inc)
    )
    local = (mnlo, mnhi, mxlo, mxhi, seen)
    fin = minmax_seg_combine(carry, local)
    return tuple(f.reshape(-1) for f in fin[:4])
