"""Wrappers for the page-statistics kernels: ragged pages, padding, dispatch.

``column_page_stats`` is fully batched: ragged record-aligned pages are
padded edge-value style into one ``(n_pages, max_len)`` matrix and reduced in
a **single** ``page_minmax`` launch (the per-page Python loop of earlier
revisions launched the kernel once per page). Edge padding keeps per-page
results identical to the loop; empty pages are patched to ``(+inf, -inf)``
on the host afterwards.

``segment_minmax`` dispatches the segmented per-record min/max scan (order
keys, see ref.py) between the Pallas block kernel and the flat jnp oracle —
the reduction stage of ``repro.kernels.fp_delta.decode_refine_stream``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import default_interpret
from . import kernel, ref
from .kernel import _TILE, SEG_BLOCK


def page_minmax(
    x: jnp.ndarray, *, use_pallas: bool = True, interpret: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(n_pages, page_size) -> per-page (min, max); pads to the VMEM tile."""
    x = jnp.asarray(x)
    n_pages, page_size = x.shape
    pad = (-page_size) % _TILE
    if pad:
        x = jnp.concatenate([x, jnp.broadcast_to(x[:, -1:], (n_pages, pad))], axis=1)
    if not use_pallas:
        return jax.jit(ref.minmax_ref)(x)
    interp = default_interpret() if interpret is None else interpret
    return kernel.minmax(x, interpret=interp)


# dense-batch element budget of column_page_stats (float32 elements, 64 MiB):
# bounds the padded (rows, max_len) matrix so one outlier-long page cannot
# inflate the whole batch to n_pages * max_len
_BATCH_BUDGET = 1 << 24


def _batch_spans(counts: np.ndarray):
    """Split pages into contiguous row spans with rows * running_max under
    the budget (a skewed giant page lands in its own span)."""
    spans = []
    start, mx = 0, 1
    for i, c in enumerate(counts):
        mx_new = max(mx, int(c), 1)
        if i > start and (i + 1 - start) * mx_new > _BATCH_BUDGET:
            spans.append((start, i))
            start, mx = i, max(int(c), 1)
        else:
            mx = mx_new
    spans.append((start, len(counts)))
    return spans


def column_page_stats(values: np.ndarray, page_bounds: np.ndarray, **kw):
    """Ragged host entry: per-page stats for record-aligned page bounds.

    Used as the accelerated index-build path; equals what the writer computes
    per page on the host. One batched launch for the whole column (typical
    layouts): pages are edge-padded to the longest page — padding with a
    page's own last value changes neither its min nor its max — and empty
    pages patched to ``(+inf, -inf)`` afterwards. Heavily skewed page sizes
    split into a few budget-bounded launches instead of one dense matrix.
    """
    values = np.asarray(values, dtype=np.float32)
    bounds = np.asarray(page_bounds, dtype=np.int64)
    counts = np.diff(bounds)
    n_pages = len(counts)
    if n_pages == 0:
        return np.zeros(0), np.zeros(0)
    empty = counts == 0
    out_min = np.full(n_pages, np.inf)
    out_max = np.full(n_pages, -np.inf)
    if len(values) == 0 or empty.all():
        return out_min, out_max
    for lo, hi in _batch_spans(counts):
        # the batch shape is bucketed — rows to a power of two, columns to
        # the kernel tile — so a writer's row groups share a few compiled
        # shapes; padding rows repeat the first page and are dropped
        rows = np.arange(lo, lo + (1 << (hi - lo - 1).bit_length()))
        rows[rows >= hi] = lo
        c = counts[rows]
        max_len = -(-max(int(c.max()), 1) // _TILE) * _TILE
        # int32 positions + in-place clip keep the gather-index temporaries
        # within a small constant factor of the float32 batch itself
        pos = np.minimum(np.arange(max_len, dtype=np.int32)[None, :],
                         np.maximum(c - 1, 0).astype(np.int32)[:, None])
        idx = bounds[rows, None] + pos
        np.minimum(idx, len(values) - 1, out=idx)
        batch = values[idx]
        mn, mx = page_minmax(jnp.asarray(batch), **kw)
        out_min[lo:hi] = np.asarray(mn)[: hi - lo]
        out_max[lo:hi] = np.asarray(mx)[: hi - lo]
    out_min[empty] = np.inf
    out_max[empty] = -np.inf
    return out_min, out_max


def column_page_stats_ex(values: np.ndarray, page_bounds: np.ndarray, **kw):
    """NaN-aware per-page stats for any numeric dtype: (vmin, vmax, nnan).

    ``vmin``/``vmax`` are the per-page extrema over *non-NaN* values in the
    column's own dtype (``(+inf, -inf)`` for pages with none — empty or
    all-NaN), ``nnan`` the per-page NaN count. float32 columns reduce
    through the batched :func:`page_minmax` launch (the cast in
    :func:`column_page_stats` is exact for them); wider/integer dtypes use
    an exact host segmented reduction, since a float32 round-trip could
    move a bound across a value and make pruning unsound.
    """
    values = np.asarray(values)
    bounds = np.asarray(page_bounds, dtype=np.int64)
    counts = np.diff(bounds)
    n_pages = len(counts)
    if n_pages == 0:
        return np.zeros(0), np.zeros(0), np.zeros(0, np.int64)
    if values.dtype.kind == "f" and np.isnan(values).any():
        csum = np.concatenate([[0], np.cumsum(np.isnan(values), dtype=np.int64)])
        nnan = csum[bounds[1:]] - csum[bounds[:-1]]
    else:
        nnan = np.zeros(n_pages, np.int64)
    out_min = np.full(n_pages, np.inf)
    out_max = np.full(n_pages, -np.inf)
    if values.dtype == np.float32:
        mn, mx = column_page_stats(values, bounds, **kw)
        out_min, out_max = np.asarray(mn), np.asarray(mx)
        # jnp.min propagates NaN; recompute NaN-carrying pages exactly
        for i in np.flatnonzero((nnan > 0) & (nnan < counts)):
            v = values[bounds[i]:bounds[i + 1]]
            out_min[i], out_max[i] = np.fmin.reduce(v), np.fmax.reduce(v)
        all_nan = nnan == counts
        out_min[all_nan], out_max[all_nan] = np.inf, -np.inf
        return out_min, out_max, nnan
    nonempty = np.flatnonzero(counts > 0)
    if len(nonempty):
        # reduceat over non-empty page starts: skipped empty pages contribute
        # zero elements, so each segment reduces exactly one page; fmin/fmax
        # skip NaNs (all-NaN segments yield NaN, patched below)
        starts = bounds[:-1][nonempty]
        mn = np.fmin.reduceat(values, starts)
        mx = np.fmax.reduceat(values, starts)
        out_min[nonempty] = mn
        out_max[nonempty] = mx
        all_nan = nnan == counts
        out_min[all_nan], out_max[all_nan] = np.inf, -np.inf
    return out_min, out_max, nnan


def segment_minmax(key_lo, key_hi, flag, *, use_pallas: bool = True,
                   interpret: bool | None = None):
    """Segmented running min/max over order-key limbs.

    Inputs shaped ``(n_blocks, SEG_BLOCK)`` int32 (flags: 1 at segment
    starts; padding tail must be flagged). Returns four flattened uint32
    arrays ``(min_lo, min_hi, max_lo, max_hi)``; the value at a segment's
    last position is the segment's reduction. jit-safe (used inside the
    fused decode→refine launch chain).
    """
    if not use_pallas:
        return ref.segment_minmax_ref(key_lo, key_hi, flag)
    interp = default_interpret() if interpret is None else interpret
    return kernel.segminmax_blocks(key_lo, key_hi, flag, interpret=interp)
