"""Segmented scans over one (8, 128) VPU tile, in the tile's own layout.

Both segmented-scan kernels (the page-stream decode in
``repro.kernels.fp_delta`` and the per-record min/max in
``repro.kernels.minmax``) scan 1024 values held as one row-major
``(8, 128)`` tile. The flat oracles in their ``ref.py`` shift a 1024-vector
by concatenating slices; Mosaic refuses such a concatenate at offsets that
are not tile-aligned. :func:`tile_scan` performs exactly the same
Hillis–Steele steps (same shifts, same identity padding, same combine), so
its result is bit-identical to the flat scan, but each flat shift is built
from lane and sublane rotations of the tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def _shift_flat(x, shift: int, ident, flat, lane):
    """``x`` moved ``shift`` positions later in row-major order, with the
    first ``shift`` positions set to ``ident``."""
    # flags move as 32-bit lanes: Mosaic rotates and selects no i1 vectors
    is_bool = x.dtype == jnp.bool_
    v = x.astype(jnp.int32) if is_bool else x
    rows, lanes = v.shape
    if shift < lanes:
        r = pltpu.roll(v, shift, 1)  # r[i, j] = v[i, (j - shift) % lanes]
        # lanes j < shift take their value from the row above
        y = jnp.where(lane >= shift, r, pltpu.roll(r, 1, 0))
    else:
        y = pltpu.roll(v, shift // lanes, 0)
    y = jnp.where(flat >= shift, y, jnp.asarray(ident, v.dtype))
    return y != 0 if is_bool else y


def tile_scan(combine, state: tuple, identity: tuple) -> tuple:
    """Inclusive segmented scan of a row-major ``(rows, 128)`` tile.

    ``combine(earlier, later)`` is the scan's associative operator over
    tuples of equally shaped arrays, ``identity`` its per-component
    identity (Python scalars). Equals the flat log-step scan of the same
    combine over ``x.reshape(-1)`` element for element.
    """
    rows, lanes = state[0].shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    flat = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes + lane
    shift = 1
    while shift < rows * lanes:
        prev = tuple(_shift_flat(x, shift, i, flat, lane)
                     for x, i in zip(state, identity))
        state = combine(prev, state)
        shift *= 2
    return state
