"""The device stage of exact box-intersects scans, chained after the fused
bbox refine (``read_columnar(device="jax", refine=True, exact=True)``).

:func:`exact_narrow` takes the refine's device-resident stream limbs and
its record mask. For every record the mask keeps it gathers the record's
coordinates on the device and classifies the record with order-key
compares alone (:func:`repro.core.exact.classify` in key space, bit for
bit): *in*, *out*, or *undecided*. One byte a record crosses back. Only
the undecided records' coordinates then move to the host, where
:func:`repro.core.exact.resolve` settles them with exact orientation signs.
The narrowed mask feeds the reader's survivor gathers, so records the
stage drops never reach the host.

The jitted stage is ``exact_fn`` (trace module ``jit_exact_fn``), apart
from the refine chain's ``jit_fn``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import exact as host_exact

from .fp_delta.ops import _aot, _pow2_bucket, gather_stream_values, ragged_ranges
from .minmax import bbox_query_keys, float_order_key_np, float_order_keys
from .minmax import inf_keys, lex_gt
from .minmax.ref import _canonical_bound

# the gathered values (and the records, which are fewer) pad to a power of
# two of at least this many, so a scan's launches share a few shapes
_MIN_VALUES = 1024


def stage_keys(bbox, dtype) -> np.ndarray | None:
    """(5, 2) uint32 key limbs of the stage's bounds: the refine's ``x0,
    x1, y0, y1`` (:func:`~repro.kernels.minmax.bbox_query_keys`) and ``y0``
    canonicalized from above, for the ray's ``y > y0`` test. None when the
    box is empty."""
    keys = bbox_query_keys(bbox, dtype)
    if keys is None:
        return None
    y0_hi = float_order_key_np(_canonical_bound(bbox[1], dtype, "hi"), dtype)
    return np.concatenate([keys, np.array([y0_hi], np.uint32)])


@functools.lru_cache(maxsize=None)
def _exact_jit(width: int):
    """Jitted stage: gather the kept records' coordinates, compare their
    order keys with the box, classify each record. Returns one int8 a
    record: its class, plus 4 where a vertex lies in the box."""
    (neg_lo, neg_hi), (pos_lo, pos_hi) = inf_keys(width)

    def exact_fn(lo, hi, ix, iy, nxt, poly, rstart, rend, qkeys):
        q = qkeys.astype(jnp.uint32)
        kneg = (jnp.uint32(neg_lo), jnp.uint32(neg_hi))
        kpos = (jnp.uint32(pos_lo), jnp.uint32(pos_hi))

        def keys(i):
            return float_order_keys(jnp.take(lo, i, mode="clip"),
                                    jnp.take(hi, i, mode="clip"), width)

        def below(k, r):
            return lex_gt(q[r, 0], q[r, 1], *k)

        def over(k, r):
            return lex_gt(*k, q[r, 0], q[r, 1])

        def at(a):  # the value at each edge's other end
            return jnp.take(a, nxt, mode="clip")

        xk, yk = keys(ix), keys(iy)
        xl, xr, yb, ya = below(xk, 0), over(xk, 1), below(yk, 2), over(yk, 3)
        bad = ~(lex_gt(*xk, *kneg) & lex_gt(*kpos, *xk)
                & lex_gt(*yk, *kneg) & lex_gt(*kpos, *yk))
        inside = ~(xl | xr | yb | ya) & ~bad
        miss = (xl & at(xl)) | (xr & at(xr)) | (yb & at(yb)) | (ya & at(ya))
        xin, yin = ~(xl | xr), ~(yb | ya)
        cross = ~miss & ((yin & at(yin)) | (xin & at(xin)))
        und = ~miss & ~cross
        above = over(yk, 4)
        ray = poly & (above != at(above)) & xr & at(xr)

        def per_record(f):
            f = f.astype(jnp.int32)
            cs = jnp.cumsum(f)
            return (jnp.take(cs, rend, mode="clip")
                    - jnp.take(cs, rstart, mode="clip")
                    + jnp.take(f, rstart, mode="clip"))

        n_bad = per_record(bad)
        n_vertex = per_record(inside)
        cls = jnp.where(
            n_bad > 0, host_exact.OUT,
            jnp.where(n_vertex + per_record(cross) > 0, host_exact.IN,
                      jnp.where(per_record(und) > 0, host_exact.UNDECIDED,
                                jnp.where(per_record(ray) % 2 == 1,
                                          host_exact.IN, host_exact.OUT))))
        vertex = (n_vertex > 0) & (n_bad == 0)
        return (cls + 4 * vertex.astype(jnp.int32)).astype(jnp.int8)

    return jax.jit(exact_fn)


def classify_device(lo, hi, ix, iy, off, poly, counts, bbox, width: int):
    """The stage's launch over records laid out one after another in the
    gathered order: ``ix``/``iy`` are their values' stream positions,
    ``off``/``poly`` their edges (:func:`repro.core.exact.edge_partners`),
    ``counts`` their values. Returns ``(cls, vertex)`` as
    :func:`repro.core.exact.classify` does."""
    counts = np.asarray(counts, np.int64)
    n_rec, n_val = len(counts), len(ix)
    dtype = np.float32 if width == 32 else np.float64
    qkeys = stage_keys(bbox, dtype)
    if qkeys is None or n_rec == 0:
        return np.zeros(n_rec, np.int8), np.zeros(n_rec, bool)
    size = _pow2_bucket(n_val, _MIN_VALUES)
    pos = np.arange(size, dtype=np.int32)
    ix_p = np.zeros(size, np.int32)
    iy_p = np.zeros(size, np.int32)
    ix_p[:n_val], iy_p[:n_val] = ix, iy
    nxt = pos.copy()
    nxt[:n_val] += np.asarray(off, np.int32)
    poly_p = np.zeros(size, bool)
    poly_p[:n_val] = poly
    rstart = np.zeros(size, np.int32)
    rend = np.zeros(size, np.int32)
    rstart[:n_rec] = np.cumsum(counts) - counts
    rend[:n_rec] = rstart[:n_rec] + counts - 1
    args = (lo, hi, ix_p, iy_p, nxt, poly_p, rstart, rend, qkeys)
    key = ("exact", int(lo.shape[0]), size, width)
    fn = _aot(key, _exact_jit(width), args)
    with obs.span("device.exact_launch", cat="device", values=n_val,
                  records=n_rec, width=width):
        code = fn(*args)
        with obs.span("device.wait", cat="device"):
            code = np.asarray(code)[:n_rec]
    return code & 3, code >= 4


def exact_narrow(lo, hi, aux, keep: np.ndarray, bbox, width: int,
                 dtype) -> np.ndarray:
    """Narrow the refine's record mask ``keep`` (MBR ∧ predicate) to the
    records whose geometry meets ``bbox`` exactly. ``lo``/``hi`` are the
    decoded stream limbs, ``aux`` the stream's
    :class:`~repro.kernels.fp_delta.RefineAux`, built with the records'
    edges."""
    kept = np.flatnonzero(keep)
    if len(kept) == 0:
        return keep
    counts = aux.counts[kept]
    ix = ragged_ranges(aux.x_start[kept], counts)
    iy = ragged_ranges(aux.y_start[kept], counts)
    iv = ragged_ranges((np.cumsum(aux.counts) - aux.counts)[kept], counts)
    off, poly = aux.edge_off[iv], aux.edge_poly[iv]
    cls, vertex = classify_device(lo, hi, ix, iy, off, poly, counts, bbox,
                                  width)
    out = keep.copy()
    out[kept] = cls == host_exact.IN
    und = np.flatnonzero(cls == host_exact.UNDECIDED)
    n_host = 0
    with obs.span("rg.exact", cat="refine", records=len(und)):
        if len(und):
            iu = ragged_ranges((np.cumsum(counts) - counts)[und], counts[und])
            xs = gather_stream_values(lo, hi, ix[iu], width, dtype)
            ys = gather_stream_values(lo, hi, iy[iu], width, dtype)
            out[kept[und]], n_host = host_exact.resolve(
                xs, ys, counts[und], off[iu], poly[iu], bbox)
    host_exact.count(len(kept), int(vertex.sum()), int(out[kept].sum()),
                     int(np.count_nonzero(off)), n_host)
    return out
