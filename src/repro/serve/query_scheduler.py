"""Multi-tenant bbox query server over a Spatial Parquet dataset.

Continuous-batching-lite for spatial scans: concurrent bbox queries are
admitted in waves, their surviving ``(shard, row group)`` sets are unioned,
and each surviving row group is decoded **once** per wave — the multi-query
refinement then runs as a single launch with the queries' order-key bounds
stacked along a bbox axis (`decode_refine_stream_multi`). This is the
corrected form of the LM scheduler's admission pattern
(:mod:`repro.serve.scheduler`): shared state touched by a new wave must be
written *per slot*, never whole-batch — here the shared state is the decoded
row-group cache, and a wave only ever adds entries keyed by
``(shard, row group, generation)``; in-flight results of earlier queries are
never rewritten.

Caching and identity
--------------------

Pages are record-aligned, so a record decoded from the *full* row group is
bit-identical to the same record decoded through any bbox-pruned page run.
That makes the whole row group the natural cache unit:
:meth:`~repro.core.reader.SpatialParquetReader.read_row_group` decodes every
page once, and each query gathers only its own hit-run record ranges out of
the shared decode. In device mode the cache keeps the decoded stream limbs
and the per-record min/max **order-key stack** on the accelerator; a cache
hit re-tests new bboxes with a compare-only launch
(`refine_minmax_multi`) — no decode, no scan. Hit and miss paths share the
exact compare of the solo fused scan, so every query's survivor set (and
therefore its results) is bit-identical to a sequential
``scanner.scan(bbox, refine=True)``.

Attribution and telemetry
-------------------------

Each query carries its own :class:`~repro.core.reader.ReadStats`, computed
from index metadata to equal what its *unshared* solo scan would have
reported (pages/bytes pruned and read, records scanned/returned) — sharing
the decode changes the cost, not the attribution. Every query runs under an
``obs.span("serve.query")`` and folds its end-to-end latency into the
``serve.query_latency_s`` histogram; :meth:`SpatialQueryServer.metrics`
reports p50/p99 from that histogram plus cache hit/evict counters and the
shared-decode ratio (row-group touches per actual decode).
"""

from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.core.columnar import GeometryColumns
from repro.core.reader import (
    ReadStats,
    RowGroupData,
    compact_records,
    extra_columns,
)

__all__ = ["SpatialQuery", "SpatialQueryServer"]


@dataclass
class SpatialQuery:
    """One submitted bbox query and, after :meth:`SpatialQueryServer.run`,
    its results: the same ``(geo, extras, stats)`` triple a solo
    ``scanner.scan(bbox, refine=True)`` returns, plus timing."""

    qid: int
    bbox: tuple | None
    columns: tuple | None = None
    # attribute predicate (repro.core.filters.Predicate); evaluated against
    # the shared row-group decodes so results equal a solo
    # ``scanner.scan(bbox, refine=True, filter=...)``
    filter: object | None = None
    geo: GeometryColumns | None = None
    extras: dict = field(default_factory=dict)
    stats: ReadStats | None = None
    done: bool = False
    t_submit: float = 0.0  # perf_counter timestamps (monotonic)
    t_done: float = 0.0

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclass
class _HostChunkState:
    """Per-launch-chunk cache state, host compare flavor: decoded values
    plus the NaN-propagating per-record bbox statistics (float64; zero-count
    records hold NaN so every compare drops them, matching
    ``_bbox_keep_mask``)."""

    rec_lo: int
    rec_hi: int
    x: np.ndarray
    y: np.ndarray
    starts: np.ndarray  # chunk-local value start per record
    counts: np.ndarray
    xmin: np.ndarray
    xmax: np.ndarray
    ymin: np.ndarray
    ymax: np.ndarray

    def keep(self, bbox) -> np.ndarray:
        qx0, qy0, qx1, qy1 = bbox
        with np.errstate(invalid="ignore"):
            return ((self.xmin <= qx1) & (self.xmax >= qx0)
                    & (self.ymin <= qy1) & (self.ymax >= qy0))

    def gather(self, sub: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        from repro.kernels.fp_delta import ragged_ranges

        iv = ragged_ranges(self.starts[sub], self.counts[sub])
        return self.x[iv], self.y[iv]


@dataclass
class _DevChunkState:
    """Device flavor: decoded stream limbs + the (8, n_rec_pad) min/max
    order-key stack stay on the accelerator; ``aux`` keeps the record
    segmentation for survivor gathers."""

    rec_lo: int
    rec_hi: int
    lo: object
    hi: object
    minmax: object
    aux: object
    width: int

    def keep_multi(self, qkeys, qvalid) -> np.ndarray:
        from repro.kernels.fp_delta import refine_minmax_multi

        return refine_minmax_multi(
            self.minmax, self.aux.valid, qkeys, qvalid,
            width=self.width, n_records=self.rec_hi - self.rec_lo)

    def gather(self, sub: np.ndarray, dtype) -> tuple[np.ndarray, np.ndarray]:
        from repro.kernels.fp_delta import gather_stream_values, ragged_ranges

        xs = np.asarray(self.aux.x_start)
        ys = np.asarray(self.aux.y_start)
        cs = np.asarray(self.aux.counts)
        ix = ragged_ranges(xs[sub], cs[sub])
        iy = ragged_ranges(ys[sub], cs[sub])
        return (gather_stream_values(self.lo, self.hi, ix, self.width, dtype),
                gather_stream_values(self.lo, self.hi, iy, self.width, dtype))


def _host_chunk_stats(rec_lo, rec_hi, x, y, vcounts) -> _HostChunkState:
    counts = np.asarray(vcounts, np.int64)
    starts = np.cumsum(counts) - counts
    n = len(counts)
    mins = np.full((4, n), np.nan)
    nz = counts > 0
    if nz.any():
        s = starts[nz]
        xs = x.astype(np.float64, copy=False)
        ys = y.astype(np.float64, copy=False)
        mins[0, nz] = np.minimum.reduceat(xs, s)
        mins[1, nz] = np.maximum.reduceat(xs, s)
        mins[2, nz] = np.minimum.reduceat(ys, s)
        mins[3, nz] = np.maximum.reduceat(ys, s)
    return _HostChunkState(rec_lo, rec_hi, x, y, starts, counts,
                           mins[0], mins[1], mins[2], mins[3])


@dataclass
class _CacheEntry:
    data: RowGroupData
    chunks: list  # _HostChunkState | _DevChunkState, record order


class _RowGroupCache:
    """LRU over decoded row groups, keyed ``(shard, rg, generation)``.

    The generation is bumped by :meth:`SpatialQueryServer.invalidate` (e.g.
    after the dataset is rewritten in place); stale-generation entries can
    never be returned because the key includes it, and they are dropped
    eagerly so device memory is released."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._d: OrderedDict[tuple, _CacheEntry] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key) -> _CacheEntry | None:
        e = self._d.get(key)
        if e is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return e

    def put(self, key, entry: _CacheEntry) -> None:
        self._d[key] = entry
        self._d.move_to_end(key)
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)
            self.evictions += 1

    def drop_all(self) -> None:
        self._d.clear()

    def __len__(self) -> int:
        return len(self._d)


class SpatialQueryServer:
    """Serve concurrent bbox queries over one dataset with shared decodes.

    ``device="jax"`` keeps decoded row groups accelerator-resident and runs
    multi-query refinement as one fused launch per row group (falls back to
    host compares for non-float coordinates, like the solo scan).
    ``cache_rgs`` bounds the decoded-row-group LRU; ``max_wave`` bounds how
    many pending queries join one admission wave. Queries always refine
    (results are exact, identical to ``scan(bbox, refine=True)``); a
    ``bbox=None`` query returns the full dataset.
    """

    def __init__(self, scanner, *, device: str = "cpu", cache_rgs: int = 32,
                 max_wave: int = 64):
        if device not in ("cpu", "jax"):
            raise ValueError(f"device must be 'cpu' or 'jax', got {device!r}")
        self.scanner = scanner
        self._device_requested = device
        self.coord_dtype = np.dtype(scanner.manifest.coord_dtype)
        # device refinement needs float order keys; exotic int coordinates
        # take the host compare path (same fallback as the solo fused scan)
        self.device = device if self.coord_dtype.kind == "f" else "cpu"
        self.width = self.coord_dtype.itemsize * 8
        self.cache = _RowGroupCache(cache_rgs)
        self.max_wave = int(max_wave)
        self.generation = 0
        # catalog-backed scanners: pin the generation the open readers point
        # at, so a background compaction's GC can never delete shard files
        # out from under them mid-wave
        self.data_generation = getattr(scanner, "generation", 0)
        catalog = getattr(scanner, "catalog", None)
        self._gen_pin = (catalog.pin(self.data_generation)
                         if catalog is not None else None)
        self.pending: deque[SpatialQuery] = deque()
        self._next_qid = 0
        self._readers: dict[int, object] = {}
        # shared-decode accounting: touches / decodes ≈ how many solo decodes
        # one shared decode replaced
        self.queries_total = 0
        self.waves = 0
        self.rg_touches = 0
        self.rg_decodes = 0

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        for r in self._readers.values():
            r.close()
        self._readers.clear()
        self.cache.drop_all()
        if self._gen_pin is not None:
            self._gen_pin.release()
            self._gen_pin = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def invalidate(self) -> None:
        """Invalidate every cached decode (dataset mutated in place)."""
        self.generation += 1
        self.cache.drop_all()

    def _sync_generation(self) -> bool:
        """Adopt a newer catalog generation before admitting a wave.

        Returns True when a commit (e.g. background compaction) moved the
        head since the last wave: open shard readers are closed, the decoded
        row-group cache is invalidated (its keys include the bumped
        ``generation``, so stale decodes are unreachable *and* dropped), the
        schema-derived state is re-derived, and the server's pin moves to
        the new generation so its files outlive the next GC.
        """
        refresh = getattr(self.scanner, "refresh", None)
        if refresh is None:
            return False
        gen = refresh()
        if gen == self.data_generation:
            return False
        for r in self._readers.values():
            r.close()
        self._readers.clear()
        self.invalidate()
        self.coord_dtype = np.dtype(self.scanner.manifest.coord_dtype)
        self.device = (self._device_requested
                       if self.coord_dtype.kind == "f" else "cpu")
        self.width = self.coord_dtype.itemsize * 8
        if self._gen_pin is not None:
            new_pin = self.scanner.catalog.pin(gen)
            self._gen_pin.release()
            self._gen_pin = new_pin
        obs.instant("serve.generation_bump", cat="serve",
                    old=self.data_generation, new=gen)
        self.data_generation = gen
        return True

    def _reader(self, shard_i: int):
        r = self._readers.get(shard_i)
        if r is None:
            r = self._readers[shard_i] = self.scanner.open_shard(shard_i)
        return r

    # ------------------------------------------------------------------ API
    def submit(self, bbox=None, columns=None, filter=None, *,
               exact: bool = False) -> SpatialQuery:
        """Queue one query; it answers by MBR, as ``scan(bbox, refine=True,
        filter=filter)``. ``exact=True`` is refused: exact geometry tests
        run through ``SpatialDatasetScanner.scan(..., exact=True)``."""
        if exact:
            raise ValueError(
                "SpatialQueryServer answers by MBR only; exact=True is not "
                "supported here: use SpatialDatasetScanner.scan(bbox, "
                "refine=True, exact=True)")
        if filter is not None:
            from repro.core.filters import validate_predicate

            validate_predicate(filter, self.scanner.extra_schema)
        q = SpatialQuery(self._next_qid, bbox, columns, filter,
                         t_submit=time.perf_counter())
        self._next_qid += 1
        self.pending.append(q)
        return q

    def run(self) -> list[SpatialQuery]:
        """Drain the pending queue in admission waves; returns the completed
        queries in submission order."""
        out = []
        while self.pending:
            self._sync_generation()
            wave = [self.pending.popleft()
                    for _ in range(min(self.max_wave, len(self.pending)))]
            self._run_wave(wave)
            out.extend(wave)
        return out

    def metrics(self) -> dict:
        m = {
            "queries": self.queries_total,
            "waves": self.waves,
            "rg_touches": self.rg_touches,
            "rg_decodes": self.rg_decodes,
            "shared_decode_ratio":
                self.rg_touches / max(1, self.rg_decodes),
            "cache_hits": self.cache.hits,
            "cache_misses": self.cache.misses,
            "cache_evictions": self.cache.evictions,
            "cache_entries": len(self.cache),
        }
        m.update({f"latency_{k}": v
                  for k, v in obs.percentiles("serve.query_latency_s").items()})
        return m

    # ------------------------------------------------------------ internals
    def _plan(self, q: SpatialQuery):
        """Shard pruning for one query, then each hit shard's own file plan
        (:meth:`~repro.core.reader.SpatialParquetReader.plan`, the solo
        scan's): its row groups' page runs and its ``ReadStats``, summed
        with the pruned shards' totals — the accounting of the query's solo
        ``scanner.scan``."""
        dindex = self.scanner.index
        hits = [int(i) for i in dindex.query(q.bbox, filter=q.filter)]
        hit_set = set(hits)
        stats = ReadStats(shards_total=len(dindex), shards_read=len(hits))
        for i, shard in enumerate(self.scanner.manifest.shards):
            if i not in hit_set:
                stats.pages_total += shard.n_pages
                stats.bytes_total += shard.data_bytes
        # the solo scan also fetches the predicate's columns (then trims
        # them from the output)
        want_extra, read_extra = extra_columns(self.scanner.extra_schema,
                                               q.columns, q.filter)
        plan = {}
        for shard_i in hits:
            _, rgs, shard_stats = self._reader(shard_i).plan(
                q.bbox, q.filter, read_extra)
            stats = stats + shard_stats
            plan.update(((shard_i, rgp.rg_i), rgp) for rgp in rgs)
        return hits, plan, want_extra, stats

    def _fill_entry(self, shard_i: int, rg_i: int, qkeys, qvalid):
        """Cache miss: decode the whole row group once. Device mode fuses
        the *current wave's* multi-query refinement into the decode launch
        and returns its keep matrix alongside the new entry."""
        r = self._reader(shard_i)
        self.rg_decodes += 1
        data = r.read_row_group(rg_i, device=self.device)
        chunks: list = []
        wave_keep: np.ndarray | None = None
        if self.device == "cpu":
            chunks.append(_host_chunk_stats(
                0, data.n_records, data.x, data.y, data.rec_vcounts))
        else:
            from repro.kernels.fp_delta import decode_refine_stream_multi

            wave_keep = np.zeros((len(qkeys), data.n_records), bool)
            for ch in data.chunks:
                vc = data.rec_vcounts[ch.rec_lo : ch.rec_hi]
                if ch.kind == "host":
                    chunks.append(_host_chunk_stats(
                        ch.rec_lo, ch.rec_hi, ch.x, ch.y, vc))
                    continue
                res = decode_refine_stream_multi(ch.stream, ch.aux,
                                                 qkeys, qvalid)
                chunks.append(_DevChunkState(
                    ch.rec_lo, ch.rec_hi, res.lo, res.hi, res.minmax,
                    ch.aux, self.width))
                wave_keep[:, ch.rec_lo : ch.rec_hi] = res.keep
        return _CacheEntry(data, chunks), wave_keep

    def _rg_keep(self, entry: _CacheEntry, bboxes, filters, qkeys, qvalid,
                 wave_keep) -> np.ndarray:
        """(Q, n_records) survivor matrix for this row group: the fused miss
        launch's matrix when fresh, else compare-only re-tests of the cached
        statistics. ``bbox=None`` rows keep everything; a query's attribute
        predicate then ANDs its exact record mask into its row (masks are
        memoized per predicate key, so same-predicate queries in a wave
        evaluate it once per row group)."""
        n_rec = entry.data.n_records
        keep = np.zeros((len(bboxes), n_rec), bool)
        dev_done = wave_keep is not None
        dev_keep = wave_keep
        if not dev_done and any(isinstance(c, _DevChunkState)
                                for c in entry.chunks):
            dev_keep = np.zeros((len(bboxes), n_rec), bool)
            for c in entry.chunks:
                if isinstance(c, _DevChunkState):
                    dev_keep[:, c.rec_lo : c.rec_hi] = c.keep_multi(
                        qkeys, qvalid)
        for c in entry.chunks:
            if isinstance(c, _DevChunkState):
                keep[:, c.rec_lo : c.rec_hi] = dev_keep[:, c.rec_lo : c.rec_hi]
            else:
                for qi, bbox in enumerate(bboxes):
                    if bbox is not None:
                        keep[qi, c.rec_lo : c.rec_hi] = c.keep(bbox)
        for qi, bbox in enumerate(bboxes):
            if bbox is None:
                keep[qi, :] = True
        masks: dict[tuple, np.ndarray] = {}
        for qi, pred in enumerate(filters):
            if pred is None:
                continue
            attr = masks.get(pred.key)
            if attr is None:
                attr = masks[pred.key] = pred.mask(
                    {k: entry.data.extras[k] for k in pred.columns()})
            keep[qi, :] &= attr
        return keep

    def _run_wave(self, wave: list[SpatialQuery]) -> None:
        from repro.kernels.fp_delta import ragged_ranges
        from repro.kernels.minmax import stack_bbox_query_keys

        self.waves += 1
        self.queries_total += len(wave)
        with obs.span("serve.wave", cat="serve", queries=len(wave)):
            plans = [self._plan(q) for q in wave]
            # (Q, 4, 2) stacked order-key bounds for the whole wave; a
            # bbox=None query gets an invalid (all-False) row that _rg_keep
            # rewrites to all-True — it must not be fenced in key space
            qkeys, qvalid = stack_bbox_query_keys(
                [q.bbox if q.bbox is not None else (np.nan,) * 4
                 for q in wave], self.coord_dtype)
            bboxes = [q.bbox for q in wave]
            filters = [q.filter for q in wave]

            acc = [_QueryAccum(want_extra) for _, _, want_extra, _ in plans]
            union = sorted({key for _, plan, _, _ in plans for key in plan})
            for shard_i, rg_i in union:
                touching = [qi for qi, (_, plan, _, _) in enumerate(plans)
                            if (shard_i, rg_i) in plan]
                self.rg_touches += len(touching)
                key = (shard_i, rg_i, self.generation)
                entry = self.cache.get(key)
                wave_keep = None
                if entry is None:
                    entry, wave_keep = self._fill_entry(
                        shard_i, rg_i, qkeys, qvalid)
                    self.cache.put(key, entry)
                keep = self._rg_keep(entry, bboxes, filters, qkeys, qvalid,
                                     wave_keep)
                for qi in touching:
                    rgp = plans[qi][1][(shard_i, rg_i)]
                    a = acc[qi]
                    rec_parts = []
                    for r0, r1 in rgp.rec_runs:
                        entry.data.levels.append_run(a.level_parts, r0, r1)
                        a.keep_parts.append(keep[qi, r0:r1])
                        for k in a.want_extra:
                            a.extra_parts[k].append(
                                entry.data.extras[k][r0:r1])
                        rec_parts.append(np.arange(r0, r1))
                    recs = (np.concatenate(rec_parts) if rec_parts
                            else np.zeros(0, np.int64))
                    kept = recs[keep[qi, recs]]
                    for c in entry.chunks:
                        sub = kept[(kept >= c.rec_lo) & (kept < c.rec_hi)] \
                            - c.rec_lo
                        if isinstance(c, _DevChunkState):
                            xv, yv = c.gather(sub, self.coord_dtype)
                        else:
                            xv, yv = c.gather(sub)
                        a.x_parts.append(xv)
                        a.y_parts.append(yv)

            for q, (hits, _, want_extra, stats), a in zip(wave, plans, acc):
                self._finalize(q, hits, want_extra, stats, a)

    def _finalize(self, q: SpatialQuery, hits, want_extra,
                  stats: ReadStats, a: "_QueryAccum") -> None:
        """Assemble one query's result with the reader's own compaction
        (:func:`~repro.core.reader.compact_records`)."""
        with obs.span("serve.query", cat="serve", qid=q.qid,
                      shards=len(hits)) as sp:
            self._finalize_inner(q, hits, want_extra, stats, a)
            sp.add(records=stats.records_returned)
        obs.observe("serve.query_latency_s", q.latency_s)

    def _finalize_inner(self, q: SpatialQuery, hits, want_extra,
                        stats: ReadStats, a: "_QueryAccum") -> None:
        keep = None
        if q.bbox is not None or q.filter is not None:
            keep = (np.concatenate(a.keep_parts) if a.keep_parts
                    else np.zeros(0, bool))
        extras = {
            k: (np.concatenate(a.extra_parts[k]) if a.extra_parts[k]
                else np.zeros(0, np.dtype(self.scanner.extra_schema[k])))
            for k in (want_extra if hits else ())
        }
        q.geo, q.extras = compact_records(a.level_parts, keep, a.x_parts,
                                          a.y_parts, extras, self.coord_dtype)
        stats.records_returned = q.geo.n_records if q.geo is not None else (
            len(next(iter(q.extras.values()))) if q.extras else 0)
        q.stats = stats
        q.done = True
        q.t_done = time.perf_counter()


class _QueryAccum:
    """Per-query result parts, appended in the query's own scan order."""

    def __init__(self, want_extra):
        self.want_extra = want_extra
        self.level_parts = ([], [], [], [])
        self.keep_parts: list[np.ndarray] = []
        self.x_parts: list[np.ndarray] = []
        self.y_parts: list[np.ndarray] = []
        self.extra_parts = {k: [] for k in want_extra}
