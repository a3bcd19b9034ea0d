"""Spatial Parquet file reader: projection, range-filter pushdown, pruning.

The reader exposes two access paths:

* ``read(...)`` — the object API returning :class:`Geometry` lists (paper's
  reported read path), and
* ``read_columnar(...)`` — direct access to the decoded coordinate arrays.
  The paper (§5.1) names exactly this as the fix for its read-speed gap
  ("providing a lower-level access to the coordinate arrays from Parquet
  rather than reading one value at a time"); it is our primary fast path and
  what the training data pipeline consumes.

One planner, two executors
--------------------------

Every read goes the same three steps; only the last one differs by device.

1. **Plan** (:meth:`SpatialParquetReader.plan`, metadata only): the page
   index prunes by ``bbox`` and the filter's zone statistics, hit pages
   group into runs per row group (``SpatialIndex.page_runs``), and the
   ``ReadStats`` fields that metadata decides — pages and bytes in total
   and read, records scanned — are counted once. The query server plans
   each of its shards with the same method.
2. **Prepare** each hit row group on the host: its level streams
   (``rg.levels``), then under ``rg.plan`` each page run's coordinate pages
   checksum-verified (``rg.crc``) with the record range of every page, and
   the extra columns (``rg.extras``: every page's checksum, then one batched
   decode a column). No page of a row group decodes before every checksum
   of the row group has been verified.
3. **Execute**, per row group:

   * **host** (``device="cpu"``, the default and the oracle): the x pages
     and the y pages decode in one batch an axis
     (:func:`~repro.core.pages.decode_pages`, ``rg.decode``); then one keep
     function: the per-record bbox test ∧ the attribute mask, then the
     exact test (:mod:`repro.core.exact`) over what both keep.
   * **device** (``device="jax"``): each page run's pages are planned into
     stream plans (``rg.stream_plan``; raw pages through a synthetic
     raw-mode plan, see ``pages.page_stream_plans``), page pairs are
     chunked under the launch cap (``chunk_plan_pairs``) and each chunk
     runs the launch chain (``rg.launch``): decode, and with ``refine``
     the segmented per-record min/max and bbox test
     (``decode_refine_stream``) with the attribute mask AND-ed in, then
     with ``exact`` the compare-only stage of :mod:`repro.kernels.exact`.
     Only the record mask and the surviving coordinates cross back
     (``rg.gather``), or nothing with ``keep_on_device=True``
     (:class:`~repro.core.columnar.DeviceCoords` columns). A page pair too
     large for any launch decodes on the host and goes through the host
     keep function. Integer coordinates decode on the device and refine
     through the host keep function. Results are **bit-identical** to the
     host executor.

Both executors compact the kept records the same way
(:func:`compact_records`). ``read_row_group`` runs the same preparation
over every page of one row group, for the query server's decode cache.

Coalesced I/O
-------------

Per row group the plan collects the ``(offset, nbytes)`` of every blob the
read needs — the four level streams, each run of consecutive hit x/y pages,
and the matching extra column pages — merges byte ranges whose gap is at
most ``coalesce_max_gap``, and issues exactly one ``readinto`` per merged
range (``rg.fetch``). Blobs are zero-copy ``memoryview`` slices of those
buffers. Row groups are **double-buffered** (``prefetch_row_groups``,
default 1): a reader thread fetches row group N+1 while the main thread
works on row group N; results are byte-identical to the sequential order
(``prefetch_row_groups=0`` disables the overlap; ``coalesce=False`` reads
one blob at a time and implies it).

Fault-tolerant storage boundary (``repro.io``)
----------------------------------------------

All I/O goes through a :class:`~repro.io.source.ByteRangeSource`. The
default :class:`~repro.io.source.LocalFileSource` reads the local file;
passing ``source=RemoteRangeSource(...)`` runs the identical read path
against an object-store-style backend with retries, deadlines and a
read-through block cache. Format-v2 files carry per-blob checksums which are
verified on every stored blob *before* it is decompressed, planned or
launched; a mismatch triggers one cache-bypassing re-fetch (which heals a
poisoned block cache) and raises an attributed
:class:`~repro.io.checksum.ChecksumError` only if the bytes are still wrong.
All recoveries are counted in :class:`ReadStats` (``retries``, ``timeouts``,
``checksum_failures``, ``cache_hits``/``cache_misses``).
"""

from __future__ import annotations

import struct
import time
from bisect import bisect_right
from collections import deque
from dataclasses import dataclass, field
from dataclasses import replace as dc_replace

import msgpack
import numpy as np

from repro import obs
from repro.io.checksum import ChecksumError, checksum_fn, crc32c
from repro.io.source import LocalFileSource

from .columnar import DeviceCoords, GeometryColumns, assemble, ragged_ranges
from .exact import edge_partners, narrow as exact_narrow
from .filters import Predicate, canonical_bbox, validate_predicate
from .fp_delta import fp_delta_execute
from .geometry import Geometry
from .index import SpatialIndex
from .pages import PageMeta, decode_pages, decompress, page_stream_plans
from .rle import decode_levels, rle_decode
from .writer import MAGIC, MAGIC_V2

_LEVEL_NAMES = ("type", "type_rep", "rep", "defn")


def footer_data_bytes(footer: dict) -> int:
    """Total stored bytes of every blob (levels, coord pages, extras)."""
    total = 0
    for rg in footer["row_groups"]:
        total += sum(rg[name]["nbytes"] for name in _LEVEL_NAMES)
        total += sum(p["nbytes"] for p in rg["x_pages"])
        total += sum(p["nbytes"] for p in rg["y_pages"])
        for ep in rg.get("extra", {}).values():
            total += sum(p["nbytes"] for p in ep)
    return total


def footer_page_count(footer: dict) -> int:
    """Number of x/y page pairs (the unit of the per-page spatial index)."""
    return sum(len(rg["x_pages"]) for rg in footer["row_groups"])


@dataclass
class ReadStats:
    """Pruning accounting for the light-weight index (paper Figure 11).

    ``bytes_read``/``bytes_total`` count every stored blob (level streams,
    coordinate pages, extra-column pages) — not just x/y pages — so pruning
    ratios reflect what actually hits the disk.

    Stats are *mergeable*: ``a + b`` (or ``a.merge(b)``, or ``sum(stats)``)
    field-wise sums two accounts, so a multi-shard dataset scan reports one
    aggregate. ``shards_total``/``shards_read`` stay 0 for single-file reads
    and are filled in by the dataset scanner, where pruned shards contribute
    their page/byte totals but nothing to the ``*_read`` side.

    Recovery accounting (the fault-tolerant I/O layer): ``retries`` counts
    re-issued range requests (backoff retries inside a
    :class:`~repro.io.remote.RemoteRangeSource` plus checksum-triggered blob
    re-fetches), ``timeouts`` the requests dropped for missing their
    deadline, ``checksum_failures`` every blob whose stored CRC mismatched
    (recovered or not), ``cache_hits``/``cache_misses`` the remote block
    cache, ``shard_retries`` scanner-level shard re-reads, and ``failures``
    the attributed record of shards a ``skip``-policy scan dropped (list of
    :class:`~repro.dataset.errors.ShardFailure`).
    """

    pages_total: int = 0
    pages_read: int = 0
    bytes_total: int = 0
    bytes_read: int = 0
    records_scanned: int = 0
    records_returned: int = 0
    shards_total: int = 0
    shards_read: int = 0
    retries: int = 0
    timeouts: int = 0
    checksum_failures: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    shard_retries: int = 0
    failures: list = field(default_factory=list)

    @property
    def pages_skipped(self) -> int:
        return self.pages_total - self.pages_read

    @property
    def shards_skipped(self) -> int:
        return self.shards_total - self.shards_read

    @property
    def shards_failed(self) -> int:
        return len(self.failures)

    def merge(self, other: "ReadStats") -> "ReadStats":
        """Field-wise sum of two accounts (one aggregate per dataset scan)."""
        return ReadStats(
            pages_total=self.pages_total + other.pages_total,
            pages_read=self.pages_read + other.pages_read,
            bytes_total=self.bytes_total + other.bytes_total,
            bytes_read=self.bytes_read + other.bytes_read,
            records_scanned=self.records_scanned + other.records_scanned,
            records_returned=self.records_returned + other.records_returned,
            shards_total=self.shards_total + other.shards_total,
            shards_read=self.shards_read + other.shards_read,
            retries=self.retries + other.retries,
            timeouts=self.timeouts + other.timeouts,
            checksum_failures=self.checksum_failures + other.checksum_failures,
            cache_hits=self.cache_hits + other.cache_hits,
            cache_misses=self.cache_misses + other.cache_misses,
            shard_retries=self.shard_retries + other.shard_retries,
            failures=self.failures + other.failures,
        )

    def __add__(self, other):
        if not isinstance(other, ReadStats):
            return NotImplemented
        return self.merge(other)

    def __radd__(self, other):
        if other == 0:  # support sum(list_of_stats)
            return self
        return NotImplemented


class _CoalescedRanges:
    """Merge (offset, nbytes) requests and serve blobs from batched reads.

    One ``readinto_at`` per merged run — for a :class:`LocalFileSource` that
    is the historical single ``seek``+``readinto`` syscall pair, verbatim.
    """

    def __init__(self, source, ranges: list[tuple[int, int]], max_gap: int):
        spans = sorted(set(r for r in ranges if r[1] > 0))
        merged: list[list[int]] = []
        for off, nb in spans:
            if merged and off <= merged[-1][1] + max_gap:
                merged[-1][1] = max(merged[-1][1], off + nb)
            else:
                merged.append([off, off + nb])
        self._source = source
        self._starts = [m[0] for m in merged]
        self._bufs: list[memoryview] = []
        self.n_reads = 0
        for start, end in merged:
            buf = bytearray(end - start)
            got = source.readinto_at(start, buf)
            if got != len(buf):
                raise IOError("short read (truncated Spatial Parquet file)")
            self.n_reads += 1
            self._bufs.append(memoryview(buf))

    def blob(self, offset: int, nbytes: int) -> memoryview:
        i = bisect_right(self._starts, offset) - 1
        rel = offset - self._starts[i]
        return self._bufs[i][rel : rel + nbytes]

    def refetch(self, offset: int, nbytes: int) -> bytes:
        """Re-read one blob straight from storage, bypassing (and healing)
        any cache layer — the checksum-mismatch recovery path."""
        return self._source.read_at(offset, nbytes, refresh=True)


class _DirectRanges:
    """One read per blob (legacy path; kept for equivalence testing)."""

    def __init__(self, source):
        self._source = source

    def blob(self, offset: int, nbytes: int) -> bytes:
        return self._source.read_at(offset, nbytes)

    def refetch(self, offset: int, nbytes: int) -> bytes:
        return self._source.read_at(offset, nbytes, refresh=True)


@dataclass
class _RowGroupLevels:
    """Decoded level streams of one row group + record start indices.

    Owns the record-range slicing shared by the row-group preparation of
    every read and by the query server, so no path can drift apart on level
    semantics (their bit-identity is part of the fused-refine contract).
    """

    types: np.ndarray
    type_rep: np.ndarray
    rep: np.ndarray
    defn: np.ndarray
    slot_starts: np.ndarray
    type_starts: np.ndarray

    @property
    def n_rec(self) -> int:
        return len(self.slot_starts)

    def append_run(self, parts, r0: int, r1: int) -> None:
        """Slice records ``[r0, r1)`` into the four level part lists; the
        first slot of a run always starts a record, so the rep/type_rep
        heads are (re)pinned to 0."""
        types_parts, type_rep_parts, rep_parts, defn_parts = parts
        n_rec = self.n_rec
        s0 = self.slot_starts[r0]
        s1 = self.slot_starts[r1] if r1 < n_rec else len(self.rep)
        t0 = self.type_starts[r0]
        t1 = self.type_starts[r1] if r1 < n_rec else len(self.types)
        types_parts.append(self.types[t0:t1])
        tr = self.type_rep[t0:t1].copy()
        rp = self.rep[s0:s1].copy()
        tr[0] = 0
        rp[0] = 0
        type_rep_parts.append(tr)
        rep_parts.append(rp)
        defn_parts.append(self.defn[s0:s1])

    def record_value_counts(self) -> np.ndarray:
        """Values per record across the whole row group (pages are
        record-aligned, so hit runs slice out of this contiguously)."""
        return np.add.reduceat(self.defn, self.slot_starts, dtype=np.int64)


@dataclass
class RowGroupChunk:
    """One launch-chunk of a fully decoded row group (``device="jax"``).

    ``kind == "dev"`` carries an *unlaunched* packed page stream plus its
    refine aux (record segmentation) — the serve tier fuses multi-query
    refinement into the launch. ``kind == "host"`` carries host-decoded x/y
    values of a page pair too large for any launch.
    ``rec_lo``/``rec_hi`` are the rg-local record range the chunk covers.
    """

    kind: str
    rec_lo: int
    rec_hi: int
    stream: object = None
    aux: object = None
    x: np.ndarray | None = None
    y: np.ndarray | None = None


@dataclass
class RowGroupData:
    """Every page of one row group, decoded once (see ``read_row_group``).

    ``extras`` holds the full extra-column arrays (length ``n_records``);
    ``nbytes`` is the stored bytes fetched to build this (levels + extras +
    x/y pages) — the cache-attribution unit. Exactly one of ``x``/``y``
    (``device="cpu"``) or ``chunks`` (``device="jax"``) is populated.
    """

    rg_i: int
    n_records: int
    rec_vcounts: np.ndarray
    levels: _RowGroupLevels
    extras: dict
    nbytes: int
    x: np.ndarray | None = None
    y: np.ndarray | None = None
    chunks: list[RowGroupChunk] | None = None


@dataclass
class _RowGroupPlan:
    """One hit row group of a file plan (metadata only): its page runs,
    the row-group records each run covers, the extra columns' page
    metadata, and the byte ranges and stored bytes its read fetches."""

    rg_i: int
    rg: dict
    base: int                       # index position of the group's page 0
    geometry: bool                  # levels and coordinate pages read
    runs: list[tuple[int, int]]     # hit page runs [p0, p1)
    rec_runs: list[tuple[int, int]]  # the records [r0, r1) of each run
    extra_pages: dict
    ranges: list[tuple[int, int]]
    nbytes: int

    @property
    def n_records(self) -> int:
        return sum(r1 - r0 for r0, r1 in self.rec_runs)


@dataclass
class _PreparedRowGroup:
    """A row group after its host preparation (see ``_prepare_rg``)."""

    levels: _RowGroupLevels | None
    pages: list            # (meta_x, blob_x, meta_y, blob_y), verified
    pairs: list            # each page's records, local to the read records
    rec_vcounts: np.ndarray  # values of each read record
    plans: list | None     # device executor: x, y stream plan of each page


class SpatialParquetReader:
    """Reader over one ``.spqf`` object.

    ``path`` opens a :class:`~repro.io.source.LocalFileSource`; pass
    ``source=`` instead (e.g. a :class:`~repro.io.remote.RemoteRangeSource`)
    to read the same bytes from elsewhere — the reader owns whichever source
    it ends up with and closes it. ``verify_checksums=False`` skips the v2
    integrity checks (v1 files carry none and are never verified).
    """

    def __init__(self, path=None, *, source=None, coalesce_max_gap: int = 1 << 16,
                 prefetch_row_groups: int = 1, verify_checksums: bool = True):
        if source is None:
            if path is None:
                raise ValueError("SpatialParquetReader needs a path or a source")
            source = LocalFileSource(path)
        self.path = str(path) if path is not None else getattr(
            source, "path", "<source>")
        self.coalesce_max_gap = int(coalesce_max_gap)
        self.prefetch_row_groups = max(0, int(prefetch_row_groups))
        self._source = source
        self._closed = False
        try:
            self.footer = self._read_footer()
            self.coord_dtype = np.dtype(self.footer["coord_dtype"])
            self.codec = self.footer["codec"]
            self.n_records = self.footer["n_records"]
            self.extra_schema = self.footer.get("extra_schema", {})
            self.checksum_algo = self.footer.get("checksum_algo")
            self._verify = bool(verify_checksums) and self.checksum_algo is not None
            self._blob_crc = checksum_fn(self.checksum_algo) if self._verify else None
            self.index = SpatialIndex(self.footer)
            self._data_bytes = footer_data_bytes(self.footer)
        except Exception:
            # never leak the handle/source when construction fails mid-way
            self.close()
            raise

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self):
        if not self._closed:
            self._closed = True
            self._source.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------- internals
    def _read_footer(self) -> dict:
        src = self._source
        size = src.size()
        if size < 2 * len(MAGIC) + 4:
            raise ValueError("truncated Spatial Parquet file (too short)")
        lead = src.read_at(0, len(MAGIC))
        if lead not in (MAGIC, MAGIC_V2):
            raise ValueError("not a Spatial Parquet file (bad leading magic)")
        tail = src.read_at(size - len(MAGIC) - 4, len(MAGIC) + 4)
        (flen,) = struct.unpack("<I", tail[:4])
        trail = tail[4:]
        if trail not in (MAGIC, MAGIC_V2):
            raise ValueError("truncated Spatial Parquet file (bad trailing magic)")
        if flen > size - 2 * len(MAGIC) - 4:
            raise ValueError("truncated Spatial Parquet file (bad footer length)")
        stored = src.read_at(size - len(MAGIC) - 4 - flen, flen)
        if trail == MAGIC_V2:
            # v2 trailer: [footer][crc32c(footer): u32]; verify before unpack
            # so a corrupt footer never feeds garbage to msgpack / the index
            blob, crc_bytes = stored[:-4], stored[-4:]
            (want,) = struct.unpack("<I", crc_bytes)
            got = crc32c(blob)
            if got != want:
                raise ChecksumError("file footer", size - len(MAGIC) - 4 - flen,
                                    len(blob), want, got)
        else:
            blob = stored
        return msgpack.unpackb(blob, raw=False, strict_map_key=False)

    def _checked_blob(self, src, offset: int, nbytes: int,
                      crc: int | None, stats: ReadStats, what: str):
        """Fetch one stored blob, verifying its v2 checksum when present.

        A mismatch triggers exactly one cache-bypassing re-fetch (healing a
        poisoned remote block cache); if the fresh bytes still mismatch, the
        blob is genuinely corrupt and an attributed ChecksumError raises
        *before* any decompress/decode/launch consumes it.
        """
        blob = src.blob(offset, nbytes)
        if not self._verify or crc is None:
            return blob
        got = self._blob_crc(blob)
        if got == crc:
            return blob
        stats.checksum_failures += 1
        obs.instant("checksum.refetch", cat="io", what=what, offset=offset)
        fresh = src.refetch(offset, nbytes)
        stats.retries += 1
        got = self._blob_crc(fresh)
        if got == crc and len(fresh) == nbytes:
            return fresh
        raise ChecksumError(what, offset, nbytes, crc, got)

    def _rg_plan(self, rg_i: int, runs, extra, geometry: bool) -> _RowGroupPlan:
        """The plan of one row group's page runs ``runs`` (metadata only):
        the records each run covers, the byte ranges of every blob the read
        needs and their stored bytes (levels and coordinate pages only
        with ``geometry``; the pages of the extra columns ``extra``)."""
        idx = self.index
        rg = self.footer["row_groups"][rg_i]
        base = int(np.searchsorted(idx.row_group, rg_i, side="left"))
        extra_pages = {k: rg["extra"][k] for k in extra}
        ranges: list[tuple[int, int]] = []
        rec_runs = []
        nbytes = 0
        if geometry:
            ranges += [(rg[n]["offset"], rg[n]["nbytes"]) for n in _LEVEL_NAMES]
            nbytes += sum(rg[n]["nbytes"] for n in _LEVEL_NAMES)
        for p0, p1 in runs:
            j0, j1 = base + p0, base + p1 - 1
            rec_runs.append((int(idx.rec_start[j0]),
                             int(idx.rec_start[j1] + idx.rec_count[j1])))
            if geometry:
                for off, nb in ((idx.x_offset, idx.x_nbytes),
                                (idx.y_offset, idx.y_nbytes)):
                    ranges.append((int(off[j0]),
                                   int(off[j1] + nb[j1] - off[j0])))
                    nbytes += int(nb[j0 : j1 + 1].sum())
            for ep in extra_pages.values():
                first, last = ep[p0], ep[p1 - 1]
                ranges.append((first["offset"],
                               last["offset"] + last["nbytes"] - first["offset"]))
                nbytes += sum(ep[p]["nbytes"] for p in range(p0, p1))
        return _RowGroupPlan(rg_i, rg, base, geometry, list(runs), rec_runs,
                             extra_pages, ranges, nbytes)

    def _level_blob(self, src, rg, name: str, stats: ReadStats):
        meta = rg[name]
        return self._checked_blob(src, meta["offset"], meta["nbytes"],
                                  meta.get("crc"), stats,
                                  f"{name!r} level stream")

    def _decode_rg_levels(self, src, rg, stats: ReadStats) -> _RowGroupLevels:
        """Decode one row group's four level streams (``rg.levels``)."""
        with obs.span("rg.levels", cat="decode"):
            types, type_rep, rep, defn = (
                (rle_decode if name == "type" else decode_levels)(
                    decompress(self._level_blob(src, rg, name, stats),
                               self.codec))
                for name in _LEVEL_NAMES)
            return _RowGroupLevels(types, type_rep, rep, defn,
                                   np.flatnonzero(rep == 0),
                                   np.flatnonzero(type_rep == 0))

    def _decode_extras(self, src, extra_pages, extra_all, we: int, runs,
                       stats: ReadStats) -> None:
        """Decode one row group's extra-column pages of the page runs
        ``runs`` into the preallocated columns from record cursor ``we``, a
        column at a time (one ``rg.extras`` span). Every page's checksum is
        verified first, a column's pages in one ``rg.crc`` child; then each
        column's pages decode in one batch (:func:`decode_pages`), counted
        in ``reader.extras_pages`` and ``reader.extras_batches``."""
        n_pages = sum(p1 - p0 for p0, p1 in runs)
        with obs.span("rg.extras", cat="decode", pages=n_pages):
            batches = []
            for k, ep in extra_pages.items():
                with obs.span("rg.crc", cat="plan", pages=n_pages):
                    pages = []
                    for p0, p1 in runs:
                        for p in range(p0, p1):
                            meta = PageMeta.from_dict(ep[p])
                            pages.append((self._checked_blob(
                                src, meta.offset, meta.nbytes, meta.crc, stats,
                                f"extra column {k!r} page {p}"), meta))
                batches.append((k, pages))
            for k, pages in batches:
                n = sum(meta.count for _, meta in pages)
                decode_pages(pages, np.dtype(self.extra_schema[k]), self.codec,
                             out=extra_all[k][we : we + n])
            obs.count("reader.extras_pages", n_pages * len(batches))
            obs.count("reader.extras_batches", len(batches))

    def _coord_pages(self, src, rg, rg_i: int, base: int, p0: int, p1: int,
                     stats: ReadStats) -> list[tuple]:
        """``(meta_x, blob_x, meta_y, blob_y)`` of pages ``[p0, p1)`` of one
        row group, every blob checksum-verified (one ``rg.crc`` span)."""
        idx = self.index
        xp, yp = rg["x_pages"], rg["y_pages"]
        out = []
        with obs.span("rg.crc", cat="plan", pages=p1 - p0):
            for p in range(p0, p1):
                j = base + p
                meta_x = PageMeta.from_dict(xp[p])
                meta_y = PageMeta.from_dict(yp[p])
                out.append((
                    meta_x,
                    self._checked_blob(
                        src, int(idx.x_offset[j]), int(idx.x_nbytes[j]),
                        meta_x.crc, stats, f"x page {p} of row group {rg_i}"),
                    meta_y,
                    self._checked_blob(
                        src, int(idx.y_offset[j]), int(idx.y_nbytes[j]),
                        meta_y.crc, stats, f"y page {p} of row group {rg_i}"),
                ))
        return out

    def _stream_plans(self, pages, plans: list) -> None:
        """Append the x and y stream plan of each verified page to
        ``plans`` (one ``rg.stream_plan`` span)."""
        with obs.span("rg.stream_plan", cat="plan", pages=len(pages)):
            plans += page_stream_plans(
                [pair for meta_x, blob_x, meta_y, blob_y in pages
                 for pair in ((blob_x, meta_x), (blob_y, meta_y))],
                self.coord_dtype, self.codec)

    def _prepare_rg(self, src, rgp: _RowGroupPlan, stats: ReadStats,
                    extra_all: dict, we: int, level_parts=None, *,
                    device: bool = False) -> _PreparedRowGroup:
        """Every host step of one row group before any coordinate decode,
        shared by both executors and ``read_row_group``.

        With geometry: the level streams (``rg.levels``), then under
        ``rg.plan`` each page run's coordinate pages, checksum-verified
        (``rg.crc``) and, for the device executor, planned into stream
        plans (``rg.stream_plan``), with the record range of each page; the
        read records' levels are appended to ``level_parts`` when given.
        Then the extra columns (``rg.extras``) decode into ``extra_all``
        from record cursor ``we``.

        Every checksum of the row group, coordinate and attribute pages
        alike, is verified before any of its pages decodes, on both
        executors: the attribute pages decode only after every coordinate
        page's checksum and their own, and the executors decode (or launch)
        coordinates only after this returns. Planning reads only verified
        blobs.
        """
        idx = self.index
        lv = self._decode_rg_levels(src, rgp.rg, stats) if rgp.geometry else None
        pages: list = []
        pairs: list[tuple[int, int]] = []
        vc_parts: list[np.ndarray] = []
        plans: list | None = [] if device else None
        with obs.span("rg.plan", cat="plan", rg=rgp.rg_i) as plan_span:
            if lv is not None:
                vcounts = lv.record_value_counts()
                local = 0
                for (p0, p1), (r0, r1) in zip(rgp.runs, rgp.rec_runs):
                    run = self._coord_pages(src, rgp.rg, rgp.rg_i, rgp.base,
                                            p0, p1, stats)
                    if device:
                        self._stream_plans(run, plans)
                    pages += run
                    j0, j1 = rgp.base + p0, rgp.base + p1
                    lo = idx.rec_start[j0:j1] - r0 + local
                    pairs += zip(lo.tolist(),
                                 (lo + idx.rec_count[j0:j1]).tolist())
                    vc_parts.append(vcounts[r0:r1])
                    if level_parts is not None:
                        lv.append_run(level_parts, r0, r1)
                    local += r1 - r0
            self._decode_extras(src, rgp.extra_pages, extra_all, we,
                                rgp.runs, stats)
            plan_span.add(pages=len(pairs))
        rec_vcounts = (np.concatenate(vc_parts) if vc_parts
                       else np.zeros(0, np.int64))
        return _PreparedRowGroup(lv, pages, pairs, rec_vcounts, plans)

    def _iter_sources(self, items, coalesce: bool):
        """Yield ``(item, src)`` per hit row group, double-buffering reads.

        With coalescing on and ``prefetch_row_groups >= 1``, a single worker
        thread runs row group N+1's ``readinto`` calls while the caller
        decodes row group N (file I/O releases the GIL; the main thread only
        touches prefilled buffers, never the source). Yields in file order,
        so results are byte-identical to the sequential path.

        The read loops close this generator in a ``finally`` (triggering
        ``GeneratorExit`` here), so the pool's ``with`` block always joins
        the prefetch thread — including when a decode raises mid-row-group.
        """
        if not coalesce:
            for it in items:
                yield it, _DirectRanges(self._source)
            return

        def fetch(it):
            # the "fetch" stage span: every readinto of one row group's
            # coalesced ranges (runs on the prefetch thread when enabled —
            # obs.submit hands the span context across)
            with obs.span("rg.fetch", cat="io", rg=it.rg_i):
                return _CoalescedRanges(self._source, it.ranges,
                                        self.coalesce_max_gap)

        lookahead = self.prefetch_row_groups
        if lookahead == 0 or len(items) <= 1:
            for it in items:
                yield it, fetch(it)
            return
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=1) as pool:
            pending: deque = deque()
            nxt = 0
            while nxt < len(items) and len(pending) < lookahead:
                pending.append(obs.submit(pool, fetch, items[nxt]))
                nxt += 1
            for it in items:
                src = pending.popleft().result()
                if nxt < len(items):
                    pending.append(obs.submit(pool, fetch, items[nxt]))
                    nxt += 1
                yield it, src

    # -------------------------------------------------------------- read API
    def plan(self, bbox=None, filter: Predicate | None = None, extra=(),
             geometry: bool = True):
        """The file plan of a read (metadata only): the pages the index
        keeps for ``bbox`` and ``filter``'s zone statistics, grouped into
        page runs per hit row group, and the :class:`ReadStats` fields that
        metadata decides — ``pages_total``, ``bytes_total``, ``pages_read``,
        ``records_scanned`` and ``bytes_read`` (levels and coordinate pages
        with ``geometry``; the pages of the extra columns ``extra``).

        Returns ``(hit, row_groups, stats)``: the hit page indices, a
        ``_RowGroupPlan`` per hit row group in file order, and the stats.
        Executors add only what they observe: ``records_returned``,
        checksum failures and retries, and the source's counters.
        """
        idx = self.index
        hit = idx.query(bbox, filter=filter)
        runs_by_rg: dict[int, list[tuple[int, int]]] = {}
        for rg_i, p0, p1 in idx.page_runs(bbox, hit=hit):
            runs_by_rg.setdefault(rg_i, []).append((p0, p1))
        rgs = [self._rg_plan(rg_i, runs, extra, geometry)
               for rg_i, runs in sorted(runs_by_rg.items())]
        stats = ReadStats(
            pages_total=len(idx), bytes_total=self._data_bytes,
            pages_read=sum(p1 - p0 for r in rgs for p0, p1 in r.runs),
            bytes_read=sum(r.nbytes for r in rgs),
            records_scanned=sum(r.n_records for r in rgs))
        return hit, rgs, stats

    def read_columnar(
        self,
        bbox=None,
        columns: tuple[str, ...] | None = None,
        refine: bool = False,
        coalesce: bool = True,
        device: str = "cpu",
        *,
        keep_on_device: bool = False,
        filter: Predicate | None = None,
        exact: bool = False,
    ) -> tuple[GeometryColumns | None, dict[str, np.ndarray], ReadStats]:
        """Decode records whose *page* bbox intersects ``bbox``.

        Returns (geometry columns, extra columns, stats). ``refine=True``
        additionally drops records whose exact bbox misses the query.
        ``columns`` restricts which extra columns decode ("geometry" is
        implied unless columns excludes it explicitly). ``filter`` is a
        :mod:`repro.core.filters` predicate over extra columns: pages whose
        zone statistics prove no match are skipped, and the surviving
        records are filtered *exactly* (the result is always identical to
        reading without zone pruning and masking afterwards — the record
        mask is ``bbox ∧ attrs`` when combined with ``refine``). Columns a
        filter needs are decoded as required but only returned when
        requested. ``coalesce=False`` disables batched range I/O (one read
        per blob; identical results).

        The read is planned once (:meth:`plan`), each hit row group is
        prepared on the host (levels, verified pages, extra columns), then
        one of two executors decodes and keeps records. ``device="cpu"``
        (the default and the oracle) decodes on the host, an axis of a row
        group in one batch, and refines there. ``device="jax"`` decodes the
        coordinate pages on the accelerator, one launch chain per row group
        under the launch cap, bit-identical to the host; with
        ``refine=True`` the per-record bbox test also runs on the device and
        only surviving records transfer back. ``keep_on_device=True``
        (requires ``device="jax"``) returns :class:`DeviceCoords` coordinate
        columns that never leave the accelerator; it is a no-op when
        ``columns`` excludes geometry (extra columns always decode on the
        host).

        ``exact=True`` (with ``refine=True``) answers by geometry instead of
        MBR: a record is returned iff its closed point set meets the closed
        box (polygons by the even–odd rule; see :mod:`repro.core.exact`).
        The MBR refine still runs first; on the device path the records it
        keeps go through the compare-only stage of
        :mod:`repro.kernels.exact`, and only the ones it cannot decide
        reach the host, for exact orientation signs.

        With telemetry on (``repro.obs.enable()``) the call is wrapped in a
        ``scan.file`` span with per-row-group fetch/plan/decode/launch/
        transfer child spans, and on return folds its ``ReadStats`` plus the
        derived gauges (``scan.latency_s``, ``scan.host_cpu_s_per_gb``,
        bytes-pruned-per-level) into the metrics registry. Disabled, the
        path is allocation- and result-identical to the uninstrumented one.
        """
        if exact and not refine:
            raise ValueError("exact=True requires refine=True")
        if not obs.enabled():
            return self._read_columnar_impl(
                bbox, columns, refine, coalesce, device,
                keep_on_device=keep_on_device, filter=filter, exact=exact)
        t0 = time.perf_counter()
        c0 = time.process_time()
        with obs.span("scan.file", path=self.path, device=device,
                      refine=bool(refine), filtered=filter is not None):
            out = self._read_columnar_impl(
                bbox, columns, refine, coalesce, device,
                keep_on_device=keep_on_device, filter=filter, exact=exact)
        wall = time.perf_counter() - t0
        cpu = time.process_time() - c0
        stats = out[2]
        obs.observe("scan.latency_s", wall)
        scanned_gb = stats.bytes_read / 1e9
        if scanned_gb > 0:
            # process-wide CPU per scanned GB: the GPU-layout-v2 ROADMAP
            # metric (how much host planning/decode a scan still costs)
            obs.gauge("scan.host_cpu_s_per_gb", cpu / scanned_gb)
        obs.count("pruned.page_bytes",
                  max(0, stats.bytes_total - stats.bytes_read))
        obs.fold_read_stats(stats)
        return out

    def _read_columnar_impl(self, bbox, columns, refine, coalesce, device,
                            *, keep_on_device, filter=None, exact=False):
        if device not in ("cpu", "jax"):
            raise ValueError(f"device must be 'cpu' or 'jax', got {device!r}")
        if exact and self.coord_dtype.kind != "f":
            raise ValueError("exact=True requires float coordinates")
        exact = exact and refine and bbox is not None
        if keep_on_device and device != "jax":
            raise ValueError("keep_on_device=True requires device='jax'")
        if filter is not None:
            validate_predicate(filter, self.extra_schema)
        want_geom = columns is None or "geometry" in columns
        want_extra, read_extra = extra_columns(self.extra_schema, columns,
                                               filter)
        on_device = device == "jax" and want_geom
        # the bbox a record's coordinates are refined against (None: none)
        refine_box = bbox if refine and bbox is not None and want_geom else None
        if (on_device and keep_on_device and refine_box is not None
                and self.coord_dtype.kind != "f"):
            raise ValueError("device refinement requires float coordinates")
        do_compact = refine_box is not None or filter is not None

        hit, rgs, stats = self.plan(bbox, filter, read_extra, want_geom)
        src_stats0 = self._source.stats.copy()
        idx = self.index
        if filter is not None and obs.enabled():
            # coordinate bytes of pages the zone stats pruned beyond bbox
            zoned = np.setdiff1d(idx.query(bbox), hit, assume_unique=True)
            obs.count("pruned.zone_bytes", int(idx.nbytes[zoned].sum()))

        total_recs = int(idx.rec_count[hit].sum()) if len(hit) else 0
        extra_all = {
            k: np.empty(total_recs, np.dtype(self.extra_schema[k]))
            for k in read_extra
        }
        level_parts = ([], [], [], []) if want_geom else None
        keep_parts: list[np.ndarray] = []
        x_parts: list = []
        y_parts: list = []
        we = 0        # record write cursor into the extra columns
        n_attr = 0    # read records the attribute predicate keeps
        vals_pruned = 0  # values of dropped records (record-level pruning)
        src_iter = self._iter_sources(rgs, coalesce)
        try:
            for rgp, src in src_iter:
                lv0 = len(level_parts[0]) if want_geom else 0
                prep = self._prepare_rg(src, rgp, stats, extra_all, we,
                                        level_parts, device=on_device)
                n_rec = rgp.n_records
                attr = None
                if filter is not None:
                    # the attribute mask over this row group's read records
                    attr = filter.mask({k: extra_all[k][we : we + n_rec]
                                        for k in filter.columns()})
                    n_attr += int(attr.sum())
                we += n_rec
                if not want_geom:
                    if attr is not None:
                        keep_parts.append(attr)
                    continue
                edges = None
                if exact:
                    # edges of the read records only, from their levels
                    edges = edge_partners(*(np.concatenate(parts[lv0:])
                                            for parts in (level_parts[2],
                                                          level_parts[3],
                                                          level_parts[0])))
                if on_device:
                    parts = self._execute_device(rgp, prep, refine_box, attr,
                                                 edges, keep_on_device)
                else:
                    parts = [self._execute_host(rgp, prep, refine_box, attr,
                                                edges)]
                for keep_c, xs, ys, vc in parts:
                    if do_compact:
                        keep_parts.append(keep_c)
                        if obs.enabled():
                            vals_pruned += int(vc.sum() - vc[keep_c].sum())
                    x_parts.append(xs)
                    y_parts.append(ys)
        finally:
            src_iter.close()
        if want_geom and do_compact:
            obs.count("pruned.record_bytes",
                      vals_pruned * 2 * self.coord_dtype.itemsize)
        if filter is not None and we and obs.enabled():
            obs.observe("filter.selectivity", n_attr / we)

        keep = None
        if do_compact:
            keep = (np.concatenate(keep_parts) if keep_parts
                    else np.zeros(0, bool))
        extras = {k: v[:we] for k, v in extra_all.items()}
        geo, extras = compact_records(level_parts, keep, x_parts, y_parts,
                                      extras, self.coord_dtype)
        extras = {k: extras[k] for k in want_extra}
        stats.records_returned = geo.n_records if geo is not None else (
            len(next(iter(extras.values()))) if extras else 0
        )
        self._fold_source_stats(stats, src_stats0)
        return geo, extras, stats

    def _fold_source_stats(self, stats: ReadStats, before) -> None:
        """Fold the source's recovery counters accrued by this read into the
        query's ReadStats (delta against the snapshot taken at entry)."""
        d = self._source.stats - before
        stats.retries += d.retries
        stats.timeouts += d.timeouts
        stats.cache_hits += d.cache_hits
        stats.cache_misses += d.cache_misses

    # ------------------------------------------------------------ executors
    def _execute_host(self, rgp: _RowGroupPlan, prep: _PreparedRowGroup,
                      refine_box, attr, edges) -> tuple:
        """The host executor over one prepared row group: its x pages, then
        its y pages, decode in one batch each (``rg.decode``), then the host
        keep function. Returns ``(keep, x, y, rec_vcounts)`` with the kept
        records' coordinates (``keep`` None: every record kept)."""
        vc = prep.rec_vcounts
        n = int(vc.sum())
        with obs.span("rg.decode", cat="decode", rg=rgp.rg_i, device="cpu"):
            x, y = (decode_pages(axis, self.coord_dtype, self.codec,
                                 out=np.empty(n, self.coord_dtype))
                    for axis in ([(bx, mx) for mx, bx, _, _ in prep.pages],
                                 [(by, my) for _, _, my, by in prep.pages]))
        keep = _host_keep(x, y, vc, refine_box, attr, edges)
        return (keep, *_kept_values(x, y, vc, keep), vc)

    def _execute_device(self, rgp: _RowGroupPlan, prep: _PreparedRowGroup,
                        refine_box, attr, edges, keep_on_device: bool) -> list:
        """The device executor over one prepared row group: its page pairs
        chunked under the launch cap, each chunk one launch chain
        (``rg.launch``) — decode, and with ``refine_box`` the per-record
        bbox test with ``attr`` AND-ed into the record mask, then with
        ``edges`` the exact stage — and a gather of the survivors
        (``rg.gather``). A pair too large for any launch decodes on the host
        (counted in ``device.host_fallback_pages``), and integer coordinates
        decode on the device; both then go through the host keep function.

        Returns ``(keep, x, y, rec_vcounts)`` a chunk, in record order, with
        the kept records' coordinates.
        """
        from repro.kernels.exact import exact_narrow as exact_narrow_device
        from repro.kernels.fp_delta import (
            build_page_stream,
            build_refine_aux,
            chunk_plan_pairs,
            decode_refine_stream,
            decode_stream_device,
            gather_stream_values,
        )

        dtype = self.coord_dtype
        width = dtype.itemsize * 8
        rg_i = rgp.rg_i
        dev_refine = refine_box is not None and dtype.kind == "f"
        host_refine = refine_box is not None and not dev_refine
        vc_rg = prep.rec_vcounts
        if edges is not None:
            vbound = np.concatenate([[0], np.cumsum(vc_rg)])
        out = []
        for kind, cplans, cpairs, (rl, rh) in chunk_plan_pairs(prep.plans,
                                                              prep.pairs):
            vc = vc_rg[rl:rh]
            attr_c = attr[rl:rh] if attr is not None else None
            edges_c = None
            if edges is not None:
                v0, v1 = int(vbound[rl]), int(vbound[rh])
                edges_c = (edges[0][v0:v1], edges[1][v0:v1])
            if kind == "host":
                # a single pair too large for any launch: same bits via
                # fp_delta_execute, same keep as the host executor
                with obs.span("rg.launch", cat="decode", rg=rg_i, kind="host"):
                    x_v = fp_delta_execute(cplans[0])
                    y_v = fp_delta_execute(cplans[1])
                    keep_c = _host_keep(x_v, y_v, vc, refine_box, attr_c,
                                        edges_c)
                    xs, ys = _kept_values(x_v, y_v, vc, keep_c)
                if keep_c is None:
                    keep_c = np.ones(len(vc), bool)
                if keep_on_device:
                    xs = DeviceCoords.from_numpy(xs)
                    ys = DeviceCoords.from_numpy(ys)
                out.append((keep_c, xs, ys, vc))
                continue
            with obs.span("rg.launch", cat="device", rg=rg_i,
                          kind="refine" if dev_refine else "decode",
                          pairs=len(cpairs)):
                with obs.span("launch.build", cat="plan"):
                    stream = build_page_stream(cplans)
                    aux = build_refine_aux(
                        stream, [(a - rl, b - rl) for a, b in cpairs],
                        vc, edges=edges_c)
                    if attr_c is not None and dev_refine:
                        # the device record mask is valid ∧ bbox; AND-ing
                        # the attribute mask into a fresh copy of valid
                        # makes it bbox ∧ attrs in the same launch
                        v2 = aux.valid.copy()
                        v2[:len(attr_c)] &= attr_c
                        aux = dc_replace(aux, valid=v2)
                if dev_refine:
                    res = decode_refine_stream(stream, aux, refine_box)
                    keep_c, lo_d, hi_d = res.keep, res.lo, res.hi
                    if edges_c is not None:
                        keep_c = exact_narrow_device(
                            lo_d, hi_d, aux, keep_c, refine_box, width, dtype)
                else:
                    lo_d, hi_d = decode_stream_device(stream)
                    keep_c = (attr_c.copy() if attr_c is not None
                              and not host_refine else np.ones(len(vc), bool))
            with obs.span("rg.gather", cat="transfer", rg=rg_i):
                ix = ragged_ranges(aux.x_start[keep_c], aux.counts[keep_c])
                iy = ragged_ranges(aux.y_start[keep_c], aux.counts[keep_c])
                xs = gather_stream_values(lo_d, hi_d, ix, width, dtype,
                                          keep_on_device=keep_on_device)
                ys = gather_stream_values(lo_d, hi_d, iy, width, dtype,
                                          keep_on_device=keep_on_device)
            if host_refine:
                keep_c = _host_keep(xs, ys, vc, refine_box, attr_c, None)
                xs, ys = _kept_values(xs, ys, vc, keep_c)
            out.append((keep_c, xs, ys, vc))
        return out

    # ---------------------------------------------- whole-row-group decode
    def read_row_group(self, rg_i: int, *, columns=None,
                       device: str = "cpu") -> "RowGroupData":
        """Fetch + decode *every* page of one row group, independent of any
        query bbox — the unit of the serve tier's decoded-row-group cache
        (:mod:`repro.serve.query_scheduler`).

        Pages are record-aligned, so a record's values (and therefore its
        exact [min, max]) computed from the full row group are bit-identical
        to the same record decoded through a bbox-pruned page run — the
        property that lets one decode serve queries whose page sets differ.
        The row group goes through the read path's own preparation;
        ``device="cpu"`` then runs the host executor's decode into ``x``/
        ``y``, and ``device="jax"`` returns *unlaunched* per-chunk page
        streams (the caller owns the launch so it can fuse multi-query
        refinement into it).
        """
        if device not in ("cpu", "jax"):
            raise ValueError(f"device must be 'cpu' or 'jax', got {device!r}")
        n_pages = len(self.footer["row_groups"][rg_i]["x_pages"])
        want_extra, _ = extra_columns(self.extra_schema, columns, None)
        rgp = self._rg_plan(rg_i, [(0, n_pages)] if n_pages else [],
                            want_extra, True)
        with obs.span("rg.read_full", cat="io", rg=rg_i, device=device):
            src = _CoalescedRanges(self._source, rgp.ranges,
                                   self.coalesce_max_gap)
            extra_all = {k: np.empty(rgp.n_records,
                                     np.dtype(self.extra_schema[k]))
                         for k in want_extra}
            prep = self._prepare_rg(src, rgp, ReadStats(), extra_all, 0,
                                    device=device == "jax")
            data = RowGroupData(rg_i, prep.levels.n_rec, prep.rec_vcounts,
                                prep.levels, extra_all, rgp.nbytes)
            if device == "cpu":
                _, data.x, data.y, _ = self._execute_host(rgp, prep, None,
                                                          None, None)
                return data

            from repro.kernels.fp_delta import (
                build_page_stream,
                build_refine_aux,
                chunk_plan_pairs,
            )

            data.chunks = []
            for kind, cplans, cpairs, (rl, rh) in chunk_plan_pairs(
                    prep.plans, prep.pairs):
                if kind == "host":
                    data.chunks.append(RowGroupChunk(
                        "host", rl, rh,
                        x=fp_delta_execute(cplans[0]),
                        y=fp_delta_execute(cplans[1])))
                    continue
                stream = build_page_stream(cplans)
                aux = build_refine_aux(
                    stream, [(a - rl, b - rl) for a, b in cpairs],
                    prep.rec_vcounts[rl:rh])
                data.chunks.append(RowGroupChunk("dev", rl, rh,
                                                 stream=stream, aux=aux))
            return data

    def read(self, bbox=None, refine: bool = False) -> tuple[list[Geometry], ReadStats]:
        """Object-API read returning Geometry instances."""
        geo, _, stats = self.read_columnar(bbox=bbox, refine=refine)
        return (assemble(geo) if geo is not None else []), stats


def extra_columns(schema: dict, columns, filter) -> tuple[list, list]:
    """``(returned, read)`` extra columns of a read: those ``columns``
    names (every one when None), and those plus the columns ``filter``
    needs, which decode but are not returned."""
    want = (list(schema) if columns is None
            else [c for c in columns if c in schema])
    if filter is None:
        return want, list(want)
    return want, want + [c for c in sorted(filter.columns()) if c not in want]


def _bbox_keep_mask(x: np.ndarray, y: np.ndarray, counts: np.ndarray,
                    bbox) -> np.ndarray:
    """Exact per-record bbox mask over contiguous value slices (the host
    refinement oracle: NaN-propagating ``minimum.reduceat`` + float
    compares — any NaN coordinate drops its record). The query box goes
    through the shared :func:`~repro.core.filters.canonical_bbox` rule
    first, so an empty box (NaN bound / inverted extent) keeps nothing —
    the same answer the shard-, page- and device-record-level tests give.
    """
    counts = np.asarray(counts, np.int64)
    keep = np.zeros(len(counts), dtype=bool)
    bbox = canonical_bbox(bbox)
    if bbox is None:
        return keep
    starts = np.cumsum(counts) - counts
    nz = counts > 0
    if nz.any():
        s = starts[nz]
        xs = x.astype(np.float64, copy=False)
        ys = y.astype(np.float64, copy=False)
        xmin = np.minimum.reduceat(xs, s)
        xmax = np.maximum.reduceat(xs, s)
        ymin = np.minimum.reduceat(ys, s)
        ymax = np.maximum.reduceat(ys, s)
        qx0, qy0, qx1, qy1 = bbox
        keep[nz] = (xmin <= qx1) & (xmax >= qx0) & (ymin <= qy1) & (ymax >= qy0)
    return keep


def _host_keep(x, y, counts, refine_box, attr, edges) -> np.ndarray | None:
    """The host keep function over records laid out one after another in
    ``x``/``y`` (``counts`` values each): the bbox test against
    ``refine_box`` (``refine.host``; None: no refine) ∧ the attribute mask
    ``attr``, then, with ``edges``, the exact test over the records both
    keep (``refine.exact``). None when nothing narrows."""
    keep = None
    if refine_box is not None:
        with obs.span("refine.host", cat="refine"):
            keep = _bbox_keep_mask(x, y, counts, refine_box)
    if attr is not None:
        keep = attr if keep is None else keep & attr
    if edges is not None:
        with obs.span("refine.exact", cat="refine"):
            keep = exact_narrow(x, y, counts, *edges, keep, refine_box)
    return keep


def _kept_values(x, y, counts, keep):
    """The coordinates of the records ``keep`` holds (all when None)."""
    if keep is None:
        return x, y
    counts = np.asarray(counts, np.int64)
    iv = ragged_ranges((np.cumsum(counts) - counts)[keep], counts[keep])
    return x[iv], y[iv]


def _concat_coords(parts, dtype):
    if not parts:
        return np.zeros(0, dtype)
    if isinstance(parts[0], DeviceCoords):
        return DeviceCoords.concat(parts)
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def compact_records(level_parts, keep, x_parts, y_parts, extras, dtype):
    """A read's result from its parts, cut to the records ``keep`` holds
    (a mask over the read records; None keeps every one).

    The one compaction rule of every path: the level streams concatenate
    and keep the slots and sub-geometries of the kept records — a
    record-aligned subset of canonical levels is canonical, and equals
    :func:`~repro.core.writer.permute_records` of the kept records; the
    extra columns take the same mask; ``x_parts``/``y_parts`` already hold
    only the kept records' coordinates. Returns ``(geo, extras)``, ``geo``
    None when no level part was read.
    """
    if keep is not None:
        extras = {k: v[keep] for k, v in extras.items()}
    if level_parts is None or not level_parts[0]:
        return None, extras
    types, type_rep, rep, defn = (np.concatenate(p) for p in level_parts)
    if keep is not None:
        slot_keep = keep[np.cumsum(rep == 0) - 1]
        type_keep = keep[np.cumsum(type_rep == 0) - 1]
        types, type_rep = types[type_keep], type_rep[type_keep]
        rep, defn = rep[slot_keep], defn[slot_keep]
    return GeometryColumns(types, type_rep, rep, defn,
                           _concat_coords(x_parts, dtype),
                           _concat_coords(y_parts, dtype)), extras
