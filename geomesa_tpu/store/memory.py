"""In-memory TPU datastore: the end-to-end execution engine.

The reference's in-memory store (geomesa-memory/.../GeoCQEngine.scala:33)
indexes features in CQEngine collections and evaluates queries on the
CPU; here feature batches live as columnar device arrays and queries run
as fused XLA scans:

    write(batch) -> host columns + device scan arrays
    query(q)     -> plan (splitter + cost decider)
                 -> device kernel mask (spatio-temporal, exact via
                    two-float + boundary f64 patch)
                 -> residual filter on surviving candidates (host f64
                    reference evaluator; device compilation later)
                 -> QueryResult (ids / batches / aggregates)

This single-device path is the building block the mesh-sharded store
(parallel/) distributes.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import weakref
from typing import Any, Iterator

import numpy as np

from ..features.batch import FeatureBatch, PointColumn
from ..features.sft import SimpleFeatureType, parse_spec
from ..filters import ast
from ..filters.ecql import parse_ecql
from ..filters.evaluate import evaluate, take_for
from ..filters.helper import extract_geometries, extract_intervals
from ..geometry import Envelope
from ..index.api import Explainer, FilterStrategy, Query, QueryHints
from ..index.planner import decide_strategy
from ..obs import runtime, tracer
from .api import DataStore
from ..scan import gscan, zscan
from ..stats import DataStoreStats, parse_stat
from ..utils.properties import SystemProperty
from ..utils.threads import ThreadManagement

# process-wide query reaper (ThreadManagement.scala's 5s sweep)
_REAPER = ThreadManagement()

# index-pruned candidate sets at or below this size evaluate exactly on
# host in f64 (one vectorized pass over the gathered rows) instead of a
# device round trip — per-query latency is then index-search +
# candidate-sized work, not dispatch-floor bound. Larger candidate sets
# ride the device kernels where HBM bandwidth wins.
HOST_SCAN_ROWS = SystemProperty("geomesa.scan.host.rows", "2000000")

# the extent pruned path re-checks candidates with per-geometry exact
# predicates (Python-loop scale, not the vectorized point math), so its
# crossover back to the dense device tristate sits much lower
EXTENT_HOST_SCAN_ROWS = SystemProperty("geomesa.scan.extent.host.rows",
                                       "50000")

# point-in-polygon residuals below this row count stay on the host
# (vectorized crossing-number, ~tens of M rows/s): a device dispatch
# pays a round trip that only amortizes over large candidate sets
_DEVICE_PIP_ROWS = 2_000_000

# pre-compile the dwithin/KNN join-kernel shape family at bulk-ingest
# time (analytics/join.prewarm_join_kernels): the compile (or its
# persistent-cache load) runs inside the untimed load phase, so the
# first join/KNN query pays milliseconds, not a multi-second XLA
# compile — the join-path analog of the eager z-index build below
JOIN_PREWARM = SystemProperty("geomesa.join.prewarm", "true")

__all__ = ["InMemoryDataStore", "QueryResult"]


class _PlanArtifacts:
    """Filter-derived plan state reused across identical queries
    (cached next to the FilterStrategy in _TypeState.plan_cache):
    query geometries/boxes/intervals and the device scan-query struct.
    All fields derive from the immutable filter AST only, never from
    the data, so they survive until the plan cache is invalidated."""

    __slots__ = ("geoms", "boxes", "intervals", "needs_exact",
                 "spatial_f", "sq", "filled")

    def __init__(self):
        self.filled = False
        self.sq = None


class _LazyBatch:
    """Deferred result materialization: the source batch snapshot (the
    columnar arrays are immutable — writes build new objects) plus the
    matched rows. The column copies happen only if a caller actually
    reads ``result.batch`` — id-only consumers (counts, exactness
    checks, bench loops) never pay them. The reference's feature
    readers are lazy in the same way (KryoBufferSimpleFeature)."""

    def __init__(self, source: FeatureBatch, idx: np.ndarray,
                 properties, row_order: bool = True):
        self.source = source
        self.idx = idx
        self.properties = properties
        # False when the caller reordered idx (sort_by): the endpoint
        # identity check below would misread a permutation as identity
        self.row_order = row_order
        self._mat: FeatureBatch | None = None

    def detach(self):
        """Break the pin on the source snapshot (the store calls this
        when data mutates): small results materialize — the copy is
        trivial, and an unread small result must not keep a superseded
        multi-GB snapshot alive. Large results stay lazy (pre-existing
        policy: their consumers read the columns soon, and the copy is
        the expensive part)."""
        if self._mat is None and len(self.idx) <= 10_000:
            self.materialize()

    def materialize(self) -> FeatureBatch:
        if self._mat is not None:
            return self._mat
        if (self.row_order and self.properties is None
                and len(self.idx) == self.source.n
                and self.idx[0] == 0 and self.idx[-1] == self.source.n - 1):
            # full-table result in ASCENDING row order (the scan
            # strategies all return sorted indices), so endpoint +
            # length checks imply identity: the immutable source
            # snapshot IS the result — an INCLUDE scan over 100M rows
            # must not copy every column
            self._mat = self.source
            return self._mat
        batch = self.source.take(self.idx)
        if self.properties is not None:
            cols = {p: batch.columns[p] for p in self.properties}
            batch = FeatureBatch(
                _project_sft(self.source.sft, self.properties),
                batch.ids, cols)
        self._mat = batch
        self.source = None  # release the snapshot pin
        return batch


class QueryResult:
    """Result of a feature query.

    ``batch`` materializes lazily when the store handed over a
    _LazyBatch; id-only consumers never pay the column copies. ``None``
    means the store/type held no data at all — a zero-hit query still
    yields an (empty) batch.
    """

    def __init__(self, ids, batch, explain: Explainer,
                 plan: FilterStrategy, n: int | None = None,
                 scan_span: tuple | None = None):
        # ids may be a thunk: the object-array id gather at 10M+ rows
        # costs more than many whole queries, and join/count consumers
        # never read it. scan_span is the query's store-scan span as
        # (trace_id, span_id): the deferred gather's span links to it
        self._ids = ids
        self._scan_span = scan_span
        self._n = n if n is not None else len(ids)
        self._batch = batch          # FeatureBatch | None | _LazyBatch
        self.explain = explain
        self.plan = plan

    @property
    def ids(self) -> np.ndarray:
        if callable(self._ids):
            # runs after the request's trace has closed: a root of its own
            with tracer.span("result-ids", root=True) as sp:
                if self._scan_span is not None:
                    sp.link(*self._scan_span)
                self._ids = self._ids()
                sp.set_attr(ids=len(self._ids))
        return self._ids

    @property
    def batch(self) -> FeatureBatch | None:
        if isinstance(self._batch, _LazyBatch):
            self._batch = self._batch.materialize()
        return self._batch

    @batch.setter
    def batch(self, value):
        self._batch = value

    @property
    def n(self) -> int:
        return self._n

    def features(self) -> Iterator[dict[str, Any]]:
        if self.batch is None:
            return iter(())
        return (self.batch.feature(i) for i in range(self.batch.n))

    def __repr__(self) -> str:
        return (f"QueryResult(n={self.n}, "
                f"plan={self.plan.index if self.plan else None})")


def _attr_vis_masks(vis_rows, n_attr: int, auths) -> np.ndarray:
    """(len(rows), n_attr) bool authorization matrix for
    attribute-level visibility labels (comma-joined per attribute;
    empty part = world-readable). Distinct label combos are parsed
    once."""
    from ..security import parse_visibility
    out = np.ones((len(vis_rows), n_attr), dtype=bool)
    cache: dict[str, np.ndarray] = {}
    auth_set = set(auths)
    for i, v in enumerate(vis_rows):
        if not v:
            continue
        row = cache.get(v)
        if row is None:
            parts = (str(v).split(",") + [""] * n_attr)[:n_attr]
            row = np.array(
                [not p or parse_visibility(p).evaluate(auth_set)
                 for p in parts], dtype=bool)
            cache[v] = row
        out[i] = row
    return out


def _null_cells(col, bad: np.ndarray):
    """Copy of a column with `bad` rows nulled (unauthorized
    attribute values at query time)."""
    import dataclasses as _dc

    from ..features.batch import GeometryColumn, StringColumn
    if isinstance(col, StringColumn):
        codes = col.codes.copy()
        codes[bad] = -1
        return StringColumn(col.name, codes, col.vocab)
    if isinstance(col, GeometryColumn):
        geoms = [None if b else g for g, b in zip(col.geoms, bad)]
        bounds = col.bounds.copy()
        bounds[bad] = np.nan
        return GeometryColumn(col.name, geoms, bounds)
    return _dc.replace(col, valid=np.asarray(col.valid) & ~bad)


class _TypeState:
    """Per-feature-type storage: host batch + lazily-built device index.

    Writes are LSM-style: appends land in a pending buffer (O(delta));
    the first read flushes the buffer — one concat, and already-built
    sort orders are MERGED with the delta (ZKeyIndex.extend sorted-run
    merge, device-side scan-array concat) instead of rebuilt from
    scratch. The reference gets the same shape from BatchWriter
    mutations merging into tablets at minor compaction
    (accumulo/util/GeoMesaBatchWriterConfig.scala)."""

    def __init__(self, sft: SimpleFeatureType):
        self.sft = sft
        # guards the lazy read-side mutations (pending flush, index
        # build, deferred device upload): process helpers reach these
        # through the state object directly, without the store-level
        # _op_lock, so two concurrent fused dispatches must not race a
        # rebuild. Store ops already hold _op_lock when they get here —
        # the order is always store lock -> state lock, never reversed.
        self._state_lock = threading.RLock()
        self._batch: FeatureBatch | None = None
        self._pending: list[tuple[FeatureBatch, np.ndarray]] = []
        self._pending_n = 0
        self._scan_data: zscan.DeviceScanData | None = None
        self._scan_thunk = None  # deferred device build (see scan_data)
        self.extent_data = None  # gscan.ExtentScanData for non-points
        self.zindex = None       # index.zkeys.ZKeyIndex for points
        # lazily-built sorted attribute indexes (AttributeIndex analog)
        self.attr_idx: dict[str, Any] = {}
        # lazy device uploads of attribute columns for residual kernels
        self.devcols = None  # scan.residual.DeviceColumns
        self.dirty = False
        # per-feature visibility expressions (None = world-readable);
        # has_vis avoids an O(n) object-array scan on every query.
        # Attribute-level schemas store comma-joined per-attribute
        # labels in the same array (split lazily at query time).
        self.vis: np.ndarray = np.empty(0, dtype=object)
        self.has_vis = False
        # persisted sort orders to install into the next-built zindex
        # (fs-store index sidecars); consumed by ensure_index
        self.zindex_warm: dict | None = None
        # (filter, hints) -> (filter_ref, FilterStrategy, _PlanArtifacts):
        # repeated queries skip the splitter/cost decision and the
        # filter-side geometry/interval extraction (the reference keeps
        # the same artifacts on its QueryPlan). Cleared on any data
        # mutation — costs and n_features feed the decision.
        self.plan_cache: dict = {}
        # outstanding lazy results: on data mutation, small ones are
        # detached (materialized) so they stop pinning the superseded
        # column snapshot
        self.live_lazy: "weakref.WeakSet" = weakref.WeakSet()

    @property
    def scan_data(self):
        """The device point-scan arrays, uploaded ON FIRST DEVICE USE:
        ensure_index defers the host->device column transfer (the
        dominant cold-start cost at 100M rows) so selective queries
        answered by the host z-index fast path never pay it. Reading
        this property materializes the upload."""
        if self._scan_data is None and self._scan_thunk is not None:
            with self._state_lock:
                if self._scan_thunk is not None:
                    self._scan_data = self._scan_thunk()
                    self._scan_thunk = None
        return self._scan_data

    @scan_data.setter
    def scan_data(self, value):
        self._scan_data = value
        self._scan_thunk = None

    def _deferred_scan_build(self):
        """Thunk over the CURRENT batch: reads state at materialize
        time, so successive deferred extends just re-defer."""
        def build():
            geom = self.sft.geom_field
            dtg = self.sft.dtg_field
            col = self._batch.col(geom)
            millis = (self._batch.col(dtg).millis if dtg is not None
                      else np.zeros(self._batch.n, dtype=np.int64))
            return zscan.build_scan_data(col.x, col.y, millis)
        return build

    @property
    def n(self) -> int:
        return (0 if self._batch is None else self._batch.n) \
            + self._pending_n

    @property
    def batch(self) -> FeatureBatch | None:
        self.flush()
        return self._batch

    def validate(self, batch: FeatureBatch, visibilities=None):
        """Pre-flight append checks WITHOUT mutating — also the durable
        write path's guard: a record must be known applyable before it
        is journaled, or replay would re-fail on it. Returns the
        normalized (vis array, distinct labels)."""
        if visibilities is None:
            # fast path: no O(n) object scan for the common open write
            vis = np.full(batch.n, None, dtype=object)
            distinct = set()
        else:
            vis = np.asarray(visibilities, dtype=object)
            distinct = set(v for v in vis.tolist() if v)
        if len(vis) != batch.n:
            raise ValueError("visibilities length mismatch")
        from ..security import validate_labels
        validate_labels(self.sft, distinct)  # raises on malformed
        return vis, distinct

    def append(self, batch: FeatureBatch, visibilities=None):
        # validate everything BEFORE mutating: a failed write must not
        # leave batch/vis misaligned
        vis, distinct = self.validate(batch, visibilities)
        if distinct:
            self.has_vis = True
        self._pending.append((batch, vis))
        self._pending_n += batch.n
        self.plan_cache.clear()
        self._detach_live()

    def _detach_live(self):
        """Materialize outstanding small lazy results so they release
        the about-to-be-superseded column snapshot."""
        for lb in list(self.live_lazy):
            lb.detach()
        self.live_lazy.clear()

    def has_point_scan(self) -> bool:
        """Whether a device point-scan structure is built or deferred
        (subclasses redefine what that structure is — e.g. mesh-sharded
        segments). Checking must NOT force the deferred upload."""
        return (self._scan_data is not None
                or self._scan_thunk is not None)

    def has_extent_scan(self) -> bool:
        return self.extent_data is not None

    def flush(self):
        """Materialize pending appends: one concat for the burst, then
        incremental index maintenance when the index is already built."""
        with self._state_lock:
            self._flush_locked()

    def _flush_locked(self):
        if not self._pending:
            return
        delta = FeatureBatch.concat_all([b for b, _ in self._pending])
        base = self._batch
        can_merge = (base is not None and not self.dirty
                     and self.has_point_scan()
                     and self.zindex is not None)
        # build everything BEFORE mutating state: a MemoryError on the
        # big concat must leave the store consistent (batch/vis/pending
        # aligned), matching append()'s fail-atomic contract
        new_batch = delta if base is None else base.concat(delta)
        new_vis = np.concatenate([self.vis]
                                 + [v for _, v in self._pending])
        self._batch = new_batch
        self.vis = new_vis
        self._pending = []
        self._pending_n = 0
        # merged indexes go stale per-column; rebuild those lazily
        self.attr_idx.clear()
        self.devcols = None
        # pessimistically dirty: if index maintenance below fails midway,
        # the next read must rebuild rather than scan a short index
        self.dirty = True
        if not can_merge:
            return
        geom = self.sft.geom_field
        col = delta.col(geom) if geom else None
        if not isinstance(col, PointColumn):
            return
        dtg = self.sft.dtg_field
        dmillis = (delta.col(dtg).millis if dtg is not None
                   else np.zeros(delta.n, dtype=np.int64))
        # device first: when it declines (segment-cap compaction), the
        # O(n) zindex sorted-run merge must not have been paid for
        # nothing; dirty stays True throughout, so a failure at any
        # point still rebuilds on the next read
        if not self._extend_device_index(col, dmillis):
            return  # stays dirty: next read rebuilds (compaction)
        self.zindex = self.zindex.extend(
            col.x, col.y, dmillis if dtg is not None else None)
        self.dirty = False

    def _extend_device_index(self, col: PointColumn,
                             dmillis: np.ndarray) -> bool:
        """Append the delta to the device scan structures; False leaves
        the state dirty so the next read rebuilds from scratch."""
        if self._scan_data is None and self._scan_thunk is not None:
            # device build still deferred: re-defer over the merged batch
            self._scan_thunk = self._deferred_scan_build()
            return True
        dxhi, dxlo = zscan.split_two_float(col.x)
        dyhi, dylo = zscan.split_two_float(col.y)
        scan_data = zscan.extend_scan_data(
            self.scan_data, col.x, col.y, dmillis,
            xy_split=(dxhi, dxlo, dyhi, dylo))
        if scan_data is None:
            # capacity exhausted: rebuild once with power-of-two
            # headroom, then future bursts append in place again
            dtg = self.sft.dtg_field
            gcol = self._batch.col(self.sft.geom_field)
            fmillis = (self._batch.col(dtg).millis if dtg is not None
                       else np.zeros(self._batch.n, dtype=np.int64))
            scan_data = zscan.build_scan_data(
                gcol.x, gcol.y, fmillis,
                cap=zscan.next_pow2(self._batch.n + 1))
        # all structures built: publish atomically
        self.scan_data = scan_data
        return True

    def delete(self, ids: set[str]):
        # dirty first: the flush skips merge work the delete is about to
        # invalidate anyway
        self.dirty = True
        self.plan_cache.clear()
        self._detach_live()
        self.flush()
        if self._batch is None:
            return
        keep = ~np.isin(self._batch.ids.astype(str), list(ids))
        self._batch = self._batch.take(np.flatnonzero(keep))
        self.vis = self.vis[keep]
        self.attr_idx.clear()
        self.devcols = None
        self.dirty = True

    def ensure_index(self):
        """(Re)build device arrays if writes happened."""
        with self._state_lock:
            self._ensure_index_locked()

    def _ensure_index_locked(self):
        self.flush()  # may maintain the index incrementally
        if not self.dirty and (self.has_point_scan()
                               or self.has_extent_scan()):
            return
        if self.batch is None or self.batch.n == 0:
            self._clear_device_index()
            self.dirty = False
            return
        geom = self.sft.geom_field
        dtg = self.sft.dtg_field
        col = self.batch.col(geom) if geom else None
        if not isinstance(col, PointColumn):
            # extent geometries: device bbox tristate scan (XZ analog)
            # plus a host XZ-key index for range pruning
            self._clear_device_index()
            if col is not None:
                millis = (self.batch.col(dtg).millis
                          if dtg is not None else None)
                self._build_extent_index(col.bounds, millis)
                from ..index.xzkeys import XZKeyIndex
                self.zindex = XZKeyIndex(col.bounds, millis,
                                         self.sft.z3_interval)
            self.dirty = False
            return
        x = col.x
        y = col.y
        if dtg is not None:
            millis = self.batch.col(dtg).millis
        else:
            millis = np.zeros(len(x), dtype=np.int64)
        self._build_point_index(x, y, millis)
        # host sorted z-key index for range pruning (lazy per curve);
        # Z3IndexKeySpace.getRanges analog feeding the scan tiers
        from ..index.zkeys import ZKeyIndex
        self.zindex = ZKeyIndex(x, y,
                                millis if dtg is not None else None,
                                self.sft.z3_interval,
                                version=self.sft.index_version)
        if self.zindex_warm is not None:
            self.zindex.load_state(self.zindex_warm)  # no-op when stale
            self.zindex_warm = None
        self.dirty = False

    def _clear_device_index(self):
        self.scan_data = None
        self.extent_data = None

    def _build_point_index(self, x, y, millis):
        # DEFER the device upload: a selective first query resolves on
        # the host z-index and never pays it
        self._scan_data = None
        self._scan_thunk = self._deferred_scan_build()

    def _build_extent_index(self, bounds, millis):
        self.extent_data = gscan.build_extent_data(bounds, millis)

    def attr_index(self, name: str):
        """Sorted attribute index for one column, built on first use
        (AttributeIndex analog; see index/attr.py). Keys are (value,
        date) composites when the schema has a default date, so
        equality scans narrow by the filter's date bounds."""
        self.flush()  # cached indexes must cover pending rows
        if name not in self.attr_idx:
            from ..index.attr import AttributeKeyIndex
            dtg = self.sft.dtg_field
            date_millis = (self.batch.col(dtg).millis
                           if dtg is not None and dtg != name else None)
            try:
                self.attr_idx[name] = AttributeKeyIndex(
                    self.batch.col(name), date_millis=date_millis)
            except TypeError:
                self.attr_idx[name] = None  # unindexable column type
        return self.attr_idx[name]

    def device_cols(self):
        self.flush()  # cached uploads must cover pending rows
        if self.devcols is None:
            from ..scan.residual import DeviceColumns
            self.devcols = DeviceColumns(self.batch)
        return self.devcols


def _synchronized(fn):
    """Serialize a store operation on the per-store reentrant lock.
    Reads mutate state too (pending-append flush, lazy index builds,
    plan caches), so ANY two concurrent operations on one store may
    race — a replica apply loop interleaving with scatter-gather query
    legs would desync batch/vis and silently drop rows. Per-store
    serialization keeps cross-store parallelism (each shard group owns
    its lock) while making a single store safe to serve from many
    threads."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._op_lock:
            return fn(self, *args, **kwargs)
    return wrapper


def _grid_copy(grid: np.ndarray) -> np.ndarray:
    """Cache encode/decode for density grids: every hit hands out a
    private copy, so a caller scribbling on its grid (or a cluster leg
    accumulating in place) cannot corrupt the memoized original."""
    return np.asarray(grid).copy()


class InMemoryDataStore(DataStore):
    """A GeoTools-DataStore-shaped API over device-resident batches."""

    def __init__(self, audit=None, durable_dir: str | None = None,
                 wal_fsync: str | None = None):
        self._op_lock = threading.RLock()
        self._types: dict[str, _TypeState] = {}
        self.stats = DataStoreStats()
        self.audit = audit  # AuditLogger or None
        # LSN-keyed materialized pushdown cache (cache/ subsystem):
        # every mutation stamps the type's version — the WAL LSN when
        # durable, a store-local counter otherwise — so density/stats/
        # bin/arrow results memoize until the type actually changes.
        # Created before the journal: recovery replays mutations
        # through write()/delete(), which stamp versions.
        from ..cache import ResultCache
        self._pushdown_clock = 0
        self._pushdown_versions: dict[str, int] = {}
        self.result_cache = ResultCache(self.pushdown_version)
        # evolve/ subsystem: per-type dual-feed taps installed while a
        # shadow schema build is in flight (empty = zero-cost path),
        # and the lazily built Evolver behind them
        self._evolve_feeds: dict = {}
        self._evolver = None
        # opt-in durability: journal mutations to a WAL under
        # durable_dir (validate -> journal -> apply) and replay the
        # last checkpoint + log tail on open (wal/ subsystem)
        self.journal = None
        if durable_dir:
            from ..wal.durable import Journal
            self.journal = Journal(durable_dir, fsync=wal_fsync)
            self.journal.recover(self)

    # -- schema management (MetadataBackedDataStore surface) --------------

    @_synchronized
    def create_schema(self, sft: SimpleFeatureType | str,
                      spec: str | None = None):
        if isinstance(sft, str):
            sft = parse_spec(sft, spec or "")
        if sft.type_name in self._types:
            raise ValueError(f"schema {sft.type_name!r} already exists")
        if self.journal is not None:
            self.journal.log_create_schema(sft)
        self._types[sft.type_name] = self._new_state(sft)
        # an estimator exists from schema creation: a type with zero
        # observed rows estimates 0 (a cluster group that owns no rows
        # of a type must not null the coordinator's merged estimate);
        # only an explicit stats.clear() makes a type non-estimable
        self.stats.ensure(sft)
        self._bump_pushdown_version(sft.type_name)

    def _new_state(self, sft: SimpleFeatureType) -> _TypeState:
        return _TypeState(sft)

    def get_schema(self, type_name: str) -> SimpleFeatureType:
        return self._state(type_name).sft

    def get_type_names(self) -> list[str]:
        return sorted(self._types)

    @_synchronized
    def remove_schema(self, type_name: str):
        if self.journal is not None and type_name in self._types:
            self.journal.log_drop_schema(type_name)
        st = self._types.pop(type_name, None)
        if st is not None:
            # outstanding small lazy results must not pin the dropped
            # column snapshot
            st._detach_live()
        self._bump_pushdown_version(type_name)
        self.result_cache.invalidate(type_name)

    def _state(self, type_name: str) -> _TypeState:
        if type_name not in self._types:
            raise KeyError(f"no such schema: {type_name}")
        if self._evolve_feeds:
            # a mid-flip evolution fences every op on its type typed
            # (SchemaEvolutionError) until resume()/abort() restores a
            # consistent state — exact-or-typed, never silently stale
            feed = self._evolve_feeds.get(type_name)
            if feed is not None:
                feed.guard()
        return self._types[type_name]

    @property
    def evolver(self):
        """The online schema-evolution plane for this store (evolve/
        subsystem), built on first touch."""
        if self._evolver is None:
            with self._op_lock:
                if self._evolver is None:
                    from ..evolve import Evolver
                    self._evolver = Evolver(self)
        return self._evolver

    # -- pushdown versions (cache/ subsystem) ------------------------------

    def _bump_pushdown_version(self, type_name: str):
        """Stamp the type's version after a mutation: the WAL LSN when
        the journal advanced, a store-local counter otherwise (replay
        suppresses journaling, so the counter also covers recovery)."""
        prev = self._pushdown_versions.get(type_name, 0)
        v = self.journal.wal.last_lsn if self.journal is not None else 0
        if v <= prev:
            self._pushdown_clock += 1
            v = max(prev + 1, self._pushdown_clock)
        self._pushdown_versions[type_name] = v

    def pushdown_version(self, type_name: str) -> int:
        """Cache/ETag version for the type: any change to its rows or
        schema advances it; unchanged version == identical pushdown
        results. Per-type, so writes to one type never invalidate
        another's cached tiles."""
        return self._pushdown_versions.get(type_name, 0)

    def cache_status(self) -> dict:
        out = self.result_cache.status()
        out["versions"] = dict(self._pushdown_versions)
        return out

    def invalidate_cache(self, type_name: str | None = None) -> int:
        return self.result_cache.invalidate(type_name)

    # -- writes ------------------------------------------------------------

    # bulk writes at or above this build the z-key orders eagerly: the
    # reference indexes at INGEST (every BatchWriter mutation carries
    # its z-keys, write path 3.2), so a bulk load should hand the first
    # query a ready index instead of a multi-second build
    _EAGER_INDEX_ROWS = 5_000_000

    @_synchronized
    def write(self, type_name: str, batch: FeatureBatch, visibilities=None):
        st = self._state(type_name)
        if batch.sft != st.sft:
            raise ValueError("batch schema does not match store schema")
        feed = self._evolve_feeds.get(type_name) \
            if self._evolve_feeds else None
        if feed is not None:
            # refuse before journaling: a write that conflicts with an
            # in-flight evolution (non-null values for a mid-drop
            # attribute) must never be acked
            feed.check_write(batch)
        if self.journal is not None:
            # write-ahead: validate (so the journaled record is known
            # applyable), journal, then apply
            st.validate(batch, visibilities)
            self.journal.log_write(type_name, batch, visibilities)
        was_empty = st.n == 0
        st.append(batch, visibilities)
        self._bump_pushdown_version(type_name)
        # auto-maintained stats, the write-side StatsCombiner analog
        # (accumulo/data/stats/StatsCombiner.scala)
        self.stats.observe(st.sft, batch)
        if feed is not None:
            # dual-feed: non-durable stores queue the acked mutation
            # for the shadow build (durable stores tail the WAL)
            feed.on_write(batch, visibilities)
        # initial bulk load only: chunked ingests must not re-merge the
        # whole accumulated table per batch (later chunks stay lazy and
        # fold into ONE incremental merge at the next read)
        if was_empty and batch.n >= self._EAGER_INDEX_ROWS:
            try:
                st.ensure_index()
                if st.zindex is not None and hasattr(st.zindex, "warm"):
                    st.zindex.warm()
                self._prewarm_join(st)
            except MemoryError:
                raise
            except Exception:
                import logging

                from ..metrics import metrics
                logging.getLogger("geomesa_tpu").warning(
                    "ingest-time index build failed; falling back to "
                    "lazy build on first read", exc_info=True)
                metrics.counter("store.ingest.index_build.failed")

    @staticmethod
    def _prewarm_join(st):
        """Compile-cache the dwithin/KNN kernel family for this type's
        capacity class during ingest (``geomesa.join.prewarm``), so the
        first join/KNN query is a cache hit — the join analog of the
        eager z-index build above."""
        if str(JOIN_PREWARM.get()).lower() not in ("true", "1", "yes"):
            return
        from ..features.batch import PointColumn
        col = st.batch.col(st.sft.geom_field) if st.batch is not None \
            else None
        if not isinstance(col, PointColumn):
            return
        sd = getattr(st, "scan_data", None)
        device_xy = (sd.xhi, sd.yhi) if sd is not None else None
        from ..analytics.join import prewarm_join_kernels
        prewarm_join_kernels(col.x, col.y, device_xy=device_xy)

    @_synchronized
    def delete(self, type_name: str, ids):
        st = self._state(type_name)
        ids = set(map(str, ids))
        if self.journal is not None:
            self.journal.log_delete(type_name, sorted(ids))
        st.delete(ids)
        self._bump_pushdown_version(type_name)
        feed = self._evolve_feeds.get(type_name) \
            if self._evolve_feeds else None
        if feed is not None:
            feed.on_delete(ids)

    # -- durability (wal/ subsystem, opt-in via durable_dir) ---------------

    @_synchronized
    def checkpoint(self, keep: int = 2) -> dict:
        """Snapshot current state and compact the journal; requires the
        ``durable_dir`` knob. ``keep=2`` retains the prior checkpoint
        (and the log back to it) so recovery can fall back id-exactly
        if the newest snapshot is later found corrupt."""
        if self.journal is None:
            raise ValueError("store is not durable (no durable_dir)")
        return self.journal.checkpoint(self, keep=keep)

    def close(self):
        if self.journal is not None:
            self.journal.close()

    @_synchronized
    def warm_index(self, type_name: str, state: dict):
        """Install persisted z-key sort orders (possibly memory-mapped)
        to be adopted by the next index build — the fs store's sidecar
        reopen path. Stale states (row count mismatch) are ignored."""
        self._state(type_name).zindex_warm = state

    @_synchronized
    def index_state(self, type_name: str) -> dict | None:
        """Built z-key sort orders for persistence, or None when no
        index has been built yet."""
        st = self._state(type_name)
        if st.zindex is None or not hasattr(st.zindex, "state_dict"):
            return None
        out = st.zindex.state_dict()
        return out or None

    @_synchronized
    def count(self, type_name: str) -> int:
        return self._state(type_name).n

    @_synchronized
    def reindex(self, type_name: str, to_version: int | None = None):
        """Migrate the type's z-index layout to ``to_version`` (the
        WriteIndexJob / AttributeIndexJob reindex analog,
        jobs/accumulo/AttributeIndexJob; GeoMesaFeatureIndex.scala:33-35
        versioned tables): rebuild the sort orders under the new
        curve and swap them in atomically — the old index serves every
        query until the swap."""
        from ..features.sft import Configs, check_index_version
        to_version = check_index_version(to_version)
        st = self._state(type_name)
        if st.sft.index_version == to_version:
            return
        st.sft.user_data[Configs.INDEX_VERSION] = to_version
        if st.batch is None or st.n == 0:
            return
        st.dirty = True
        st.plan_cache.clear()
        st.ensure_index()  # rebuild + atomic swap

    @_synchronized
    def analyze(self, type_name: str):
        """Recompute stats from scratch (stats are additive on write and
        go stale after deletes — the reference's `stats analyze` run)."""
        st = self._state(type_name)
        self.stats.clear(type_name)
        st.plan_cache.clear()  # cached strategies used the stale stats
        if st.batch is not None and st.n:
            self.stats.observe(st.sft, st.batch)
        return self.stats.get(type_name)

    # -- materialized pushdowns (cache/ subsystem) -------------------------
    #
    # The public pushdowns are caching wrappers: canonical plan key +
    # per-type version lookup and single-flight coalescing run OUTSIDE
    # _op_lock, so repeated identical tiles cost a dict lookup and a
    # thundering herd of cold ones costs one device dispatch with zero
    # lock convoys. The _*_uncached bodies hold the synchronized
    # compute; store subclasses override those, keeping the cache on
    # every flavor.

    def density(self, type_name: str, ecql, bbox, width: int, height: int,
                weight_attr: str | None = None) -> np.ndarray:
        """Density surface (DensityScan pushdown analog): heatmap grid of
        matching features over bbox at width x height pixels."""
        from ..cache import density_key
        flt, key = density_key(ecql, bbox, width, height, weight_attr)
        return self.result_cache.get_or_compute(
            type_name, key,
            lambda: self._density_uncached(type_name, flt, bbox, width,
                                           height, weight_attr),
            encode=_grid_copy, decode=_grid_copy)

    def bin_query(self, type_name: str, ecql, track: str | None = None,
                  label: str | None = None, sort: bool = False) -> bytes:
        """BIN-format results (BinAggregatingScan analog): compact
        16/24-byte records for matching features."""
        from ..cache import bin_key
        flt, key = bin_key(ecql, track, label, sort)
        return self.result_cache.get_or_compute(
            type_name, key,
            lambda: self._bin_query_uncached(type_name, flt, track=track,
                                             label=label, sort=sort))

    def arrow_ipc(self, type_name: str, ecql="INCLUDE",
                  sort_by: str | None = None) -> bytes:
        """Arrow IPC stream of matching features, readable by
        FeatureArrowFileReader (the ARROW_ENCODE hint surface)."""
        from ..cache import arrow_key
        flt, key = arrow_key(ecql, sort_by)
        return self.result_cache.get_or_compute(
            type_name, key,
            lambda: self._arrow_ipc_uncached(type_name, flt,
                                             sort_by=sort_by))

    def stats_query(self, type_name: str, stat_spec: str,
                    ecql: str | ast.Filter = None):
        """Run a stat sketch over query results (StatsScan analog):
        returns the observed Stat. Cached in serialized-sketch form
        (stats/serialize.py) so every caller gets a private copy —
        the cluster's in-place merge cannot corrupt the original."""
        from ..cache import stats_key
        from ..stats.serialize import deserialize_stat, serialize_stat
        flt, key = stats_key(ecql, stat_spec)
        return self.result_cache.get_or_compute(
            type_name, key,
            lambda: self._stats_query_uncached(type_name, stat_spec, flt),
            encode=serialize_stat, decode=deserialize_stat)

    @_synchronized
    def _density_uncached(self, type_name: str, ecql, bbox, width: int,
                          height: int,
                          weight_attr: str | None = None) -> np.ndarray:
        from ..scan.aggregations import density_grid
        st = self._state(type_name)
        if st.batch is None or st.n == 0:
            return np.zeros((height, width), dtype=np.float32)
        res = self.query(Query(type_name, ecql))
        if res.batch is None or res.batch.n == 0:
            return np.zeros((height, width), dtype=np.float32)
        x, y, gvalid = _geom_centroids(res.batch, st.sft.geom_field)
        mask = gvalid.copy()
        w = None
        if weight_attr is not None:
            wcol = res.batch.col(weight_attr)
            w = np.where(wcol.valid, wcol.values, 0.0).astype(np.float32)
            mask &= wcol.valid
        # NaN coords on invalid rows would clip into pixel (0,0): zero them
        x = np.where(gvalid, x, bbox[0])
        y = np.where(gvalid, y, bbox[1])
        return density_grid(x, y, mask, bbox, width, height, w)

    @_synchronized
    def _bin_query_uncached(self, type_name: str, ecql,
                            track: str | None = None,
                            label: str | None = None,
                            sort: bool = False) -> bytes:
        from ..scan.aggregations import encode_bin_batch
        st = self._state(type_name)
        res = self.query(Query(type_name, ecql))
        if res.batch is None or res.batch.n == 0:
            return b""
        return encode_bin_batch(st.sft, res.ids, res.batch,
                                track=track, label=label, sort=sort)

    @_synchronized
    def arrow_query(self, type_name: str, ecql):
        """Arrow-encoded results (ArrowScan analog): a pyarrow
        RecordBatch of matching features."""
        res = self.query(Query(type_name, ecql))
        if res.batch is None:
            return None
        return res.batch.to_arrow()

    @_synchronized
    def _arrow_ipc_uncached(self, type_name: str, ecql="INCLUDE",
                            sort_by: str | None = None) -> bytes:
        # the distributed store overrides this with the shard-local
        # dictionary-delta merge
        from ..arrow.scan import ArrowScan
        return ArrowScan(self).execute(type_name, ecql, sort_by=sort_by)

    @_synchronized
    def _stats_query_uncached(self, type_name: str, stat_spec: str,
                              ecql: str | ast.Filter = None):
        # StatsScan analog (index/iterators/StatsScan.scala)
        st = self._state(type_name)
        stat = parse_stat(stat_spec)
        if st.batch is None or st.n == 0:
            return stat
        if ecql is None or isinstance(ecql, ast.Include):
            stat.observe(st.batch)
            return stat
        res = self.query(Query(type_name, ecql))
        if res.batch is not None and res.batch.n:
            stat.observe(res.batch)
        return stat

    # -- queries -----------------------------------------------------------

    def _indices(self, sft: SimpleFeatureType) -> list[str]:
        out = []
        if sft.geom_field is not None:
            if sft.is_points:
                if sft.dtg_field is not None:
                    out.append("z3")
                out.append("z2")
            else:
                if sft.dtg_field is not None:
                    out.append("xz3")
                out.append("xz2")
        out.append("id")
        for a in sft.attributes:
            if a.indexed:
                out.append(f"attr:{a.name}")
        return out

    def _plan_for(self, q: Query, st: _TypeState,
                  explain: Explainer) -> tuple[FilterStrategy,
                                               _PlanArtifacts]:
        """Plan-cache lookup (keyed on the filter object +
        strategy-relevant hints): the ECQL parse cache returns one
        shared AST per query string, so repeated queries hit here and
        skip the splitter / cost estimation / geometry extraction. The
        `is` check makes id() reuse after GC harmless."""
        pkey = (id(q.filter), q.hints.get(QueryHints.QUERY_INDEX))
        hit = st.plan_cache.get(pkey)
        if hit is not None and hit[0] is q.filter:
            strategy, art = hit[1], hit[2]
            explain(lambda: f"Plan cache hit: {strategy.index}")
        else:
            strategy = decide_strategy(st.sft, q,
                                       self._indices(st.sft), st.n,
                                       stats=self.stats.get(q.type_name),
                                       explain=explain)
            art = _PlanArtifacts()
            if len(st.plan_cache) >= 256:
                st.plan_cache.pop(next(iter(st.plan_cache)))
            st.plan_cache[pkey] = (q.filter, strategy, art)
        return strategy, art

    def _matching_rows(self, q: Query, st: _TypeState,
                       explain: Explainer):
        """The shared row-selection pipeline: plan (under the timeout
        reaper), scan, visibility, sampling. Returns (idx, strategy,
        t_plan, t_scan0, attr_mask) — attr_mask is the per-row
        attribute authorization matrix for attribute-level visibility
        schemas (None otherwise); query() materializes from it,
        query_count() just counts — one pipeline, no drift."""
        # query timeout enforcement at stage boundaries
        # (ThreadManagement analog; geomesa.query.timeout property)
        from ..utils.properties import QUERY_TIMEOUT
        managed = None
        timeout_s = q.hints.get("TIMEOUT") or QUERY_TIMEOUT.as_seconds()
        if timeout_s:
            from ..utils.threads import ManagedQuery
            managed = _REAPER.register(
                ManagedQuery(q.type_name, str(q.filter), float(timeout_s)))

        import time as _time
        try:
            with tracer.span("plan"):
                t_plan0 = _time.perf_counter()
                strategy, art = self._plan_for(q, st, explain)
                t_plan = _time.perf_counter() - t_plan0
            if managed is not None:
                managed.check()
            t_scan0 = _time.perf_counter()
            idx = self._execute(st, q, strategy, explain, art)
            if managed is not None:
                managed.check()
        finally:
            if managed is not None:
                _REAPER.complete(managed)

        idx, attr_mask = self._post_scan(q, st, idx, explain)
        return idx, strategy, t_plan, t_scan0, attr_mask

    def _post_scan(self, q: Query, st: _TypeState, idx: np.ndarray,
                   explain: Explainer):
        """Post-scan row stages shared by the scalar and batched
        pipelines: visibility filtering (row- or attribute-level) and
        statistical sampling. Returns (idx, attr_mask)."""
        attr_mask = None
        if q.auths is not None or st.has_vis:
            from ..security import evaluate_visibilities
            auths = q.auths or []
            if st.sft.visibility_level == "attribute" and st.has_vis:
                # a row survives when ANY of its attributes is
                # authorized; the mask rides along (aligned with idx)
                # so materialization nulls cells without re-parsing
                m = _attr_vis_masks(st.vis[idx],
                                    len(st.sft.attributes), auths)
                keep = m.any(axis=1)
                idx = idx[keep]
                attr_mask = m[keep]
                # leak guard: the scan matched on RAW values, but the
                # caller must not learn hidden cells through the
                # predicate (reference semantics put the visibility
                # filter BELOW the query filter). Re-evaluate on the
                # NULLED view; hidden cells compare as NULL (UNKNOWN
                # -> excluded). Deviation: IS NULL on a hidden cell
                # under-matches here (the raw scan already dropped it).
                if not attr_mask.all() \
                        and not isinstance(q.filter, ast.Include):
                    refd = ast.props_of(q.filter)
                    by_name = {a.name: j for j, a
                               in enumerate(st.sft.attributes)}
                    hidden_refd = [a for a in refd if a in by_name
                                   and not attr_mask[:, by_name[a]].all()]
                    if hidden_refd:
                        sub = st.batch.take(idx)
                        cols = dict(sub.columns)
                        for a in hidden_refd:
                            cols[a] = _null_cells(
                                sub.col(a), ~attr_mask[:, by_name[a]])
                        nulled = FeatureBatch(sub.sft, sub.ids, cols)
                        ok = np.asarray(evaluate(q.filter, nulled),
                                        dtype=bool)
                        idx = idx[ok]
                        attr_mask = attr_mask[ok]
                explain(f"Attribute-level visibility filter applied "
                        f"({len(auths)} auths)")
            else:
                # evaluate only the rows that survived the scan
                vis_ok = evaluate_visibilities(st.vis[idx], auths)
                idx = idx[vis_ok]
                explain(f"Visibility filter applied ({len(auths)} auths)")

        rate = q.hints.get(QueryHints.SAMPLING)
        if rate is not None and len(idx):
            from ..scan.aggregations import sample_mask
            by_attr = q.hints.get(QueryHints.SAMPLE_BY)
            by = None
            if by_attr is not None:
                col = st.batch.col(by_attr)
                # nulls sort as empty string (argsort needs a total order)
                by = np.array([col.value(int(i)) or "" for i in idx],
                              dtype=object).astype(str)
            smask = sample_mask(len(idx), float(rate), by)
            idx = idx[smask]
            if attr_mask is not None:
                attr_mask = attr_mask[smask]
            explain(f"Sampling applied: rate={rate}")
        return idx, attr_mask

    @_synchronized
    def query(self, q: Query | str, type_name: str | None = None,
              explain_out=None) -> QueryResult:
        if isinstance(q, str):
            if type_name is None:
                raise ValueError("type_name required with a filter string")
            q = Query(type_name, q)
        st = self._state(q.type_name)
        explain = Explainer(explain_out)
        explain.push(lambda: f"Planning '{q.type_name}' "
                             f"filter={q.filter}")
        if st.batch is None or st.n == 0:
            explain("Store is empty").pop()
            return QueryResult(np.empty(0, dtype=object), None, explain,
                               FilterStrategy("empty", None, None))
        with tracer.span("store-scan", q.type_name) as sp:
            idx, strategy, t_plan, t_scan0, attr_mask = \
                self._matching_rows(q, st, explain)
            sp.set_attr(index=strategy.index, rows=int(st.n),
                        hits=int(len(idx)))
            return self._finish_query(q, st, idx, attr_mask, strategy,
                                      explain, t_plan, t_scan0)

    def _finish_query(self, q: Query, st: _TypeState, idx: np.ndarray,
                      attr_mask, strategy: FilterStrategy,
                      explain: Explainer, t_plan: float,
                      t_scan0: float, batched: bool = False) -> QueryResult:
        """Result-assembly stages shared by the scalar and batched
        pipelines: sort, max_features, projection validation, lazy
        batch + attribute-cell redaction, id gather, audit. Runs inside
        the query's store-scan span, which the result keeps for the
        deferred id gather's link."""
        cur = tracer.current()
        scan_span = (cur[0].trace_id, cur[1].span_id) if cur else None
        with tracer.span("assemble") as sp:
            if q.sort_by is not None:
                from .common import sort_order
                hidden = None
                if attr_mask is not None:
                    # hidden sort values must not leak through the row
                    # ordering: they sort as NULL
                    aj = {a.name: j for j, a
                          in enumerate(st.sft.attributes)}.get(q.sort_by)
                    if aj is not None:
                        hidden = ~attr_mask[:, aj]
                order = sort_order(st.batch, q.sort_by, q.sort_desc, idx,
                                   hidden=hidden)
                idx = idx[order]
                if attr_mask is not None:
                    attr_mask = attr_mask[order]
            if q.max_features is not None:
                idx = idx[:q.max_features]
                if attr_mask is not None:
                    attr_mask = attr_mask[:q.max_features]

            if q.properties is not None:
                # validate projection names NOW: errors belong to query(),
                # not to whenever (or whether) .batch is first read
                missing = [p for p in q.properties
                           if p not in st.batch.columns]
                if missing:
                    raise KeyError(f"unknown propert"
                                   f"{'ies' if len(missing) > 1 else 'y'}: "
                                   f"{', '.join(missing)}")
            batch: Any = _LazyBatch(st.batch, idx, q.properties,
                                    row_order=q.sort_by is None)
            st.live_lazy.add(batch)
            if attr_mask is not None:
                # null unauthorized attribute values in the result rows
                # (KryoVisibilityRowEncoder: the row is assembled from the
                # cells the scanner's auths can see)
                m = attr_mask
                if not m.all():
                    mb = batch.materialize() if isinstance(batch, _LazyBatch) \
                        else batch
                    by_name = {a.name: j
                               for j, a in enumerate(st.sft.attributes)}
                    cols = {}
                    for a in mb.sft.attributes:
                        col = mb.col(a.name)
                        bad = ~m[:, by_name[a.name]]
                        cols[a.name] = (_null_cells(col, bad) if bad.any()
                                        else col)
                    batch = FeatureBatch(mb.sft, mb.ids, cols)
            if isinstance(batch, FeatureBatch):
                # attr-visibility path materialized already; reuse its ids
                ids = batch.ids
            elif len(idx) <= 100_000:
                # eager id gather (the result's identity), lazy columns:
                # id-only consumers — count checks, bench loops, join sides
                # — never pay the per-column copies, and .batch still
                # materializes on first read (the reference's readers are
                # lazy over their scan buffers the same way,
                # KryoBufferSimpleFeature). The result pins the immutable
                # column snapshot until dropped.
                ids = st.batch.ids[idx]
            else:
                # deferred gather against the immutable batch snapshot:
                # large results are often consumed via batch columns (or
                # only counted) and never read ids at all
                src = st.batch
                ids = (lambda: src.ids[idx])
            explain(f"Hits: {len(idx)}").pop()
            scan_s = time.perf_counter() - t_scan0
            from ..metrics import metrics as _metrics
            _metrics.observe("store.scan", scan_s,
                             labels={"type": q.type_name,
                                     "index": strategy.index or "none"})
            from ..obs.slo import slo_engine
            slo_engine.record("store.scan", ok=True, latency_s=scan_s)
            from ..audit import audit_query
            audit_query(self.audit, "memory", q.type_name, str(q.filter),
                        q.hints, t_plan * 1000, scan_s * 1000, len(idx),
                        index=strategy.index, rows_scanned=int(st.n),
                        batched=batched)
            sp.set_attr(ids_eager=not callable(ids))
            return QueryResult(ids, batch, explain, strategy, n=len(idx),
                               scan_span=scan_span)

    @_synchronized
    def query_count(self, q: Query | str,
                    type_name: str | None = None) -> int:
        """Count without materializing ids or columns: the shared
        row-selection pipeline (plan, scan, visibility, sampling, all
        under the timeout reaper), then just the length. Skips the
        object-array id gather and per-column result copies."""
        if isinstance(q, str):
            if type_name is None:
                raise ValueError("type_name required with a filter string")
            q = Query(type_name, q)
        st = self._state(q.type_name)
        if st.batch is None or st.n == 0:
            return 0
        import time as _time
        explain = Explainer()
        explain.push(lambda: f"Counting '{q.type_name}' "
                             f"filter={q.filter}")
        with tracer.span("store-scan", q.type_name) as sp:
            idx, strategy, t_plan, t_scan0, _m = \
                self._matching_rows(q, st, explain)
            n = len(idx)
            if q.max_features is not None:
                n = min(n, q.max_features)
            sp.set_attr(index=strategy.index, rows=int(st.n), hits=n)
            from ..audit import audit_query
            audit_query(self.audit, "memory", q.type_name,
                        str(q.filter), q.hints, t_plan * 1000,
                        (_time.perf_counter() - t_scan0) * 1000, n,
                        index=strategy.index, rows_scanned=int(st.n))
        return n

    @_synchronized
    def query_batched(self, queries: list[Query],
                      explain_out=None) -> list[QueryResult]:
        """Micro-batched execution: evaluate several queries with ONE
        fused device scan (the vmapped kernel in scan/zscan.py) and
        demultiplex per-query results.

        Queries whose plan cannot fuse — non-point schemas, id/attr
        strategies, secondary residual filters, exact-geometry
        predicates — fall back to the scalar pipeline individually, so
        the result list is always exactly what per-query ``query()``
        calls would return, id for id. Single-element batches pass
        through to ``query()`` untouched."""
        queries = list(queries)
        if len(queries) <= 1:
            return [self.query(q, explain_out=explain_out)
                    for q in queries]
        results: list[QueryResult | None] = [None] * len(queries)
        groups: dict[str, list[int]] = {}
        for i, q in enumerate(queries):
            groups.setdefault(q.type_name, []).append(i)
        import time as _time
        for tn, members in groups.items():
            st = self._types.get(tn)
            fused: list[int] = []
            plans: dict[int, tuple[FilterStrategy, _PlanArtifacts]] = {}
            fallback: list[int] = []
            for i in members:
                q = queries[i]
                if st is None or st.batch is None or st.n == 0:
                    fallback.append(i)
                    continue
                explain = Explainer(explain_out)
                strategy, art = self._plan_for(q, st, explain)
                ok = (strategy.index in ("z3", "z2")
                      and strategy.secondary is None)
                if ok:
                    st.ensure_index()
                    ok = st.has_point_scan()
                if ok:
                    _g, _b, _i, needs_exact, _s = \
                        self._fill_artifacts(st, strategy, art)
                    ok = not needs_exact
                if ok:
                    fused.append(i)
                    plans[i] = (strategy, art)
                else:
                    fallback.append(i)
            if len(fused) < 2:
                fallback = sorted(fallback + fused)
                fused = []
            for i in fallback:
                results[i] = self.query(queries[i],
                                        explain_out=explain_out)
            if not fused:
                continue
            t_scan0 = _time.perf_counter()
            with tracer.span("store-scan", tn) as sp:
                sp.set_attr(fused=len(fused), rows=int(st.n))
                rows_per_q = self._batched_scan_rows(
                    st, [(queries[i],) + plans[i] for i in fused])
                for i, rows in zip(fused, rows_per_q):
                    q = queries[i]
                    explain = Explainer(explain_out)
                    explain.push(lambda q=q: f"Batched '{q.type_name}' "
                                             f"filter={q.filter}")
                    idx, attr_mask = self._post_scan(q, st, rows,
                                                     explain)
                    results[i] = self._finish_query(
                        q, st, idx, attr_mask, plans[i][0], explain,
                        0.0, t_scan0, batched=True)
        return results  # type: ignore[return-value]

    def _batched_scan_rows(self, st: _TypeState, items) -> list[np.ndarray]:
        """One fused vmapped launch over the stacked queries, then a
        per-query exact boundary patch (candidates are compacted on
        device inside the same launch, so there is NO per-query O(n)
        host work). ``items`` is a list of (query, strategy, artifacts)
        whose plans were checked fusible by query_batched."""
        sqs = []
        for _q, strategy, art in items:
            if art.sq is None:
                _g, boxes, intervals, _ne, _s = \
                    self._fill_artifacts(st, strategy, art)
                art.sq = zscan.make_query(boxes, intervals)
            sqs.append(art.sq)
        bq = zscan.stack_queries(sqs)
        hits, cands = zscan.batch_hit_rows(st.scan_data, bq)
        batch = st.batch
        col = batch.col(st.sft.geom_field)
        dtg = st.sft.dtg_field
        millis = (batch.col(dtg).millis if dtg is not None
                  else np.zeros(st.n, dtype=np.int64))
        return [zscan.patch_hit_rows(rows, sq, col.x, col.y, millis, cand)
                for rows, cand, sq in zip(hits, cands, sqs)]

    def _execute(self, st: _TypeState, q: Query, strategy: FilterStrategy,
                 explain: Explainer,
                 art: "_PlanArtifacts | None" = None) -> np.ndarray:
        """Run the chosen strategy; returns sorted matching row indices.

        Index-space (not mask-space) so an index-pruned scan never pays
        O(n) host work — cost is proportional to the candidate set."""
        sft = st.sft
        n = st.n
        batch = st.batch
        if strategy.index == "empty":
            return np.empty(0, dtype=np.int64)

        if strategy.index in ("z3", "z2", "xz3", "xz2"):
            st.ensure_index()

        if strategy.index in ("z3", "z2") and st.has_point_scan():
            idx = self._device_scan(st, q, strategy, explain, art)
        elif strategy.index in ("xz3", "xz2") and st.has_extent_scan():
            idx = self._device_extent_scan(st, q, strategy, explain)
        elif strategy.index == "id" and strategy.primary is not None:
            idx = np.flatnonzero(
                np.isin(batch.ids.astype(str),
                        np.asarray(strategy.primary.ids, dtype=str)))
        elif (strategy.index.startswith("attr:")
              and strategy.primary is not None):
            idx = self._attr_scan(st, strategy, explain)
        else:
            # fullscan / attr-fallback / extent-geometry path: device
            # kernel when the primary is attribute-only (the pushed-down
            # "iterator" of the reference), else host evaluation
            if strategy.primary is None:
                idx = np.arange(n, dtype=np.int64)
            else:
                from ..scan import residual
                if residual.is_compilable(strategy.primary, batch):
                    explain(f"Device residual scan for {strategy.index}")
                    mask = residual.device_mask(strategy.primary, batch,
                                                st.device_cols())
                    idx = np.flatnonzero(np.asarray(mask))
                else:
                    explain(f"Executing host scan for {strategy.index}")
                    idx = np.flatnonzero(evaluate(strategy.primary, batch))

        if strategy.secondary is not None:
            if len(idx):
                idx = self._apply_residual(st, strategy.secondary, idx,
                                           explain)
            explain(f"Residual filter applied: {strategy.secondary}")
        return idx

    def _apply_residual(self, st: _TypeState, residual_f: ast.Filter,
                        idx: np.ndarray, explain: Explainer) -> np.ndarray:
        """Secondary-filter application: a dense device pass when the
        candidate set is a large fraction of the table (gathering would
        cost more than re-touching the column), host evaluation on the
        gathered candidates otherwise."""
        from ..scan import residual
        batch = st.batch
        with tracer.span("residual") as sp:
            if (len(idx) * 4 > st.n
                    and residual.is_compilable(residual_f, batch)):
                sp.set_attr(where="device", rows=int(len(idx)), columns=0,
                            ids=False)
                explain("Device residual scan (dense)")
                mask = np.asarray(residual.device_mask(residual_f, batch,
                                                       st.device_cols()))
                return idx[mask[idx]]
            sub = take_for(residual_f, batch, idx)
            sp.set_attr(where="host", rows=int(len(idx)),
                        columns=len(sub.columns), ids=sub.ids is not None)
            return idx[evaluate(residual_f, sub)]

    def _attr_scan(self, st: _TypeState, strategy: FilterStrategy,
                   explain: Explainer) -> np.ndarray:
        """Attribute-index scan: binary-searched candidate rows from the
        sorted column, then the exact primary on just those rows (bounds
        over-approximate e.g. non-prefix LIKE). The candidate gather is
        the positional join back to the record columns — the reference's
        attribute-index -> record-table join
        (accumulo/index/AttributeIndex.scala:386-395)."""
        from ..filters.helper import extract_attribute_bounds
        from ..index.zkeys import SCAN_BLOCK_THRESHOLD
        attr = strategy.index.split(":", 1)[1]
        aidx = st.attr_index(attr)
        rows = None
        intervals = []
        if aidx is not None:
            bounds = extract_attribute_bounds(strategy.primary, attr)
            # secondary date tiering: the residual's date bounds narrow
            # equality slices inside the (value, date) composite order
            dtg = st.sft.dtg_field
            if (dtg is not None and strategy.secondary is not None
                    and aidx.sorted_millis is not None):
                intervals = _intervals_ms(strategy.secondary, dtg,
                                          lo_unbounded=-(2 ** 62))
            max_rows = int(float(SCAN_BLOCK_THRESHOLD.get()) * st.n)
            rows = aidx.candidates(bounds, max_rows=max_rows,
                                   intervals_ms=intervals)
            # the secondary tier only engages on equality slices
            narrowed = bool(intervals) and any(
                aidx._is_point_bound(b) for b in bounds)
        if rows is None:
            from ..scan import residual
            if residual.is_compilable(strategy.primary, st.batch):
                explain(f"Attribute bounds too wide; dense device scan "
                        f"for {strategy.index}")
                mask = residual.device_mask(strategy.primary, st.batch,
                                            st.device_cols())
                return np.flatnonzero(np.asarray(mask))
            explain(f"Attribute bounds not range-scannable; "
                    f"host scan for {strategy.index}")
            return np.flatnonzero(evaluate(strategy.primary, st.batch))
        explain(f"Attribute index scan: {len(rows)} candidate row(s) "
                f"of {st.n}" + (" (date-narrowed)" if narrowed else ""))
        if not len(rows):
            return rows
        keep = evaluate(strategy.primary,
                        take_for(strategy.primary, st.batch, rows))
        return rows[keep]

    def _fill_artifacts(self, st: _TypeState, strategy: FilterStrategy,
                        art: "_PlanArtifacts | None"):
        """Derive (and cache on the plan artifacts) the scan-shaped
        view of a strategy's primary filter: query geometries, their
        envelopes, time intervals, and whether an exact geometry
        residual is needed."""
        sft = st.sft
        primary = (strategy.primary if strategy.primary is not None
                   else ast.Include())
        if art is not None and art.filled:
            return (art.geoms, art.boxes, art.intervals,
                    art.needs_exact, art.spatial_f)
        geom = sft.geom_field
        dtg = sft.dtg_field
        geoms = extract_geometries(primary, geom)
        boxes = [g.envelope.as_tuple() for g in geoms] or \
            [(-180.0, -90.0, 180.0, 90.0)]
        intervals = (_intervals_ms(primary, dtg)
                     if dtg is not None and strategy.index == "z3"
                     else [])
        needs_exact = _needs_exact(geoms, primary)
        spatial_f = (_spatial_only(primary, geom) if needs_exact
                     else None)
        if art is not None:
            art.geoms, art.boxes = geoms, boxes
            art.intervals = intervals
            art.needs_exact, art.spatial_f = needs_exact, spatial_f
            art.filled = True
        return geoms, boxes, intervals, needs_exact, spatial_f

    def _device_scan(self, st: _TypeState, q: Query,
                     strategy: FilterStrategy, explain: Explainer,
                     art: "_PlanArtifacts | None" = None) -> np.ndarray:
        """The hot path: z-range index pruning -> the dense z3 pass +
        exact boundary patch + non-envelope geometry residual. Returns
        sorted row indices."""
        sft = st.sft
        batch = st.batch
        geom = sft.geom_field
        primary = strategy.primary if strategy.primary is not None else ast.Include()
        geoms, boxes, intervals, needs_exact, spatial_f = \
            self._fill_artifacts(st, strategy, art)

        # z-range pruning (Z3IndexKeySpace.getRanges analog): the host
        # fast path resolves selective queries EXACTLY inside the index
        # (sequential passes over sorted-order coordinate copies); wider
        # candidate sets fall to the candidate tier, and beyond the block
        # threshold to the dense tier, which both run the dense pass. One
        # decomposition serves all tiers (zkeys.search_rows).
        from ..index.zkeys import SCAN_BLOCK_THRESHOLD, search_rows
        block_cap = int(float(SCAN_BLOCK_THRESHOLD.get()) * st.n)
        host_cap = min(block_cap, int(HOST_SCAN_ROWS.get()))
        with tracer.span("index-search") as sp:
            kind, res_rows = search_rows(st.zindex, strategy.index, boxes,
                                         intervals, host_cap, block_cap)
            sp.set_attr(outcome=kind or "dense",
                        rows=0 if res_rows is None else int(len(res_rows)))
        idx_exact = res_rows if kind == "exact" else None
        rows = res_rows if kind == "candidates" else None

        if idx_exact is not None:
            # selective query resolved exactly inside the index: no
            # two-float machinery, no boundary patch, no device round
            # trip — the reference's tablet-local iterator work as one
            # sequential pass (zkeys.ZKeyIndex.query_rows)
            explain(f"Index-pruned host scan: {len(idx_exact)} hit(s) "
                    f"of {st.n}, {len(boxes)} box(es), "
                    f"{len(intervals)} interval(s)")
            idx = idx_exact
        else:
            # the two-float device query struct is only needed by the
            # kernel tiers; the exact host tier above never builds it
            sq = art.sq if art is not None and art.sq is not None \
                else zscan.make_query(boxes, intervals)
            if art is not None:
                art.sq = sq
            if rows is not None:
                idx = self._scan_gathered(st, sq, rows, explain,
                                          len(boxes), len(intervals))
            else:
                idx = self._scan_dense(st, sq, explain,
                                       len(boxes), len(intervals))

        # non-envelope query geometries need the exact predicate too
        if needs_exact:
            if len(idx):
                if spatial_f is not None:
                    col = batch.col(geom)
                    keep = self._pip_residual(spatial_f, col, idx, explain)
                    if keep is None:
                        keep = evaluate(spatial_f,
                                        take_for(spatial_f, batch, idx))
                    idx = idx[keep]
            explain("Exact geometry predicate applied")
        return idx

    def _patch_mask(self, st: _TypeState, codes: np.ndarray,
                    sq: zscan.ScanQuery, explain: Explainer) -> np.ndarray:
        """Exact f64 recheck of the rows the dense pass flagged (bit 1 of
        the codes of ``_dense_mask``: the row's hi-cell touches a query
        bound), written into the full-table codes: their nonzero rows are
        then the exact hits."""
        cand = np.flatnonzero(codes > 1)
        tracer.current_span().set_attr(checked=int(len(cand)))
        if not len(cand):
            return codes
        batch = st.batch
        dtg = st.sft.dtg_field
        col = batch.col(st.sft.geom_field)
        millis = (batch.col(dtg).millis if dtg is not None
                  else np.zeros(st.n, dtype=np.int64))
        explain(f"Boundary recheck: {len(cand)} candidate(s)")
        # the exact verdict overwrites the whole code: flagged rows
        # become 0 or 1
        return zscan.exact_patch(codes, cand, col.x, col.y, millis, sq)

    def _patched_rows(self, st: _TypeState, codes: np.ndarray,
                      sq: zscan.ScanQuery, explain: Explainer) -> np.ndarray:
        """The exact patch of the dense pass's codes and its sorted hit
        rows."""
        with tracer.span("boundary-patch"):
            return zscan.hit_rows(self._patch_mask(st, codes, sq, explain))

    @staticmethod
    def _dense_mask(st: _TypeState,
                    sq: zscan.ScanQuery) -> tuple[np.ndarray, int]:
        """The z3 pass over every row: (host codes[n], rows scanned). One
        uint8 code a scanned row comes down: bit 0 the two-float verdict,
        bit 1 the boundary flag."""
        codes = np.asarray(zscan.scan_codes(st.scan_data, sq))[:st.n]
        return codes, int(st.scan_data.cap)

    def _dense_tier(self, st: _TypeState, sq: zscan.ScanQuery,
                    explain: Explainer, span: str, kind: str,
                    attrs) -> np.ndarray:
        """Both one-chip device tiers: the dense pass inside the tier's
        ``span``, noted as a ``kind`` scan dispatch, the span's
        ``attrs(rows scanned)``, then the patched hit rows."""
        with tracer.span(span) as sp:
            t0 = time.perf_counter()
            codes, scanned = self._dense_mask(st, sq)
            runtime.note_dispatch("scan", (kind, scanned),
                                  time.perf_counter() - t0,
                                  d2h_bytes=scanned)
            sp.set_attr(**attrs(scanned))
        return self._patched_rows(st, codes, sq, explain)

    def _scan_gathered(self, st: _TypeState, sq: zscan.ScanQuery,
                       rows: np.ndarray, explain: Explainer,
                       nb: int, ni: int) -> np.ndarray:
        """Index-pruned candidate tier: the dense tier's pass and patch.
        Rows outside the index's candidates are outside the query in
        exact f64, so the patched codes are the exact answer over the
        candidates too, with no gather of random rows on the device or
        the host."""
        explain(f"Index-pruned device scan: {len(rows)} candidate "
                f"row(s) of {st.n}, {nb} box(es), {ni} interval(s)")
        m = len(rows)
        if not m:
            with tracer.span("gather-scan") as sp:
                sp.set_attr(candidates=0, padded=0, h2d_bytes=0,
                            d2h_bytes=0)
            return np.zeros(0, dtype=np.int64)
        return self._dense_tier(
            st, sq, explain, "gather-scan", "gathered",
            lambda k: dict(candidates=m, padded=k, h2d_bytes=0,
                           d2h_bytes=k))

    def _scan_dense(self, st: _TypeState, sq: zscan.ScanQuery,
                    explain: Explainer, nb: int, ni: int) -> np.ndarray:
        """Dense full-batch tier: the dense pass + full-table boundary
        patch."""
        explain(f"Device scan: {nb} box(es), {ni} interval(s), n={st.n}")
        return self._dense_tier(
            st, sq, explain, "dense-scan", "dense",
            lambda k: dict(rows=int(st.n), d2h_bytes=k))

    def _device_extent_scan(self, st: _TypeState, q: Query,
                            strategy: FilterStrategy,
                            explain: Explainer) -> np.ndarray:
        """XZ-index analog for extent geometries: device bbox tristate
        (definite in / definite out / boundary band), exact host
        predicate only on the band — the per-candidate JTS evaluation
        of the reference's XZ scans (curve/XZ2SFC.scala:146-252 ranges
        + server-side exact filter)."""
        sft = st.sft
        batch = st.batch
        geom = sft.geom_field
        dtg = sft.dtg_field
        primary = (strategy.primary if strategy.primary is not None
                   else ast.Include())

        geoms = extract_geometries(primary, geom)
        boxes = [g.envelope.as_tuple() for g in geoms] or \
            [(-180.0, -90.0, 180.0, 90.0)]
        intervals = (_intervals_ms(primary, dtg)
                     if dtg is not None and strategy.index == "xz3" else [])

        # XZ-key pruning (XZ2/XZ3IndexKeySpace analog): selective
        # queries evaluate only the candidate extents, exactly, on host
        from ..index.zkeys import SCAN_BLOCK_THRESHOLD, prune_candidates
        max_rows = min(int(float(SCAN_BLOCK_THRESHOLD.get()) * st.n),
                       int(EXTENT_HOST_SCAN_ROWS.get()))
        rows = prune_candidates(st.zindex, strategy.index, boxes,
                                intervals, max_rows)
        if rows is not None:
            explain(f"XZ-pruned host scan: {len(rows)} candidate "
                    f"row(s) of {st.n}")
            if not len(rows):
                return rows
            keep = evaluate(primary, take_for(primary, batch, rows))
            return np.sort(rows[keep])

        eq = gscan.extent_query(boxes, intervals)
        state = self._extent_states(st, eq)
        explain(f"Device extent scan: {len(boxes)} box(es), "
                f"{len(intervals)} interval(s), n={st.n}")

        mask = state == 2  # definite IN
        needs_exact = _needs_exact(geoms, primary)
        spatial_f = _spatial_only(primary, geom)
        if needs_exact:
            # envelope containment only proves envelope intersection;
            # the true predicate needs every surviving candidate checked
            check = np.flatnonzero(state >= 1)
        else:
            check = np.flatnonzero(state == 1)  # MAYBE band only
        if spatial_f is not None and len(check):
            keep = evaluate(spatial_f, take_for(spatial_f, batch, check))
            if needs_exact:
                mask = np.zeros(st.n, dtype=bool)
            mask = mask.copy()
            mask[check[keep]] = True
            explain(f"Exact predicate on {len(check)} candidate(s)")
        elif spatial_f is None:
            # no spatial constraint (pure time query on xz3): every
            # non-OUT row matches
            mask = state >= 1
        return np.flatnonzero(mask)

    def _extent_states(self, st: _TypeState,
                       eq: "gscan.ExtentQuery") -> np.ndarray:
        return gscan.extent_tristate(st.extent_data, eq)

    def _pip_residual(self, spatial_f, col, candidates: np.ndarray,
                      explain: Explainer):
        """Device point-in-polygon for the exact residual when the data
        are points and the query is a single polygon intersects/within
        (the ST_Contains hot loop; SURVEY §7 hard part (b)). Returns a
        bool[len(candidates)] keep mask, or None if not applicable."""
        from ..geometry.base import MultiPolygon, Polygon
        if not isinstance(col, PointColumn):
            return None
        if not isinstance(spatial_f, (ast.Intersects, ast.Within)):
            return None
        g = spatial_f.geom
        if not isinstance(g, (Polygon, MultiPolygon)):
            return None
        if len(candidates) < _DEVICE_PIP_ROWS:
            # a device dispatch costs a dispatch and a host fetch; the
            # vectorized host crossing-number test clears small
            # candidate sets sooner — the selective ST_Contains hot
            # loop stays host-side
            return None
        px = col.x[candidates]
        py = col.y[candidates]
        inside, band_idx = gscan.points_in_polygon_device(
            px, py, gscan.pack_polygon(g))
        if len(band_idx):
            # exact open/closed boundary semantics via the reference
            # evaluator on just the band rows
            sub = self._batch_rows_for(col, px[band_idx], py[band_idx])
            inside[band_idx] = evaluate(spatial_f, sub)
        explain(f"Device point-in-polygon residual "
                f"({len(candidates)} candidates, {len(band_idx)} band)")
        return inside

    @staticmethod
    def _batch_rows_for(col: PointColumn, x: np.ndarray, y: np.ndarray):
        """A minimal single-column FeatureBatch view for band rechecks."""
        sft = parse_spec("band", f"*{col.name}:Point:srid=4326")
        ids = np.array([str(i) for i in range(len(x))], dtype=object)
        return FeatureBatch(sft, ids,
                            {col.name: PointColumn(
                                col.name, x, y,
                                np.ones(len(x), dtype=bool))})


def _geom_centroids(batch: FeatureBatch, geom_field: str):
    """(x, y, valid) for any geometry column: point coords, or envelope
    centroids for extent geometries."""
    col = batch.col(geom_field)
    if isinstance(col, PointColumn):
        return col.x, col.y, col.valid
    bounds = col.bounds
    x = (bounds[:, 0] + bounds[:, 2]) / 2
    y = (bounds[:, 1] + bounds[:, 3]) / 2
    return x, y, col.valid


def _intervals_ms(primary: ast.Filter, dtg: str,
                  lo_unbounded: int = 0) -> list[tuple[int, int]]:
    """Extract inclusive [lo, hi] epoch-millis intervals for the device
    kernels, applying the reference's exclusive-bound adjustment
    (FilterHelper.scala:267-307 rounding semantics). ``lo_unbounded``
    is the open-lower sentinel: 0 for the z3 kernels (the index domain
    floor), a large negative for raw-millis consumers (pre-epoch dates
    are representable there)."""
    from ..filters.helper import to_millis as _to_millis
    out = []
    for b in extract_intervals(primary, dtg):
        lo = _to_millis(b.lower.value) if b.lower.is_bounded \
            else lo_unbounded
        hi = _to_millis(b.upper.value) if b.upper.is_bounded else 2**62
        if b.lower.is_bounded and not b.lower.inclusive:
            lo += 1
        if b.upper.is_bounded and not b.upper.inclusive:
            hi -= 1
        out.append((lo, hi))
    return out


def _needs_exact(geoms, primary: ast.Filter) -> bool:
    """True when the bbox prefilter is insufficient and the exact
    geometry predicate must run on surviving candidates."""
    return any(not _is_envelope(g) for g in geoms) or any(
        isinstance(c, (ast.DWithin, ast.SpatialPredicate))
        for c in ast.walk(primary))


def _is_envelope(g) -> bool:
    from ..filters.helper import _is_box
    from ..geometry import Polygon
    return isinstance(g, Polygon) and not g.holes and _is_box(g)


def _spatial_only(f: ast.Filter, geom: str) -> ast.Filter | None:
    from ..index.splitter import spatial_part
    spatial, _ = spatial_part(f, geom)
    return spatial


def _project_sft(sft: SimpleFeatureType, props: list[str]) -> SimpleFeatureType:
    return SimpleFeatureType(
        sft.type_name, [a for a in sft.attributes if a.name in props],
        sft.user_data)
