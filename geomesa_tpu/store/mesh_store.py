"""Mesh-distributed datastore: the multi-chip execution tier.

One engine, two execution tiers: this store IS the single-device
engine (it subclasses InMemoryDataStore, inheriting the planner,
attribute strategies, visibility filtering, deletes, residual
compilation, LSM writes and the host z-key fast path), with the
*device* tier swapped out — hot columns live as mesh-sharded segments
and wide scans fan out shard-locally with ICI reduces. That mirrors
the reference, where a single ``GeoMesaDataStore`` runs the full query
surface over every distributed backend
(/root/reference/geomesa-index-api/src/main/scala/org/locationtech/
geomesa/index/geotools/GeoMesaDataStore.scala:38, with backends
plugging in through IndexAdapter.scala:24-102 — here the "adapter" is
the small set of scan-tier hooks this subclass overrides).

Execution tiers per query (same policy as the single-device store):

- selective queries resolve EXACTLY inside the host z-key index
  (index-space candidates, never an O(n) mask);
- mid-size candidate sets evaluate exactly on host over just the
  gathered candidate rows;
- wide scans run the fused kernel shard-locally on every device
  (shard_map) with the exact f64 boundary patch on the gathered
  verdict; counts/density/histograms reduce over ICI with psum and
  never materialize row sets at all.

Writes are LSM-style at BOTH levels: host appends buffer and merge
into the sorted z-key index incrementally, and the device tier appends
delta-sized sharded SEGMENTS (re-shard cost proportional to the burst,
the minor-compaction shape); segments compact into one when they pile
up. The reference gets the same write path from BatchWriter mutations
merging into tablets at minor compaction.
"""

from __future__ import annotations

import numpy as np

from ..features.sft import SimpleFeatureType
from ..filters import ast
from ..filters.helper import extract_geometries
from ..index.api import Explainer, Query, QueryHints
from ..obs import tracer
from ..parallel import (DistributedScanData, data_mesh, distributed_count,
                        distributed_density, distributed_histogram,
                        distributed_knn, distributed_tristate,
                        exact_hit_rows, shard_extent_data,
                        shard_points_split, shard_scan_data)
from ..scan import zscan
from .memory import (HOST_SCAN_ROWS, InMemoryDataStore, _TypeState,
                     _geom_centroids, _intervals_ms, _needs_exact)

__all__ = ["DistributedDataStore"]

# segment count that triggers compaction (one full re-shard): bounds
# per-query scan dispatches while keeping write bursts O(delta)
MAX_SEGMENTS = 8


class _MeshTypeState(_TypeState):
    """Per-type state whose device tier is a list of mesh-sharded
    segments (LSM runs): writes append delta-sized segments, reads scan
    every segment, compaction re-shards into one."""

    def __init__(self, sft: SimpleFeatureType, mesh):
        super().__init__(sft)
        self.mesh = mesh
        self.segments: list[DistributedScanData] = []
        self.ext_segments: list = []   # DistributedExtentData runs
        self._knn_splits: list = []    # per-segment two-float shards

    # -- device-tier hooks -------------------------------------------------

    def has_point_scan(self) -> bool:
        return bool(self.segments)

    def has_extent_scan(self) -> bool:
        return bool(self.ext_segments)

    def _clear_device_index(self):
        self.segments = []
        self.ext_segments = []
        self._knn_splits = []

    def _build_point_index(self, x, y, millis):
        self.segments = [shard_scan_data(x, y, millis, self.mesh)]
        self.ext_segments = []
        self._knn_splits = [None]

    def _build_extent_index(self, bounds, millis):
        self.ext_segments = [shard_extent_data(bounds, millis, self.mesh)]
        self.segments = []
        self._knn_splits = []

    def _extend_device_index(self, col, dmillis) -> bool:
        """Write burst -> one new delta-sized sharded segment (cost
        proportional to the delta); False once MAX_SEGMENTS runs have
        piled up, leaving the state dirty so the next read compacts
        (full re-shard)."""
        if len(self.segments) >= MAX_SEGMENTS:
            return False
        self.segments.append(
            shard_scan_data(col.x, col.y, dmillis, self.mesh))
        self._knn_splits.append(None)
        return True

    def segment_offsets(self) -> list[int]:
        offs = [0]
        for seg in self.segments:
            offs.append(offs[-1] + seg.n)
        return offs


class DistributedDataStore(InMemoryDataStore):
    """Full-featured datastore sharded over a device mesh — the scale
    tier for 100M+-row tables (BASELINE.md north-star shape), with the
    complete single-device query surface."""

    def __init__(self, mesh=None, audit=None):
        super().__init__(audit=audit)
        self.mesh = mesh if mesh is not None else data_mesh()

    def _new_state(self, sft: SimpleFeatureType) -> _MeshTypeState:
        return _MeshTypeState(sft, self.mesh)

    @staticmethod
    def _prewarm_join(st):
        """No ingest-time join prewarm: the single-device join kernels it
        compiles would take an unsharded copy of every coordinate onto
        the mesh's first device, while this store's device tier is the
        sharded segments (KNN runs as distributed_knn)."""

    # -- scan tiers over the sharded segments ------------------------------

    def _scan_gathered(self, st: _MeshTypeState, sq: zscan.ScanQuery,
                       rows: np.ndarray, explain: Explainer,
                       nb: int, ni: int) -> np.ndarray:
        """Candidate sets between the host cap and the block threshold
        evaluate exactly on host in f64 over just the gathered rows —
        index-space work, never an O(n) mask. (A cross-shard device
        gather would pay an all-gather of the candidate set for no
        arithmetic advantage at this tier.)"""
        explain(f"Index-pruned host candidate scan: {len(rows)} "
                f"candidate row(s) of {st.n}, {nb} box(es), "
                f"{ni} interval(s)")
        from ..index.zkeys import ZKeyIndex
        col = st.batch.col(st.sft.geom_field)
        intervals = [] if sq.time_any else \
            [tuple(iv) for iv in sq.host_intervals]
        ms = (st.batch.col(st.sft.dtg_field).millis
              if intervals else None)
        boxes = [tuple(b) for b in sq.host_boxes]
        with tracer.span("host-candidates") as sp:
            keep = ZKeyIndex._eval_sorted(col.x, col.y, ms, rows, boxes,
                                          intervals)
            hits = np.sort(rows[keep])
            sp.set_attr(rows=int(len(rows)), hits=int(len(hits)))
        return hits

    def _scan_dense(self, st: _MeshTypeState, sq: zscan.ScanQuery,
                    explain: Explainer, nb: int, ni: int) -> np.ndarray:
        """Dense tier: the fused kernel shard-locally on every device,
        per segment, compacted ON DEVICE to hit row ids (count-then-
        allocate; O(hits) host work, never a full-length mask) with the
        exact f64 boundary patch applied in row-set space."""
        explain(f"Distributed scan over {self.mesh.devices.size} "
                f"device(s), {len(st.segments)} segment(s), n={st.n}, "
                f"{nb} box(es), {ni} interval(s)")
        return exact_hit_rows(st.segments, sq)

    def _batched_scan_rows(self, st: _MeshTypeState,
                           items) -> list[np.ndarray]:
        """Micro-batched dense tier over the sharded segments: ONE
        shard-mapped launch per segment evaluates every query in the
        batch (parallel/mesh.batch_exact_hit_rows), replacing the
        per-query dispatch of the scalar path."""
        from ..parallel.mesh import batch_exact_hit_rows
        sqs = []
        for _q, strategy, art in items:
            if art.sq is None:
                _g, boxes, intervals, _ne, _s = \
                    self._fill_artifacts(st, strategy, art)
                art.sq = zscan.make_query(boxes, intervals)
            sqs.append(art.sq)
        bq = zscan.stack_queries(sqs)
        offs = st.segment_offsets()[:-1]
        per_query: list[list[np.ndarray]] = [[] for _ in sqs]
        for seg, off in zip(st.segments, offs):
            for j, rows in enumerate(batch_exact_hit_rows(seg, bq)):
                per_query[j].append(rows + off)
        return [np.concatenate(parts) if parts
                else np.empty(0, dtype=np.int64)
                for parts in per_query]

    def _extent_states(self, st: _MeshTypeState, eq) -> np.ndarray:
        return np.concatenate([distributed_tristate(seg, eq)
                               for seg in st.ext_segments])

    # -- aggregate pushdown (psum over ICI) --------------------------------

    def _psum_plan(self, st: _MeshTypeState, q: Query):
        """(strategy, boxes, intervals) when the plan's result is fully
        decided by the shard-local kernel — pure z envelope scan, no
        residual, no visibility, no sampling/limit stages — else None
        (caller takes the shared row pipeline)."""
        from ..index.planner import decide_strategy
        strategy = decide_strategy(st.sft, q, self._indices(st.sft), st.n,
                                   stats=self.stats.get(q.type_name),
                                   explain=Explainer())
        primary = (strategy.primary if strategy.primary is not None
                   else ast.Include())
        geoms = extract_geometries(primary, st.sft.geom_field)
        if (strategy.index not in ("z2", "z3")
                or strategy.secondary is not None
                or _needs_exact(geoms, primary)
                or st.has_vis or q.auths is not None
                or q.hints.get(QueryHints.SAMPLING) is not None
                or q.max_features is not None):
            return None
        boxes = [g.envelope.as_tuple() for g in geoms] or \
            [(-180.0, -90.0, 180.0, 90.0)]
        intervals = (_intervals_ms(primary, st.sft.dtg_field)
                     if st.sft.dtg_field is not None
                     and strategy.index == "z3" else [])
        return strategy, boxes, intervals

    def query_count(self, q: Query | str,
                    type_name: str | None = None) -> int:
        """Counts never materialize row sets on the dense tier: the
        selective path counts inside the host z-key index; wide
        psum-eligible scans reduce over ICI (server-side aggregate ->
        client reduce, SURVEY §2.5#5) with the exact host boundary
        adjustment. Every other plan shape takes the shared pipeline."""
        if isinstance(q, str):
            if type_name is None:
                raise ValueError("type_name required with a filter string")
            q = Query(type_name, q)
        st = self._state(q.type_name)
        if st.n == 0:
            return 0
        st.ensure_index()
        plan = self._psum_plan(st, q) if st.segments else None
        if plan is None:
            return super().query_count(q)
        strategy, boxes, intervals = plan
        import time as _time
        t0 = _time.perf_counter()
        from ..index.zkeys import SCAN_BLOCK_THRESHOLD, search_rows
        host_cap = min(int(float(SCAN_BLOCK_THRESHOLD.get()) * st.n),
                       int(HOST_SCAN_ROWS.get()))
        kind, rows = search_rows(st.zindex, strategy.index, boxes,
                                 intervals, host_cap, host_cap)
        if kind == "exact":
            n = len(rows)
        else:
            sq = zscan.make_query(boxes, intervals)
            n = sum(distributed_count(seg, sq) for seg in st.segments)
        from ..audit import audit_query
        audit_query(self.audit, "mesh", q.type_name, str(q.filter),
                    q.hints, 0.0, (_time.perf_counter() - t0) * 1000, n,
                    index=strategy.index, rows_scanned=int(st.n))
        return n

    def _density_uncached(self, type_name: str, ecql, bbox, width: int,
                          height: int,
                          weight_attr: str | None = None) -> np.ndarray:
        """Heatmap grid: shard-local scatter-add psum-merged over ICI
        (DensityScan -> client-reduce shape) for psum-eligible plans;
        the shared host-binned path otherwise. (The public ``density``
        wrapper in the base class adds the materialized-result cache.)"""
        st = self._state(type_name)
        if st.n == 0 or weight_attr is not None:
            return super()._density_uncached(type_name, ecql, bbox, width,
                                             height, weight_attr)
        st.ensure_index()
        q = Query(type_name, ecql)
        plan = self._psum_plan(st, q) if st.segments else None
        if plan is None:
            return super()._density_uncached(type_name, ecql, bbox, width,
                                             height, weight_attr)
        _, boxes, intervals = plan
        sq = zscan.make_query(boxes, intervals)
        grid = np.zeros((height, width), dtype=np.float32)
        for seg in st.segments:
            grid += distributed_density(seg, sq, bbox, width, height)
        return grid

    def histogram(self, type_name: str, attribute: str, nbins: int,
                  lo: float, hi: float) -> np.ndarray:
        """Distributed attribute histogram: shard-local bincount merged
        over ICI with psum (StatsCombiner merge analog)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        st = self._state(type_name)
        if st.n == 0:
            return np.zeros(nbins, dtype=np.int64)
        vals = st.batch.col(attribute)
        v = np.asarray(getattr(vals, "values", getattr(vals, "millis", None)),
                       np.float64)
        k = self.mesh.devices.size
        n_padded = ((st.n + k - 1) // k) * k
        vp = np.full(n_padded, np.nan, np.float32)
        vp[: st.n] = v
        m = np.zeros(n_padded, dtype=bool)
        m[: st.n] = np.asarray(vals.valid)
        # host arrays straight to the sharding: a jnp.asarray first would
        # build the whole column on the default device (chip 0)
        sh = NamedSharding(self.mesh, P("data"))
        return distributed_histogram(jax.device_put(vp, sh),
                                     jax.device_put(m, sh),
                                     self.mesh, nbins, lo, hi)

    def _arrow_ipc_uncached(self, type_name: str, ecql="INCLUDE",
                            sort_by: str | None = None) -> bytes:
        """Distributed Arrow output (DeltaWriter.scala:47,203 shape):
        the row-selection pipeline runs once, matched rows split along
        the mesh's shard boundaries, every shard encodes ITS rows as an
        IPC payload with shard-local dictionaries, and the payloads
        merge into one stream with global dictionaries
        (arrow/scan.merge_deltas). On hardware the per-shard encode is
        host work against that device's row range — the client-side
        reduce of the reference's server-side ArrowScan."""
        from ..arrow.io import sort_batches, write_ipc
        from ..arrow.scan import merge_deltas
        from ..features.batch import FeatureBatch
        from ..index.api import Query as _Q
        from .memory import _null_cells
        st = self._state(type_name)
        sft = st.sft
        if st.batch is None or st.n == 0:
            return merge_deltas([], sft=sft, sort_by=sort_by)
        q = ecql if isinstance(ecql, _Q) else _Q(type_name, ecql)
        idx, _strategy, _tp, _ts, attr_mask = self._matching_rows(
            q, st, Explainer())
        if not len(idx):
            return merge_deltas([], sft=sft, sort_by=sort_by)
        # matched ORIGINAL row ids split at the mesh's shard
        # boundaries (rows shard evenly in row order): each shard
        # encodes its own rows with shard-local dictionaries
        k = self.mesh.devices.size
        per = (st.n + k - 1) // k
        shard_of = np.minimum(idx // max(per, 1), k - 1)
        payloads = []
        for s in np.unique(shard_of):
            sel = shard_of == s
            sub = st.batch.take(idx[sel])
            if attr_mask is not None and not attr_mask[sel].all():
                # same cell-level redaction as query(): unauthorized
                # attribute values must not leak through the Arrow
                # surface (KryoVisibilityRowEncoder semantics)
                m = attr_mask[sel]
                cols = {}
                for j, a in enumerate(sft.attributes):
                    col = sub.col(a.name)
                    bad = ~m[:, j]
                    cols[a.name] = (_null_cells(col, bad) if bad.any()
                                    else col)
                sub = FeatureBatch(sft, sub.ids, cols)
            if sort_by:
                # shard-local sort so the client reduce is a streaming
                # k-way merge instead of a concat-then-sort (the
                # reference's tablets return sorted batches too)
                sub = sort_batches(sub, sort_by)
            payloads.append(write_ipc(sft, sub))
        return merge_deltas(payloads, sft=sft, sort_by=sort_by,
                            presorted=True)

    def knn(self, type_name: str, qx: float, qy: float, k: int) -> np.ndarray:
        """k nearest feature ids: shard-local top-k prune per segment
        (candidates travel with their two-float coords), exact f64
        re-rank across segment candidates on host."""
        st = self._state(type_name)
        if st.n == 0:
            return np.empty(0, dtype=object)
        if st.sft.geom_field is None:
            raise ValueError("knn requires a geometry field")
        st.ensure_index()
        if not st.segments:
            # extent types: exact centroid ranking on host
            x, y, valid = _geom_centroids(st.batch, st.sft.geom_field)
            d2 = np.where(valid, (x - qx) ** 2 + (y - qy) ** 2, np.inf)
            return st.batch.ids[np.argsort(d2, kind="stable")[:k]]
        col = st.batch.col(st.sft.geom_field)
        offs = st.segment_offsets()
        cands = []
        for i in range(len(st.segments)):
            split = st._knn_splits[i]
            if split is None:
                lo, hi = offs[i], offs[i + 1]
                split = shard_points_split(col.x[lo:hi], col.y[lo:hi],
                                           self.mesh)
                st._knn_splits[i] = split
            sp, valid, n = split
            idx = distributed_knn(None, None, valid, self.mesh, n,
                                  qx, qy, k, split=sp)
            cands.append(np.asarray(idx, dtype=np.int64) + offs[i])
        cand = np.concatenate(cands)
        d2 = (col.x[cand] - qx) ** 2 + (col.y[cand] - qy) ** 2
        order = np.argsort(d2, kind="stable")
        return st.batch.ids[cand[order][:k]]
