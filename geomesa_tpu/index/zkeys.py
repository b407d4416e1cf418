"""Host-side sorted z-key index: query ranges -> candidate rows.

The TPU analog of the reference's key-range pruning: the reference sorts
rows by ``[2-byte time bin][8-byte z3]`` in the backing table and turns a
query into covering key ranges (Z3IndexKeySpace.getRanges,
geomesa-index-api/.../index/z3/Z3IndexKeySpace.scala:121-136, delegating
to Z3SFC.ranges / sfcurve zranges), so scans touch only intersecting
tablets.  Here the *device* columns stay in insertion order (a gather is
order-agnostic on TPU); what is sorted is a **host-side key array +
permutation**.  Planning a query:

    boxes + time intervals
      -> per-bin z ranges (curves/zranges.py divide-and-conquer)
      -> binary search into the sorted keys (np.searchsorted)
      -> candidate row positions -> original row ids via the permutation

The candidate set is a strict over-approximation of the true matches
(range decomposition over-covers, exactly like the reference, which
re-checks every row server-side with Z3Filter); the fused device kernel's
mask is then read at just the candidate rows, and only they are patched.
When the candidate set is a large fraction of the table the store falls
back to the full-batch scan and patch, without a candidate list (the
cost crossover the reference handles with
``QueryProperties.SCAN_RANGES_TARGET`` coarsening).

Index build is lazy per curve (z3 and z2 orders are built on first use,
the two "tables" of the reference's Z3Index/Z2Index).
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ..curves import timebin
from ..curves.sfc import z2sfc, z3sfc
from ..curves.timebin import TimePeriod
from ..utils.properties import SystemProperty

__all__ = ["ZKeyIndex", "multi_arange", "prune_candidates",
           "SCAN_BLOCK_THRESHOLD"]

# candidate-fraction above which an indexed scan falls back to the dense
# full-batch kernel (gather cost crossover)
SCAN_BLOCK_THRESHOLD = SystemProperty("geomesa.scan.index.threshold", "0.4")


def multi_arange(starts: np.ndarray, stops: np.ndarray) -> np.ndarray:
    """Concatenate ``arange(starts[i], stops[i])`` without a Python loop.

    Standard cumsum trick: one output cell per emitted integer, seeded
    with jumps at segment starts.
    """
    starts = np.asarray(starts, dtype=np.int64)
    stops = np.asarray(stops, dtype=np.int64)
    counts = stops - starts
    keep = counts > 0
    starts, counts = starts[keep], counts[keep]
    if len(starts) == 0:
        return np.empty(0, dtype=np.int64)
    total = int(counts.sum())
    out = np.ones(total, dtype=np.int64)
    ends = np.cumsum(counts)
    out[0] = starts[0]
    out[ends[:-1]] = starts[1:] - (starts[:-1] + counts[:-1] - 1)
    return np.cumsum(out)


_native_sort = None  # None = unprobed, False = unavailable


def _native_sort_lib():
    """ctypes handle to the native sort (native/src/zsort.cpp): counting
    sort by bin + per-segment pair sort, replacing two indirect
    O(N log N) argsorts in np.lexsort. Tie order matches lexsort."""
    global _native_sort
    if _native_sort is False:
        return None
    if _native_sort is None:
        import ctypes
        from ..native import symbols
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        dp = ctypes.POINTER(ctypes.c_double)
        lib = symbols({
            "geomesa_sort_bin_z": (
                ctypes.c_int64,
                [i32p, i64p, ctypes.c_int64, ctypes.c_int64, i32p, i64p,
                 i64p]),
            "geomesa_sort_z": (
                ctypes.c_int64, [i64p, ctypes.c_int64, i32p, i64p]),
            "geomesa_gather_xyz": (
                ctypes.c_int64,
                [dp, dp, i64p, i32p, ctypes.c_int64, dp, dp, i64p]),
        })
        _native_sort = lib if lib is not None else False
    return _native_sort or None


def _i32p(a):
    import ctypes
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a):
    import ctypes
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _native_sort_bin_z(bins: np.ndarray, z: np.ndarray):
    """(z_sorted, perm, ubins, seg_offsets) or None. The counting sort
    exports its per-bin prefix sums, so segment boundaries come back
    for free — no bins gather / np.unique pass afterwards."""
    lib = _native_sort_lib()
    if lib is None or not len(bins):
        return None
    bins = np.ascontiguousarray(bins, dtype=np.int32)
    z = np.ascontiguousarray(z, dtype=np.int64)
    max_bin = int(bins.max())
    perm = np.empty(len(z), dtype=np.int32)
    z_sorted = np.empty(len(z), dtype=np.int64)
    offsets = np.empty(max_bin + 2, dtype=np.int64)
    rc = lib.geomesa_sort_bin_z(_i32p(bins), _i64p(z), len(z),
                                max_bin, _i32p(perm), _i64p(z_sorted),
                                _i64p(offsets))
    if rc != 0:
        return None
    counts = np.diff(offsets)
    present = counts > 0
    ubins = np.flatnonzero(present).astype(bins.dtype)
    seg_offsets = np.append(offsets[:-1][present], len(z))
    return z_sorted, perm, ubins, seg_offsets


def _native_sort_z(z: np.ndarray):
    lib = _native_sort_lib()
    if lib is None or not len(z):
        return None
    z = np.ascontiguousarray(z, dtype=np.int64)
    perm = np.empty(len(z), dtype=np.int32)
    z_sorted = np.empty(len(z), dtype=np.int64)
    rc = lib.geomesa_sort_z(_i64p(z), len(z), _i32p(perm),
                            _i64p(z_sorted))
    return None if rc != 0 else (z_sorted, perm)


_native_build = None  # None = unprobed, False = unavailable
_PERIOD_CODE = {timebin.TimePeriod.DAY: 0, timebin.TimePeriod.WEEK: 1}
_EDGE_CACHE: dict = {}  # period -> int64 bin-edge epoch millis


def _bin_edges(period) -> np.ndarray:
    """Epoch millis of every calendar bin boundary (MONTH/YEAR), one
    past the last indexable bin included — computed once, 262KB."""
    period = timebin.TimePeriod.parse(period)
    if period not in _EDGE_CACHE:
        unit = "M" if period is timebin.TimePeriod.MONTH else "Y"
        grid = np.arange(0, 32769).astype(f"datetime64[{unit}]")
        _EDGE_CACHE[period] = grid.astype("datetime64[ms]") \
            .astype(np.int64)
    return _EDGE_CACHE[period]


def _native_encode_binned_z3(x, y, millis, period):
    """(bins:int32, z:int64) from the fused native clamp+bin+encode
    pass (native/src/zbuild.cpp), or None when the native library is
    absent. DAY/WEEK use constant-divisor bin splits; MONTH/YEAR pass
    a precomputed calendar bin-edge table and binary-search it fused
    with the encode."""
    global _native_build
    period = timebin.TimePeriod.parse(period)
    if _native_build is False or not len(x):
        return None
    import ctypes
    if _native_build is None:
        from ..native import symbols
        dp = ctypes.POINTER(ctypes.c_double)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib = symbols({
            "geomesa_encode_binned_z3": (
                ctypes.c_int64,
                [dp, dp, i64p, ctypes.c_int64, ctypes.c_int32,
                 ctypes.c_double, i32p, i64p]),
            "geomesa_encode_binned_z3_edges": (
                ctypes.c_int64,
                [dp, dp, i64p, ctypes.c_int64, i64p, ctypes.c_int64,
                 ctypes.c_int64, ctypes.c_double, i32p, i64p]),
        })
        _native_build = lib if lib is not None else False
        if _native_build is False:
            return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    millis = np.ascontiguousarray(millis, dtype=np.int64)
    n = len(x)
    if len(y) != n or len(millis) != n:
        return None
    bins = np.empty(n, dtype=np.int32)
    z = np.empty(n, dtype=np.int64)
    dptr = ctypes.POINTER(ctypes.c_double)
    t_max = float(z3sfc(period).time.max)
    code = _PERIOD_CODE.get(period)
    if code is not None:
        rc = _native_build.geomesa_encode_binned_z3(
            x.ctypes.data_as(dptr), y.ctypes.data_as(dptr),
            _i64p(millis), n, code, t_max, _i32p(bins), _i64p(z))
    else:
        edges = _bin_edges(period)
        off_div = 1000 if period is timebin.TimePeriod.MONTH else 60_000
        rc = _native_build.geomesa_encode_binned_z3_edges(
            x.ctypes.data_as(dptr), y.ctypes.data_as(dptr),
            _i64p(millis), n, _i64p(edges), len(edges) - 1, off_div,
            t_max, _i32p(bins), _i64p(z))
    return None if rc != 0 else (bins, z)


def binned_candidate_positions(ubins, seg_offsets, keys_sorted,
                               intervals_ms, period, range_fn,
                               max_rows: int | None,
                               base_total: int = 0) -> np.ndarray | None:
    """Shared per-time-bin fan-out (Z3IndexKeySpace.getRanges:100-136):
    clamp intervals into the indexable range (monotone, matching the
    lenient keys), union per-bin offset hulls, and binary-search each
    bin's covering ranges (``range_fn((lo_off, hi_off))``) inside its
    sorted segment. Returns positions into the sorted order, an empty
    array when nothing matches, or None when the interval set is empty
    or the candidate count (plus ``base_total``) exceeds ``max_rows``.
    Used by both the z3 point index and the xz3 extent index."""
    cap = timebin.max_date_millis(period) - 1
    by_bin: dict[int, list[int]] = {}
    for lo_ms, hi_ms in intervals_ms:
        if hi_ms < lo_ms:
            continue
        lo_ms = min(max(int(lo_ms), 0), cap)
        hi_ms = min(max(int(hi_ms), 0), cap)
        bs, los, his = timebin.bins_of_interval(lo_ms, hi_ms, period)
        for b, lo, hi in zip(bs.tolist(), los.tolist(), his.tolist()):
            cur = by_bin.get(b)
            if cur is None:
                by_bin[b] = [lo, hi]
            else:
                # over-approximate disjoint unions with the hull; the
                # exact re-check downstream handles every candidate
                cur[0] = min(cur[0], lo)
                cur[1] = max(cur[1], hi)
    if not by_bin:
        return None
    if max_rows is not None and base_total > max_rows:
        return None
    range_cache: dict[tuple, np.ndarray] = {}
    pieces: list[np.ndarray] = []
    total = base_total
    for b in sorted(by_bin):
        i = int(np.searchsorted(ubins, b))
        if i >= len(ubins) or int(ubins[i]) != b:
            continue
        s, e = int(seg_offsets[i]), int(seg_offsets[i + 1])
        key = tuple(by_bin[b])
        ranges = range_cache.get(key)
        if ranges is None:
            ranges = range_fn(key)
            range_cache[key] = ranges
        if len(ranges) == 0:
            continue
        seg = keys_sorted[s:e]
        los = s + np.searchsorted(seg, ranges[:, 0], side="left")
        his = s + np.searchsorted(seg, ranges[:, 1], side="right")
        total += int(np.sum(his - los))
        if max_rows is not None and total > max_rows:
            return None
        pos = multi_arange(los, his)
        if len(pos):
            pieces.append(pos)
    if not pieces:
        return np.empty(0, dtype=np.int64)
    return np.concatenate(pieces)


def search_rows(zindex, index_name: str, boxes, intervals,
                host_cap: int | None, block_cap: int | None,
                cache: bool = True):
    """THE store-level fast-path policy (single copy for every store):
    whole-world gate, then one range decomposition via
    ``zindex.query_rows`` serving both tiers — ("exact", rows) under
    ``host_cap``, ("candidates", rows) under ``block_cap``,
    (None, None) for the dense path. Indexes without query_rows (the XZ
    extent family runs its own exact stage) fall back to
    prune_candidates. ``cache=False`` skips the decomposition cache —
    probe loops with never-repeating boxes (KNN ring expansion) must
    not flush entries that repeated store queries rely on."""
    whole_world = list(boxes) == [(-180.0, -90.0, 180.0, 90.0)]
    if zindex is None or (whole_world
                          and not (index_name == "z3" and intervals)):
        return None, None
    qr = getattr(zindex, "query_rows", None)
    if qr is None:
        rows = prune_candidates(zindex, index_name, boxes, intervals,
                                block_cap)
        return ("candidates", rows) if rows is not None else (None, None)
    return qr(index_name, boxes, intervals, host_cap, block_cap,
              cache=cache)


def prune_candidates(zindex, index_name: str, boxes, intervals,
                     max_rows: int | None) -> np.ndarray | None:
    """THE pruning policy, shared by every store and index family
    (z2/z3 point orders, xz2/xz3 extent orders): pick the
    spatio-temporal or spatial-only order for the strategy, skip
    pruning for unconstrained (whole-world, no-time) queries, and bail
    to a dense scan when the candidate set exceeds ``max_rows``.
    Returns candidate row indices or None (caller runs the dense path)."""
    whole_world = list(boxes) == [(-180.0, -90.0, 180.0, 90.0)]
    if zindex is None or (whole_world and not intervals):
        return None
    if index_name in ("z3", "xz3") and intervals:
        fn = getattr(zindex, f"candidates_{index_name}", None)
        return None if fn is None else fn(boxes, intervals,
                                          max_rows=max_rows)
    if not whole_world:
        spatial = "xz2" if index_name.startswith("xz") else "z2"
        fn = getattr(zindex, f"candidates_{spatial}", None)
        return None if fn is None else fn(boxes, max_rows=max_rows)
    return None


# cache-miss sentinel for ZKeyIndex._qcache (a stored None means "the
# decomposition chose the dense path", which is itself worth caching)
_QMISS = object()


class ZKeyIndex:
    """Sorted (bin, z3) and z2 key orders over point columns.

    Parameters are host arrays in insertion order; ``millis`` may be
    None for a time-less schema (z2 only).
    """

    def __init__(self, x: np.ndarray, y: np.ndarray,
                 millis: np.ndarray | None,
                 period: TimePeriod | str = TimePeriod.WEEK,
                 version: int = 2):
        self._x = np.asarray(x, dtype=np.float64)
        self._y = np.asarray(y, dtype=np.float64)
        self._millis = (None if millis is None
                        else np.asarray(millis, dtype=np.int64))
        self.period = TimePeriod.parse(period)
        # index layout version: 1 = legacy semi-normalized z3 curve
        # (curves/legacy.py), 2 = current. Sort orders and query ranges
        # must use the SAME curve or pruning silently drops rows.
        self.version = int(version)
        self.n = len(self._x)
        self._z3 = None  # (ubins, seg_offsets, z_sorted, perm)
        self._z2 = None  # (z_sorted, perm)
        # sorted-order coordinate copies, built on first search_*: the
        # candidate positions from range decomposition are CONTIGUOUS
        # runs in sorted order, so evaluating on x[perm]/y[perm] copies
        # turns the hot candidate pass from random gathers over the
        # full columns into sequential slices
        self._z3_coords = None  # (xs, ys, ms) in z3 order
        self._z2_coords = None  # (xs, ys) in z2 order
        self._z3_uses = 0       # exact-tier queries served per curve;
        self._z2_uses = 0       # gates the sorted-copy amortization
        # (boxes, intervals, caps) -> candidate positions: repeated
        # queries skip the range decomposition + seek (extend() returns
        # a NEW index, so entries never outlive the data they describe)
        self._qcache: "OrderedDict" = OrderedDict()
        self._qcache_n = 0  # total cached positions (byte bound)

    # -- build -------------------------------------------------------------

    # exact-tier queries per curve before the sorted-order coordinate
    # copies are worth their full-table build cost: the FIRST query
    # answers off the cheap per-candidate gather (cold start never pays
    # the full-table copies), any repeat usage amortizes them at once
    _COORDS_AFTER = 1

    def _perm_dtype(self):
        # XLA TPU gathers address with 32-bit indices, and a >=2^31-row
        # column set exceeds single-chip HBM anyway: larger tables must
        # shard over the mesh (store/mesh_store.py), which keeps every
        # per-device shard far below this cap.
        if self.n >= 2**31:
            raise ValueError(
                "single-shard table exceeds 2^31 rows; shard it over "
                "the mesh-distributed store instead")
        return np.int32

    def _sfc3(self):
        """The z3 curve for this index's layout version."""
        if self.version == 1:
            from ..curves.legacy import legacy_z3sfc
            return legacy_z3sfc(self.period)
        return z3sfc(self.period)

    def _build_z3(self):
        if self._z3 is not None or self._millis is None:
            return self._z3
        # the fused native encode implements only the CURRENT curve
        fused = (_native_encode_binned_z3(self._x, self._y, self._millis,
                                          self.period)
                 if self.version != 1 else None)
        if fused is not None:
            bins, z = fused
        else:
            sfc = self._sfc3()
            bins, offs = timebin.to_binned(self._millis, self.period,
                                           lenient=True)
            z = sfc.index(self._x, self._y, offs.astype(np.float64),
                          lenient=True).astype(np.int64)
        self._perm_dtype()  # enforce the row cap
        sorted_nat = _native_sort_bin_z(bins, z)
        if sorted_nat is not None:
            z_sorted, perm, ubins, seg_offsets = sorted_nat
        else:
            perm = np.lexsort((z, bins)).astype(np.int32)
            bins_sorted = bins[perm]
            z_sorted = z[perm]
            # per-bin contiguous segments in the sorted order
            ubins, seg_starts = np.unique(bins_sorted, return_index=True)
            seg_offsets = np.append(seg_starts, self.n)
        self._z3 = (ubins, seg_offsets, z_sorted, perm)
        return self._z3

    def _build_z2(self):
        if self._z2 is not None:
            return self._z2
        z = z2sfc().index(self._x, self._y, lenient=True).astype(np.int64)
        self._perm_dtype()  # enforce the row cap
        sorted_nat = _native_sort_z(z)
        if sorted_nat is not None:
            self._z2 = sorted_nat  # (z_sorted, perm)
        else:
            perm = np.argsort(z, kind="stable").astype(np.int32)
            self._z2 = (z[perm], perm)
        return self._z2

    # -- persistence (fs-store index sidecars) -----------------------------

    def state_dict(self) -> dict:
        """Built sort orders as plain arrays, for persistence next to
        the backing data (the fs store's index sidecars — the analog of
        the reference keeping its index *tables* durable while this
        design keeps device columns in insertion order plus a sorted
        host permutation). Only materialized orders are exported; the
        coordinate copies are cheap gathers and are rebuilt on demand."""
        out: dict = {}
        if self._z3 is not None:
            ubins, seg_offsets, z_sorted, perm = self._z3
            out.update(z3_ubins=ubins, z3_seg_offsets=seg_offsets,
                       z3_zsorted=z_sorted, z3_perm=perm)
        if self._z2 is not None:
            z_sorted, perm = self._z2
            out.update(z2_zsorted=z_sorted, z2_perm=perm)
        if out:
            out["index_version"] = np.array([self.version],
                                            dtype=np.int64)
        return out

    def warm(self) -> None:
        """Build the curve sort orders now (ingest-time indexing — the
        reference writes z-keys with every mutation, write path 3.2).
        Queries that arrive later find a ready index. The sorted-order
        coordinate copies stay deferred (see _COORDS_AFTER)."""
        if self._millis is not None:
            self._build_z3()
        self._build_z2()

    def load_state(self, state: dict) -> bool:
        """Install persisted sort orders (possibly memory-mapped).
        Returns False — installing nothing — when the arrays don't
        cover this table's rows (stale sidecar after writes) or were
        built under a different index layout version (a reindexed
        table must not adopt its pre-migration sort orders)."""
        persisted_v = int(np.asarray(
            state.get("index_version", [2]))[0])
        if persisted_v != self.version:
            return False
        self._qcache.clear()  # positions are per sort-order build
        self._qcache_n = 0
        ok = False
        if "z3_zsorted" in state and self._millis is not None:
            z_sorted, perm = state["z3_zsorted"], state["z3_perm"]
            if len(z_sorted) == self.n and len(perm) == self.n:
                self._z3 = (state["z3_ubins"], state["z3_seg_offsets"],
                            z_sorted, perm)
                ok = True
        if "z2_zsorted" in state:
            z_sorted, perm = state["z2_zsorted"], state["z2_perm"]
            if len(z_sorted) == self.n and len(perm) == self.n:
                self._z2 = (z_sorted, perm)
                ok = True
        return ok

    # -- incremental maintenance -------------------------------------------

    def extend(self, x: np.ndarray, y: np.ndarray,
               millis: np.ndarray | None) -> "ZKeyIndex":
        """New index covering the existing rows plus appended rows, with
        already-built sort orders MERGED (sorted-run merge: O(N) memcpy
        + O(D log D) delta sort) instead of re-sorted from scratch — the
        LSM-style write path the reference gets from its backing stores'
        minor compactions (BatchWriter mutations merging into tablets).
        """
        if (self._millis is None) != (millis is None):
            raise ValueError("time column presence must match")
        out = ZKeyIndex.__new__(ZKeyIndex)
        out._x = np.concatenate([self._x, np.asarray(x, dtype=np.float64)])
        out._y = np.concatenate([self._y, np.asarray(y, dtype=np.float64)])
        out._millis = (None if millis is None else np.concatenate(
            [self._millis, np.asarray(millis, dtype=np.int64)]))
        out.period = self.period
        out.version = self.version
        out.n = len(out._x)
        out._qcache = OrderedDict()
        out._qcache_n = 0
        out._z3_uses = self._z3_uses
        out._z2_uses = self._z2_uses
        out._perm_dtype()  # enforce the row cap before any merge work
        # built coord copies merge via the same inserts (delta-sized
        # sort + O(N) memcpy); unbuilt ones stay lazy
        out._z3, out._z3_coords = (self._merged_z3(x, y, millis)
                                   if self._z3 else (None, None))
        out._z2, out._z2_coords = (self._merged_z2(x, y)
                                   if self._z2 else (None, None))
        return out

    def _merged_z2(self, x, y):
        """Returns ((z_sorted, perm), coords_or_None)."""
        z_sorted, perm = self._z2
        x = np.asarray(x, dtype=np.float64)
        y = np.asarray(y, dtype=np.float64)
        dz = z2sfc().index(x, y, lenient=True).astype(np.int64)
        dorder = np.argsort(dz, kind="stable")
        dzs = dz[dorder]
        # side="right": appended rows land after equal existing keys,
        # preserving stable insertion order
        pos = np.searchsorted(z_sorted, dzs, side="right")
        new_z = np.insert(z_sorted, pos, dzs)
        new_perm = np.insert(perm, pos,
                             (dorder + self.n).astype(perm.dtype))
        coords = None
        if self._z2_coords is not None:
            xs, ys = self._z2_coords
            coords = (np.insert(xs, pos, x[dorder]),
                      np.insert(ys, pos, y[dorder]))
        return (new_z, new_perm), coords

    def _merged_z3(self, x, y, millis):
        """Returns ((ubins, seg_offsets, z_sorted, perm), coords)."""
        ubins, seg_offsets, z_sorted, perm = self._z3
        sfc = self._sfc3()
        x = np.asarray(x, dtype=np.float64)
        y = np.asarray(y, dtype=np.float64)
        millis = np.asarray(millis, dtype=np.int64)
        dbins, doffs = timebin.to_binned(millis, self.period, lenient=True)
        dz = sfc.index(x, y, doffs.astype(np.float64),
                       lenient=True).astype(np.int64)
        dorder = np.lexsort((dz, dbins))
        dbs, dzs = dbins[dorder], dz[dorder]
        pos = np.empty(len(dbs), dtype=np.int64)
        # per-unique-delta-bin: binary search within the bin's segment
        # (few distinct bins per write burst)
        for b in np.unique(dbs):
            m = dbs == b
            i = int(np.searchsorted(ubins, b))
            if i < len(ubins) and int(ubins[i]) == b:
                s, e = int(seg_offsets[i]), int(seg_offsets[i + 1])
                pos[m] = s + np.searchsorted(z_sorted[s:e], dzs[m],
                                             side="right")
            else:
                # a bin the table has not seen: insert at the boundary
                pos[m] = int(seg_offsets[i])
        counts = np.diff(seg_offsets)
        bins_sorted = np.repeat(ubins, counts)
        new_z = np.insert(z_sorted, pos, dzs)
        new_bins = np.insert(bins_sorted, pos, dbs)
        new_perm = np.insert(perm, pos,
                             (dorder + self.n).astype(perm.dtype))
        # new_bins is sorted: segment bounds from value changes, no sort
        steps = np.flatnonzero(new_bins[1:] != new_bins[:-1]) + 1
        seg_starts = np.concatenate([[0], steps])
        ubins2 = new_bins[seg_starts]
        seg_offsets2 = np.append(seg_starts, len(new_bins))
        coords = None
        if self._z3_coords is not None:
            xs, ys, ms = self._z3_coords
            coords = (
                np.insert(xs, pos, x[dorder]),
                np.insert(ys, pos, y[dorder]),
                None if ms is None else np.insert(ms, pos,
                                                  millis[dorder]))
        return (ubins2, seg_offsets2, new_z, new_perm), coords

    def _gather_coords(self, perm: np.ndarray, with_ms: bool):
        """Sorted-order coordinate copies — the native fused gather
        reads ``perm`` once per row and fills every output with
        sequential writes across threads; numpy fallback pays one
        single-threaded random gather per column (the difference is
        seconds of first-query latency at 100M rows)."""
        ms = self._millis if with_ms else None
        lib = _native_sort_lib()
        import os
        # single-core hosts: numpy's tuned per-array take beats the
        # fused interleaved loop (3 random streams thrash one cache);
        # the fused pass wins only when threads split the row range
        if (os.cpu_count() or 1) > 1 and lib is not None and len(perm) \
                and perm.dtype == np.int32 \
                and hasattr(lib, "geomesa_gather_xyz"):
            import ctypes
            n = len(perm)
            x = np.ascontiguousarray(self._x)
            y = np.ascontiguousarray(self._y)
            p = np.ascontiguousarray(perm)
            xo = np.empty(n, dtype=np.float64)
            yo = np.empty(n, dtype=np.float64)
            dp = ctypes.POINTER(ctypes.c_double)
            mo = None
            msp = ctypes.cast(None, ctypes.POINTER(ctypes.c_int64))
            mop = msp
            if ms is not None:
                mo = np.empty(n, dtype=np.int64)
                msp = _i64p(np.ascontiguousarray(ms))
                mop = _i64p(mo)
            rc = lib.geomesa_gather_xyz(
                x.ctypes.data_as(dp), y.ctypes.data_as(dp), msp,
                _i32p(p), n, xo.ctypes.data_as(dp),
                yo.ctypes.data_as(dp), mop)
            if rc == 0:
                return (xo, yo, mo) if with_ms else (xo, yo)
        if with_ms:
            return (self._x[perm], self._y[perm],
                    None if ms is None else ms[perm])
        return (self._x[perm], self._y[perm])

    # -- exact search (host fast path) -------------------------------------

    @staticmethod
    def _eval_sorted(xs, ys, ms, pos, boxes, intervals_ms) -> np.ndarray:
        """Exact f64 evaluation over sorted-order positions; identical
        semantics to zscan.exact_patch (inclusive box bounds, inclusive
        [lo, hi] millis intervals). Returns keep mask over pos."""
        x = xs[pos]
        y = ys[pos]
        keep = np.zeros(len(pos), dtype=bool)
        for xmin, ymin, xmax, ymax in boxes:
            keep |= ((x >= xmin) & (x <= xmax)
                     & (y >= ymin) & (y <= ymax))
        if intervals_ms and ms is not None:
            m = ms[pos]
            tk = np.zeros(len(pos), dtype=bool)
            for lo, hi in intervals_ms:
                tk |= (m >= lo) & (m <= hi)
            keep &= tk
        return keep

    def query_rows(self, index_name: str, boxes, intervals_ms,
                   host_cap: int | None, block_cap: int | None,
                   max_ranges: int | None = None, cache: bool = True):
        """ONE range decomposition serving both tiers: returns
        ("exact", rows) when the candidate positions fit ``host_cap``
        (exact evaluation over sorted-order coordinate copies —
        sequential access), ("candidates", rows) when they fit only
        ``block_cap`` (the caller's candidate tier), or
        (None, None) for the dense path. ``cache=False`` neither reads
        nor writes the decomposition cache (one-shot probe boxes)."""
        use_z3 = index_name == "z3" and bool(intervals_ms)
        # the z2 order cannot evaluate time: with intervals present but
        # no z3 order in play, results may only be CANDIDATES (the
        # caller's scan re-checks time), never "exact"
        exact_ok = use_z3 or not intervals_ms
        # decomposition + seek cache: the candidate POSITIONS (not the
        # final rows) are deterministic per sort-order snapshot, so a
        # repeated query skips the z-range decomposition and the
        # searchsorted seeks; the exact evaluation below still runs —
        # the cache holds the plan's ranges, the scan stays a scan
        if max_ranges is None:
            # the host tiers re-check every candidate exactly, so a
            # coarse cover only grows the (small) candidate set while
            # the range decomposition is a PER-QUERY cost — a deep
            # 2000-range BFS spends more than the extra candidates save
            # on selective query streams (the coarsening knob the
            # reference turns with SCAN_RANGES_TARGET)
            from ..utils.properties import HOST_RANGES_TARGET
            max_ranges = int(HOST_RANGES_TARGET.get())
        qkey = (use_z3, tuple(boxes),
                tuple(tuple(i) for i in intervals_ms),
                block_cap, max_ranges)
        hit = self._qcache.get(qkey, _QMISS) if cache else _QMISS
        if hit is not _QMISS:
            pos = hit
            if use_z3:
                _, _, _, perm = self._build_z3()
            else:
                _, perm = self._build_z2()
        elif use_z3:
            built = self._build_z3()
            if built is None:
                return None, None
            ubins, seg_offsets, z_sorted, perm = built
            sfc = self._sfc3()
            pos = binned_candidate_positions(
                ubins, seg_offsets, z_sorted, intervals_ms, self.period,
                lambda key: sfc.ranges(boxes, [key],
                                       max_ranges=max_ranges),
                block_cap)
        else:
            z_sorted, perm = self._build_z2()
            ranges = z2sfc().ranges(boxes, max_ranges=max_ranges)
            los = np.searchsorted(z_sorted, ranges[:, 0], side="left")
            his = np.searchsorted(z_sorted, ranges[:, 1], side="right")
            if block_cap is not None \
                    and int(np.sum(his - los)) > block_cap:
                pos = None
            else:
                pos = multi_arange(los, his)
        if cache and hit is _QMISS and (pos is None
                                        or len(pos) <= 262_144):
            # bounded in BYTES, not just entries: evict oldest until the
            # retained position arrays fit ~16MB (2M int64 positions)
            self._qcache_n += 0 if pos is None else len(pos)
            while (len(self._qcache) >= 64
                   or self._qcache_n > 2_097_152):
                _, old = self._qcache.popitem(last=False)
                if old is not None:
                    self._qcache_n -= len(old)
            self._qcache[qkey] = pos
        if pos is None:
            return None, None
        if not exact_ok:
            return "candidates", perm[pos].astype(np.int64)
        if not len(pos):
            return "exact", np.empty(0, dtype=np.int64)
        if host_cap is not None and len(pos) > host_cap:
            return "candidates", perm[pos].astype(np.int64)
        # sorted-order coordinate copies turn the candidate pass into
        # sequential slices, but building them costs full-table gathers
        # (~10s at 100M rows) — far more than a first query needs. Early
        # queries evaluate on a per-candidate gather (O(|pos|)); the
        # copies build only once the curve has served enough queries to
        # amortize them.
        if use_z3:
            coords, ivals = self._z3_coords, intervals_ms
            self._z3_uses += 1 if cache else 0
            if coords is None and self._z3_uses > self._COORDS_AFTER:
                coords = self._z3_coords = self._gather_coords(perm, True)
        else:
            coords, ivals = self._z2_coords, []
            # one-shot probe loops (cache=False, e.g. KNN rings) must
            # not trip the amortization gate: their boxes never repeat
            self._z2_uses += 1 if cache else 0
            if coords is None and self._z2_uses > self._COORDS_AFTER:
                coords = self._z2_coords = self._gather_coords(perm, False)
        if coords is not None:
            xs, ys = coords[0], coords[1]
            ms = coords[2] if use_z3 else None
            keep = self._eval_sorted(xs, ys, ms, pos, boxes, ivals)
            return "exact", np.sort(perm[pos[keep]].astype(np.int64))
        rows = perm[pos]
        keep = self._eval_sorted(self._x, self._y,
                                 self._millis if use_z3 else None,
                                 rows, boxes, ivals)
        return "exact", np.sort(rows[keep].astype(np.int64))


    # -- candidates --------------------------------------------------------

    def candidates_z3(self, boxes, intervals_ms, *,
                      max_rows: int | None = None,
                      max_ranges: int | None = None) -> np.ndarray | None:
        """Candidate original-order row indices for boxes + intervals, or
        None when the z3 order is unavailable / the set exceeds max_rows.

        Mirrors the per-bin fan-out of Z3IndexKeySpace.getRanges
        (:100-136): interior bins use whole-period ranges (computed
        once), edge bins their partial-offset ranges.
        """
        built = self._build_z3()
        if built is None:
            return None
        ubins, seg_offsets, z_sorted, perm = built
        sfc = self._sfc3()
        pos = binned_candidate_positions(
            ubins, seg_offsets, z_sorted, intervals_ms, self.period,
            lambda key: sfc.ranges(boxes, [key], max_ranges=max_ranges),
            max_rows)
        if pos is None:
            return None
        if not len(pos):
            return np.empty(0, dtype=np.int64)
        return perm[pos].astype(np.int64)

    def candidates_z2(self, boxes, *, max_rows: int | None = None,
                      max_ranges: int | None = None) -> np.ndarray | None:
        """Candidate rows for a pure-spatial query via the z2 order."""
        z_sorted, perm = self._build_z2()
        ranges = z2sfc().ranges(boxes, max_ranges=max_ranges)
        if len(ranges) == 0:
            return np.empty(0, dtype=np.int64)
        los = np.searchsorted(z_sorted, ranges[:, 0], side="left")
        his = np.searchsorted(z_sorted, ranges[:, 1], side="right")
        if max_rows is not None and int(np.sum(his - los)) > max_rows:
            return None
        pos = multi_arange(los, his)
        if len(pos) == 0:
            return np.empty(0, dtype=np.int64)
        return perm[pos].astype(np.int64)
