"""Mesh-sharded scans: the multi-chip execution path.

Data parallelism over a ``jax.sharding.Mesh`` axis ``"data"``: feature
columns shard evenly across devices (the analog of tablet splits,
SURVEY.md 2.5 #2-3); the scan kernel runs shard-locally under
``shard_map``; aggregations reduce over ICI with ``psum`` (the analog of
"server-side aggregate -> client reduce", SURVEY.md 2.5 #5).

Masks stay device-resident and sharded — downstream aggregation kernels
(density/stats/bin) consume them without gathering; only final small
results cross to the host.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections.abc import Sequence
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxcache import ensure_compile_cache

ensure_compile_cache()
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import runtime, tracer
from ..scan import zscan

__all__ = ["data_mesh", "DistributedScanData", "shard_scan_data",
           "distributed_scan_mask", "distributed_count",
           "distributed_contains_counts",
           "distributed_density", "distributed_histogram",
           "distributed_minmax", "DistributedExtentData",
           "shard_extent_data", "distributed_tristate"]


def data_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over the data axis."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), axis_names=("data",))


@dataclasses.dataclass
class DistributedScanData:
    """Sharded device columns + padding info + host originals (kept for
    the exact f64 boundary patch, mirroring the single-chip store)."""
    xhi: jax.Array
    xlo: jax.Array
    yhi: jax.Array
    ylo: jax.Array
    tday: jax.Array
    tms: jax.Array
    n: int            # true (unpadded) row count
    n_padded: int
    mesh: Mesh
    host_x: np.ndarray
    host_y: np.ndarray
    host_millis: np.ndarray
    host_xhi: np.ndarray
    host_yhi: np.ndarray


def shard_scan_data(x: np.ndarray, y: np.ndarray, millis: np.ndarray,
                    mesh: Mesh) -> DistributedScanData:
    """Host columns -> evenly-sharded device columns (padded so every
    shard is equal; pad rows carry out-of-domain coords so no query
    matches them)."""
    n = len(x)
    k = mesh.devices.size
    n_padded = ((n + k - 1) // k) * k
    pad = n_padded - n

    def prep(arr, fill):
        arr = np.asarray(arr)
        if pad:
            arr = np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])
        return arr

    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    millis_h = np.asarray(millis, np.int64)
    xhi, xlo = zscan.split_two_float(prep(x, 1e9))
    yhi, ylo = zscan.split_two_float(prep(y, 1e9))
    millis_p = prep(millis_h, -1)
    tday = (millis_p // zscan.MILLIS_PER_DAY).astype(np.int32)
    tms = (millis_p - tday.astype(np.int64) * zscan.MILLIS_PER_DAY).astype(np.int32)

    sharding = NamedSharding(mesh, P("data"))
    put = functools.partial(jax.device_put, device=sharding)
    return DistributedScanData(
        put(xhi), put(xlo), put(yhi), put(ylo),
        put(tday), put(tms),
        n, n_padded, mesh, x, y, millis_h, xhi[:n], yhi[:n])


def _shard_mask_fn(time_any: bool):
    """Shard-local scan body; runs identically on every device. Its name
    is the kernel's in the device trace (``jit__mesh_scan_mask``)."""
    def _mesh_scan_mask(xhi, xlo, yhi, ylo, tday, tms, boxes, box_valid,
                        times, tvalid):
        return zscan._scan_mask(xhi, xlo, yhi, ylo, tday, tms,
                                boxes, box_valid, times, tvalid, time_any)
    return _mesh_scan_mask


_SPECS_IN = (P("data"), P("data"), P("data"), P("data"),
             P("data"), P("data"), P(), P(), P(), P())


@functools.lru_cache(maxsize=32)
def _mask_fn(mesh: Mesh, time_any: bool):
    return jax.jit(jax.shard_map(_shard_mask_fn(time_any), mesh=mesh,
                                 in_specs=_SPECS_IN, out_specs=P("data")))


@functools.lru_cache(maxsize=32)
def _count_fn(mesh: Mesh, time_any: bool):
    body = _shard_mask_fn(time_any)

    def counted(*args):
        mask = body(*args)
        return jax.lax.psum(jnp.sum(mask, dtype=jnp.int32), "data")

    return jax.jit(jax.shard_map(counted, mesh=mesh,
                                 in_specs=_SPECS_IN, out_specs=P()))


def _args(data: DistributedScanData, q: zscan.ScanQuery):
    return (data.xhi, data.xlo, data.yhi, data.ylo, data.tday, data.tms,
            q.boxes, q.box_valid, q.times, q.time_valid)


def distributed_scan_mask(data: DistributedScanData,
                          q: zscan.ScanQuery) -> jax.Array:
    """Run the scan on every shard; returns the sharded bool mask (raw
    device verdict; use ``exact_host_mask`` for the f64-patched result)."""
    return _mask_fn(data.mesh, q.time_any)(*_args(data, q))


def exact_host_mask(data: DistributedScanData, q: zscan.ScanQuery) -> np.ndarray:
    """Gathered host mask with the exact f64 boundary patch applied
    (drops padding rows)."""
    mask = np.asarray(distributed_scan_mask(data, q))[:data.n]
    cand = zscan.boundary_candidates(data.host_xhi, data.host_yhi, q)
    return zscan.exact_patch(mask, cand, data.host_x, data.host_y,
                             data.host_millis, q)


@functools.partial(jax.jit, static_argnames=("cap",))
def _mask_hit_rows(mask, cap):
    """Device-side compaction of a (possibly sharded) scan mask: only
    the hit row ids come back. fill = len(mask), filtered by the
    caller's n bound (padding rows are also >= n)."""
    return jnp.nonzero(mask, size=cap, fill_value=mask.shape[0])[0]


def _device_hit_rows(data: DistributedScanData,
                     q: zscan.ScanQuery) -> tuple[np.ndarray, int]:
    """Count-then-compact on device: (the sorted hit rows below
    ``data.n`` as downloaded, the compaction's padded size)."""
    mask = distributed_scan_mask(data, q)
    # int32 is the real contract: single-table row counts are capped
    # below 2^31 (ZKeyIndex._perm_dtype)
    total = int(jnp.sum(mask, dtype=jnp.int32))
    if not total:
        return np.empty(0, dtype=np.int32), 0
    cap = 1 << (total - 1).bit_length()
    rows = np.asarray(_mask_hit_rows(mask, cap))
    # the indices ascend; the fill value and padding rows are >= n
    return rows[:np.searchsorted(rows, rows.dtype.type(data.n))], cap


def _find(rows: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """``np.searchsorted(rows, keys)`` with the keys in the rows' dtype:
    keys of a wider dtype would copy all of ``rows`` to compare."""
    return np.searchsorted(rows, keys.astype(rows.dtype, copy=False))


def _shard_candidates(data: DistributedScanData,
                      q: zscan.ScanQuery) -> np.ndarray:
    """``zscan.boundary_candidates`` over each shard's rows, the shards'
    host passes side by side (numpy's compares release the GIL), as one
    sorted array of row ids."""
    if not data.n:
        return np.empty(0, dtype=np.int64)
    per = data.n_padded // data.mesh.devices.size
    shards = [(lo, min(lo + per, data.n)) for lo in range(0, data.n, per)]

    def one(shard):
        lo, hi = shard
        return lo + zscan.boundary_candidates(data.host_xhi[lo:hi],
                                              data.host_yhi[lo:hi], q)

    with ThreadPoolExecutor(len(shards)) as pool:
        return np.concatenate(list(pool.map(one, shards)))


def _boundary_edits(data: DistributedScanData, q: zscan.ScanQuery,
                    rows: np.ndarray) -> tuple[int, np.ndarray, np.ndarray]:
    """Boundary patch in ROW-SET space: the two-float verdict recomputed
    on host for just the boundary candidates, compared with exact f64.
    Returns (candidates checked, sorted rows to add, sorted rows to
    remove) against the sorted device hits ``rows``."""
    cand = _shard_candidates(data, q)
    if not len(cand):
        return 0, cand, cand
    dev, exact = _boundary_verdicts(data, q, cand)
    flip = dev != exact
    rows_flip, want = cand[flip], exact[flip]
    pos = _find(rows, rows_flip)
    held = pos < len(rows)
    held[held] = rows[pos[held]] == rows_flip[held]
    return (len(cand), rows_flip[want & ~held], rows_flip[~want & held])


def _splice(rows: np.ndarray, add: np.ndarray, remove: np.ndarray,
            off: int, out: np.ndarray):
    """``out[:] = sorted((rows - remove) | add) + off``, copied block by
    block between the edits: all three are sorted, ``remove`` lies in
    ``rows`` and ``add`` outside it. One pass over the hits."""
    cut = _find(rows, remove)
    at = _find(rows, add)
    pos = np.concatenate([at, cut])
    is_cut = np.arange(len(pos)) >= len(at)
    i = j = 0
    # in row order; at one position the insert goes before the removal
    for e in np.lexsort((is_cut, pos)):
        p = int(pos[e])
        out[j:j + p - i] = rows[i:p]
        j += p - i
        if is_cut[e]:
            i = p + 1
        else:
            out[j] = add[e]
            i, j = p, j + 1
    out[j:] = rows[i:]
    if off:
        out += off


def exact_hit_rows(segments: Sequence[DistributedScanData],
                   q: zscan.ScanQuery) -> np.ndarray:
    """Sorted matching row ids over consecutive row segments (a segment's
    rows follow those of the segments before it) with the exact f64
    boundary patch: count-then-compact on device, so host work and
    transfers are O(hits + boundary candidates), never a full-length
    mask (the materializing analog of distributed_count's psum shape).

    Span ``mesh-scan`` covers the shard-mapped pass, the hit count, the
    compaction and the download of every segment; ``mesh-patch`` the
    boundary candidates, their verdicts and the splice."""
    segments = list(segments)
    if not segments:
        return np.empty(0, dtype=np.int32)
    k = segments[0].mesh.devices.size
    with tracer.span("mesh-scan") as sp:
        t0 = time.perf_counter()
        found = [_device_hit_rows(seg, q) for seg in segments]
        cap = sum(c for _, c in found)
        d2h = sum(c * r.itemsize for r, c in found)
        shard_hits = np.zeros(k, dtype=np.int64)
        for seg, (rows, _) in zip(segments, found):
            bounds = np.arange(k + 1) * (seg.n_padded // k)
            shard_hits += np.diff(_find(rows, bounds))
        padded = sum(seg.n_padded for seg in segments)
        runtime.note_dispatch("scan", ("mesh-dense", padded),
                              time.perf_counter() - t0, d2h_bytes=d2h)
        sp.set_attr(rows=sum(seg.n for seg in segments),
                    segments=len(segments), shards=k,
                    hits=int(shard_hits.sum()), cap=cap, d2h_bytes=d2h,
                    shard_hits=shard_hits.tolist())
    with tracer.span("mesh-patch") as sp:
        edits = [_boundary_edits(seg, q, rows)
                 for seg, (rows, _) in zip(segments, found)]
        sizes = [len(rows) + len(add) - len(rm)
                 for (rows, _), (_, add, rm) in zip(found, edits)]
        # int32, as the compaction returns them: a table's rows are
        # capped below 2^31 (ZKeyIndex._perm_dtype)
        out = np.empty(sum(sizes), dtype=np.int32)
        at = off = 0
        for seg, (rows, _), (_, add, rm), m in zip(segments, found, edits,
                                                  sizes):
            _splice(rows, add, rm, off, out[at:at + m])
            at, off = at + m, off + seg.n
        sp.set_attr(checked=sum(e[0] for e in edits),
                    added=sum(len(e[1]) for e in edits),
                    removed=sum(len(e[2]) for e in edits))
    return out


def _boundary_verdicts(data: DistributedScanData, q: zscan.ScanQuery,
                       cand: np.ndarray):
    """(two_float, exact_f64) bool verdicts for the candidate rows,
    with identical arithmetic to the device kernel for the former."""
    dev = np.zeros(len(cand), dtype=bool)
    xhi, xlo = zscan.split_two_float(data.host_x[cand])
    yhi, ylo = zscan.split_two_float(data.host_y[cand])
    boxes = q.boxes_np
    for i in range(q.n_boxes):
        b = boxes[i]
        dev |= (((xhi > b[0]) | ((xhi == b[0]) & (xlo >= b[1])))
                & ((xhi < b[2]) | ((xhi == b[2]) & (xlo <= b[3])))
                & ((yhi > b[4]) | ((yhi == b[4]) & (ylo >= b[5])))
                & ((yhi < b[6]) | ((yhi == b[6]) & (ylo <= b[7]))))
    exact = np.zeros(len(cand), dtype=bool)
    for i in range(q.n_boxes):
        xmin, ymin, xmax, ymax = q.host_boxes[i]
        cx, cy = data.host_x[cand], data.host_y[cand]
        exact |= (cx >= xmin) & (cx <= xmax) & (cy >= ymin) & (cy <= ymax)
    if not q.time_any:
        cm = data.host_millis[cand]
        t_ok = np.zeros(len(cand), dtype=bool)
        for lo, hi in q.host_intervals:
            t_ok |= (cm >= lo) & (cm <= hi)
        dev &= t_ok
        exact &= t_ok
    return dev, exact


def _shard_batch_mask_fn():
    """Shard-local BATCHED scan body: the scalar kernel vmapped over a
    stacked query batch, plus the per-query boundary-candidate mask
    (two-float hi-cell collisions) computed in the same launch. Pad
    rows carry out-of-domain coords (1e9) so neither output can flag
    them; per-query time_any is absorbed into catch-all intervals by
    zscan.stack_queries, so the temporal compare always runs. The
    kernel prints as ``jit__mesh_batch_scan_mask`` in the device trace."""
    def _mesh_batch_scan_mask(xhi, xlo, yhi, ylo, tday, tms, boxes,
                              box_valid, times, tvalid):
        def one(bx, bv, tx, tv):
            return (zscan._mask_body(xhi, xlo, yhi, ylo, tday, tms,
                                     bx, bv, tx, tv, time_any=False,
                                     n_valid=None),
                    zscan._cand_body(xhi, yhi, bx, bv))
        return jax.vmap(one)(boxes, box_valid, times, tvalid)
    return _mesh_batch_scan_mask


@functools.lru_cache(maxsize=32)
def _batch_mask_fn(mesh: Mesh):
    return jax.jit(jax.shard_map(
        _shard_batch_mask_fn(), mesh=mesh, in_specs=_SPECS_IN,
        out_specs=(P(None, "data"), P(None, "data"))))


def batch_exact_hit_rows(data: DistributedScanData,
                         bq: zscan.BatchedScanQuery) -> list[np.ndarray]:
    """Micro-batched exact_hit_rows: ONE shard-mapped launch evaluates
    every query in the batch on every device, then per-query
    count-then-compact keeps host work and transfers O(hits +
    candidates) per query — the multi-query analog of exact_hit_rows."""
    mask, cand = _batch_mask_fn(data.mesh)(
        data.xhi, data.xlo, data.yhi, data.ylo, data.tday, data.tms,
        bq.boxes, bq.box_valid, bq.times, bq.time_valid)
    counts = np.asarray(zscan._batch_count(mask))
    ccounts = np.asarray(zscan._batch_count(cand))
    size = 1 << max(int(counts.max()) - 1, 0).bit_length()
    csize = 1 << max(int(ccounts.max()) - 1, 0).bit_length()
    idx = np.asarray(zscan._batch_nonzero(mask, size))
    cidx = np.asarray(zscan._batch_nonzero(cand, csize))
    out = []
    for i, sq in enumerate(bq.queries):
        rows = idx[i, :counts[i]].astype(np.int64)
        rows = rows[rows < data.n]
        crows = cidx[i, :ccounts[i]].astype(np.int64)
        crows = crows[crows < data.n]
        out.append(zscan.patch_hit_rows(rows, sq, data.host_x,
                                        data.host_y, data.host_millis,
                                        crows))
    return out


def _exact_count_adjustment(data: DistributedScanData,
                            q: zscan.ScanQuery) -> int:
    """Difference between exact-f64 and two-float verdicts over the
    boundary candidates (time is exact in both, so only spatial flips)."""
    cand = zscan.boundary_candidates(data.host_xhi, data.host_yhi, q)
    if len(cand) == 0:
        return 0
    dev, exact = _boundary_verdicts(data, q, cand)
    return int(exact.sum()) - int(dev.sum())


def distributed_count(data: DistributedScanData, q: zscan.ScanQuery) -> int:
    """Fused scan + global count: psum over the mesh (the 'server-side
    aggregate, client reduce' shape in one XLA program), corrected by the
    host boundary adjustment so the result is exact-f64."""
    device = int(_count_fn(data.mesh, q.time_any)(*_args(data, q)))
    return device + _exact_count_adjustment(data, q)


@functools.lru_cache(maxsize=32)
def _density_fn(mesh: Mesh, time_any: bool,
                bbox: tuple[float, float, float, float],
                width: int, height: int):
    body = _shard_mask_fn(time_any)
    xmin, ymin, xmax, ymax = bbox
    sx = width / (xmax - xmin) if xmax > xmin else 0.0
    sy = height / (ymax - ymin) if ymax > ymin else 0.0

    def density(xhi, xlo, yhi, ylo, tday, tms, boxes, bvalid, times, tvalid):
        mask = body(xhi, xlo, yhi, ylo, tday, tms, boxes, bvalid, times, tvalid)
        # GridSnap pixel binning; f32 coords are ample for pixel indices
        x = xhi.astype(jnp.float32) + xlo
        y = yhi.astype(jnp.float32) + ylo
        col = jnp.clip(((x - xmin) * sx).astype(jnp.int32), 0, width - 1)
        row = jnp.clip(((y - ymin) * sy).astype(jnp.int32), 0, height - 1)
        flat = row * width + col
        grid = jnp.zeros((height * width,), dtype=jnp.float32)
        grid = grid.at[flat].add(mask.astype(jnp.float32))
        return jax.lax.psum(grid, "data")

    return jax.jit(jax.shard_map(density, mesh=mesh,
                                 in_specs=_SPECS_IN, out_specs=P()))


@functools.lru_cache(maxsize=32)
def _hist_fn(mesh: Mesh, nbins: int, lo: float, hi: float):
    scale = nbins / (hi - lo)

    def body(values, mask):
        # np.histogram semantics: values outside [lo, hi] are dropped,
        # the last bin is closed at hi
        mask = mask & (values >= lo) & (values <= hi)
        b = jnp.clip(((values - lo) * scale).astype(jnp.int32), 0, nbins - 1)
        h = jnp.zeros((nbins,), jnp.int32)
        h = h.at[b].add(mask.astype(jnp.int32))
        return jax.lax.psum(h, "data")

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=P()))


def distributed_histogram(values: jax.Array, mask: jax.Array, mesh: Mesh,
                          nbins: int, lo: float, hi: float) -> np.ndarray:
    """Shard-local scatter-add histogram merged over ICI with psum —
    the StatsCombiner server-side merge analog
    (accumulo/data/stats/StatsCombiner.scala; Histogram/BinnedArray,
    utils/stats/). `values`/`mask` are 'data'-sharded f32/bool arrays.
    np.histogram semantics: out-of-range values are dropped."""
    if nbins <= 0 or not hi > lo:
        raise ValueError(f"invalid histogram range: nbins={nbins}, "
                         f"lo={lo}, hi={hi}")
    fn = _hist_fn(mesh, int(nbins), float(lo), float(hi))
    return np.asarray(fn(values, mask))


@functools.lru_cache(maxsize=32)
def _minmax_fn(mesh: Mesh):
    def body(values, mask):
        vmin = jnp.min(jnp.where(mask, values, jnp.float32(np.inf)))
        vmax = jnp.max(jnp.where(mask, values, jnp.float32(-np.inf)))
        return (jax.lax.pmin(vmin, "data"), jax.lax.pmax(vmax, "data"))

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=(P(), P())))


def distributed_minmax(values: jax.Array, mask: jax.Array,
                       mesh: Mesh) -> tuple[float, float]:
    """Global (min, max) of masked sharded values via pmin/pmax
    (MinMax sketch merge, utils/stats/MinMax.scala analog)."""
    vmin, vmax = _minmax_fn(mesh)(values, mask)
    return float(vmin), float(vmax)


@dataclasses.dataclass
class DistributedExtentData:
    """Mesh-sharded per-feature bboxes for the XZ-analog extent scan
    (outward-rounded f32, pad rows valid=False) + optional exact time
    columns — the distributed counterpart of gscan.ExtentScanData."""
    bxmin: jax.Array
    bymin: jax.Array
    bxmax: jax.Array
    bymax: jax.Array
    valid: jax.Array
    tday: jax.Array
    tms: jax.Array
    has_time: bool
    n: int
    n_padded: int
    mesh: Mesh


def shard_extent_data(bounds: np.ndarray, millis: np.ndarray | None,
                      mesh: Mesh) -> DistributedExtentData:
    """(n, 4) f64 bounds [xmin ymin xmax ymax] (NaN rows = null geoms)
    -> evenly-sharded outward-rounded f32 device columns."""
    from ..scan.gscan import _round_out
    bounds = np.asarray(bounds, np.float64)
    n = len(bounds)
    k = mesh.devices.size
    n_padded = ((n + k - 1) // k) * k
    pad = n_padded - n
    valid = ~np.isnan(bounds[:, 0])
    safe = np.where(valid[:, None], bounds, 0.0)
    xmin, xmax = _round_out(safe[:, 0], safe[:, 2])
    ymin, ymax = _round_out(safe[:, 1], safe[:, 3])

    def prep(a, fill, dtype):
        a = np.asarray(a, dtype)
        if pad:
            a = np.concatenate([a, np.full(pad, fill, dtype)])
        return a

    has_time = millis is not None
    if has_time:
        millis = np.asarray(millis, np.int64)
        tday = (millis // zscan.MILLIS_PER_DAY).astype(np.int32)
        tms = (millis - tday.astype(np.int64)
               * zscan.MILLIS_PER_DAY).astype(np.int32)
    else:
        tday = np.zeros(n, np.int32)
        tms = np.zeros(n, np.int32)

    sharding = NamedSharding(mesh, P("data"))
    put = functools.partial(jax.device_put, device=sharding)
    return DistributedExtentData(
        put(prep(xmin, 0, np.float32)), put(prep(ymin, 0, np.float32)),
        put(prep(xmax, 0, np.float32)), put(prep(ymax, 0, np.float32)),
        put(prep(valid, False, bool)),
        put(prep(tday, 0, np.int32)), put(prep(tms, 0, np.int32)),
        has_time, n, n_padded, mesh)


@functools.lru_cache(maxsize=32)
def _tristate_fn(mesh: Mesh, time_any: bool, has_time: bool):
    from ..scan import gscan

    def body(bxmin, bymin, bxmax, bymax, valid, tday, tms,
             outer, inner, bvalid, times, tvalid):
        return gscan._tristate_body(bxmin, bymin, bxmax, bymax, valid,
                                    tday, tms, outer, inner, bvalid,
                                    times, tvalid, time_any, has_time)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),) * 7 + (P(),) * 5,
        out_specs=P("data")))


def distributed_tristate(data: DistributedExtentData, q) -> np.ndarray:
    """Shard-local extent tristate classification over the mesh;
    returns host int8[n] (0=OUT, 1=MAYBE, 2=IN) with padding dropped.
    Same exactness contract as gscan.extent_tristate — the MAYBE band
    goes to the caller's exact host predicate."""
    fn = _tristate_fn(data.mesh, q.time_any, data.has_time)
    out = fn(data.bxmin, data.bymin, data.bxmax, data.bymax, data.valid,
             data.tday, data.tms,
             q.outer, q.inner, q.box_valid, q.times, q.time_valid)
    return np.asarray(out)[:data.n]


@functools.lru_cache(maxsize=32)
def _contains_fn(mesh: Mesh, band_cap: int):
    """Shard-local ST_Contains partial counts: every device runs the
    f32 crossing-number PIP over its own point shard for ALL polygons
    (lax.map — sequential per polygon, one launch), psums the definite
    counts over ICI, and compacts its band rows (global ids via
    axis_index) so the host patch stays O(band)."""
    from ..analytics.join import _pip_body
    from ..scan.gscan import EDGE_EPS

    def body(x, y, boxes, edges, evalid):
        eps = jnp.float32(EDGE_EPS)
        base = jax.lax.axis_index("data") * x.shape[0]

        def one(args):
            bx, e, ev = args
            inbox = ((x >= bx[0] - eps) & (x <= bx[2] + eps)
                     & (y >= bx[1] - eps) & (y <= bx[3] + eps))
            inside, band = _pip_body(x, y, e, ev)
            definite = inbox & inside & ~band
            banded = inbox & band
            bpos = jnp.flatnonzero(banded, size=band_cap, fill_value=-1)
            grows = jnp.where(bpos >= 0, base + bpos, -1)
            return (jnp.sum(definite, dtype=jnp.int32),
                    jnp.sum(banded, dtype=jnp.int32)[None],
                    grows.astype(jnp.int32))

        dc, bc, brows = jax.lax.map(one, (boxes, edges, evalid))
        return jax.lax.psum(dc, "data"), bc, brows

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P(), P()),
        out_specs=(P(), P(None, "data"), P(None, "data"))))


def distributed_contains_counts(data: DistributedScanData, polygons,
                                band_cap: int = 512) -> np.ndarray:
    """Mesh-sharded exact ST_Contains counts: points vs many polygons.

    The multi-chip promotion of analytics/join.contains_join's counts
    path — device-local partial verdicts merge over ICI (psum for the
    definite counts) and only per-shard band rows (points within
    gscan.EDGE_EPS of a boundary) come back for the exact host f64
    patch, so counts carry the same exact-by-construction contract.
    Shards whose band overflows ``band_cap`` fall back to an exact host
    recount of that polygon's bbox candidates (rare: the band is a
    ~1e-4 deg strip around the boundary)."""
    from ..analytics.join import _poly_pad, pack_polygon_batch
    from ..analytics.st_functions import contains_points
    k = len(polygons)
    counts = np.zeros(k, dtype=np.int64)
    if k == 0 or data.n == 0:
        return counts
    edges, evalid, boxes = pack_polygon_batch(
        polygons, pad_to=_poly_pad(k))
    dc, bc, brows = _contains_fn(data.mesh, int(band_cap))(
        data.xhi, data.yhi, jnp.asarray(boxes), jnp.asarray(edges),
        jnp.asarray(evalid))
    counts[:] = np.asarray(dc)[:k]
    bc = np.asarray(bc)[:k]          # (k, ndev) per-shard band counts
    brows = np.asarray(brows)[:k]    # (k, band_cap * ndev) global ids
    hx, hy = data.host_x, data.host_y
    for j in np.flatnonzero(bc.sum(axis=1)):
        poly = polygons[j]
        if (bc[j] > band_cap).any():
            # a shard compacted fewer band rows than it had: recount
            # this polygon exactly on host over its bbox candidates
            xmin, ymin, xmax, ymax = poly.envelope.as_tuple()
            m = ((hx >= xmin) & (hx <= xmax)
                 & (hy >= ymin) & (hy <= ymax))
            counts[j] = int(contains_points(poly, hx[m], hy[m]).sum())
            continue
        rows = brows[j]
        rows = rows[(rows >= 0) & (rows < data.n)]
        counts[j] += int(contains_points(poly, hx[rows],
                                         hy[rows]).sum())
    return counts


def distributed_density(data: DistributedScanData, q: zscan.ScanQuery,
                        bbox: tuple[float, float, float, float],
                        width: int, height: int) -> np.ndarray:
    """Density surface: shard-local scatter-add onto the pixel grid,
    psum over ICI (DensityScan analog, index/iterators/DensityScan.scala:30).
    Pixel-snap output; boundary-band f64 differences are below pixel
    resolution, so no host patch is applied."""
    fn = _density_fn(data.mesh, q.time_any,
                     tuple(float(v) for v in bbox), width, height)
    out = fn(*_args(data, q))
    return np.asarray(out).reshape(height, width)
