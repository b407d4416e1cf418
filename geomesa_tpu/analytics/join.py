"""Spatial joins on device: the ST_DWithin / ST_Contains join kernels.

The reference runs spatial joins via Spark: spatially-partitioned RDDs +
a per-cell sweepline (GeoMesaSparkSQL.scala:312-360, SQLRules
SpatialJoinStrategy:270). On TPU the join is a tiled device kernel:

- the small side (query points / polygons) is padded to a fixed chunk;
- the large side streams through the VPU in one fused program per chunk
  computing the (n x chunk) predicate matrix;
- borderline pairs (within the f32 error band of the threshold) are
  re-checked on host in f64, so results are exact.

Counting and pair-collection both avoid materializing the full bool
matrix on the host: counts reduce on device; pair extraction pulls only
per-chunk hit masks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxcache import ensure_compile_cache

ensure_compile_cache()

from ..scan.gscan import EDGE_EPS
from ..scan.zscan import next_pow2, stack_points
from ..utils.fp import f32_band as _f32_band

__all__ = ["dwithin_join", "contains_join", "knn", "knn_batched",
           "pack_polygon_batch", "prewarm_join_kernels", "psum_counts"]


def psum_counts(leg_counts) -> int:
    """psum-style reduce of per-shard join match counts: the z-prefix
    partition of the scattered side is disjoint and covering, so the
    cluster-wide broadcast-join count is exactly the sum of leg
    counts — the host-side analog of a ``jax.lax.psum`` over the
    shard axis."""
    return int(sum(int(c) for c in leg_counts))


@jax.jit
def _dwithin_matrices(px, py, qx, qy, qvalid, r2_hi, r2_lo, nrows):
    """(n,) x (k,) -> definite-hit and uncertain-band bool matrices."""
    dx = px[:, None] - qx[None, :]
    dy = py[:, None] - qy[None, :]
    d2 = dx * dx + dy * dy                       # f32, error-banded
    rv = (jnp.arange(px.shape[0]) < nrows)[:, None]
    definite = (d2 <= r2_lo) & qvalid[None, :] & rv
    maybe = (d2 <= r2_hi) & ~definite & qvalid[None, :] & rv
    return definite, maybe


@jax.jit
def _dwithin_counts_all(px, py, qxm, qym, validm, r2_hi, r2_lo, nrows):
    """ALL query chunks in one dispatch: (nchunks, chunk) query tiles
    map over the device sequentially; only the (nchunks, chunk) count
    grids come back. One kernel launch per join, not one per chunk —
    per-dispatch latency otherwise dominates the scan itself."""
    rv = (jnp.arange(px.shape[0]) < nrows)[:, None]

    def one(args):
        qx, qy, valid = args
        dx = px[:, None] - qx[None, :]
        dy = py[:, None] - qy[None, :]
        d2 = dx * dx + dy * dy
        definite = (d2 <= r2_lo) & valid[None, :] & rv
        maybe = (d2 <= r2_hi) & ~definite & valid[None, :] & rv
        return (jnp.sum(definite, axis=0, dtype=jnp.int32),
                jnp.sum(maybe, axis=0, dtype=jnp.int32))

    return jax.lax.map(one, (qxm, qym, validm))


@jax.jit
def _sorted_by_x(px, nrows):
    """(xs, order): px sorted ascending with its permutation, padded
    rows pushed to +inf so they land at the tail. One dispatch."""
    key = jnp.where(jnp.arange(px.shape[0]) < nrows, px, jnp.inf)
    order = jnp.argsort(key)
    return key[order], order


# device x-sort LRU keyed by the coordinate buffer identity: a store's
# resident column re-resolves bands across many join calls, and the
# sort is the dominant per-call cost. Strong refs keep the keys' ids
# stable; the bound keeps pinned memory to a few tables.
_XSORT_CACHE: list = []


def _sorted_by_x_cached(pxj, nrows, cacheable):
    """`cacheable` is True only for caller-owned resident arrays: a
    per-call upload gets a fresh buffer identity every time, so caching
    it could never hit — it would only evict store entries and pin dead
    device copies."""
    for i, (ref, rn, xs, order) in enumerate(_XSORT_CACHE):
        if ref is pxj and rn == nrows:
            _XSORT_CACHE.append(_XSORT_CACHE.pop(i))
            return xs, order
    xs, order = _sorted_by_x(pxj, np.int32(nrows))
    if cacheable:
        _XSORT_CACHE.append((pxj, nrows, xs, order))
        if len(_XSORT_CACHE) > 4:
            _XSORT_CACHE.pop(0)
    return xs, order


@jax.jit
def _slab_bounds(xs, qb, w):
    """Both slab edges in ONE program: a cold call pays one executable
    load instead of two."""
    los = jnp.searchsorted(xs, qb - w, side="left")
    his = jnp.searchsorted(xs, qb + w, side="right")
    return jnp.stack([los, his])


def _slab_cand_mask(xs, order, los, widths, qxc, qyc, px, py, r2_hi,
                    smax):
    """The shared in-band candidate grid (ONE body for the count and
    compact kernels — the two must never desynchronize)."""
    pos = jnp.clip(los[:, None] + jnp.arange(smax)[None, :], 0,
                   xs.shape[0] - 1)
    rows = order[pos]
    valid = jnp.arange(smax)[None, :] < widths[:, None]
    dx = px[rows] - qxc[:, None]
    dy = py[rows] - qyc[:, None]
    return valid & (dx * dx + dy * dy <= r2_hi)


@functools.partial(jax.jit, static_argnames=("smax",))
def _slab_cand_count(xs, order, los, widths, qxc, qyc, px, py, r2_hi,
                     smax):
    """Count of in-band slab candidates for a chunk of queries — the
    device side of pair materialization (fetching the full slab grid
    over a thin transport costs more than the whole join)."""
    return jnp.sum(_slab_cand_mask(xs, order, los, widths, qxc, qyc,
                                   px, py, r2_hi, smax),
                   dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("smax", "cap"))
def _slab_cand_flat(xs, order, los, widths, qxc, qyc, px, py, r2_hi,
                    smax, cap):
    """Flat (query, slab-col) indices of the in-band candidates,
    compacted on device to ``cap`` slots (-1 padded): transfers are
    O(candidates), never O(grid)."""
    cand = _slab_cand_mask(xs, order, los, widths, qxc, qyc, px, py,
                           r2_hi, smax)
    return jnp.flatnonzero(cand.ravel(), size=cap, fill_value=-1)


@functools.partial(jax.jit, static_argnames=("smax",))
def _slab_rows(xs, order, los, smax):
    """Row ids of up to smax sorted positions starting at each lo —
    the x-slab candidate gather for a batch of banded queries."""
    pos = los[:, None] + jnp.arange(smax)[None, :]
    pos = jnp.clip(pos, 0, xs.shape[0] - 1)
    return order[pos]


# total padded slab-grid ids per gather dispatch (64MB of int32): wide
# radii chunk the banded queries instead of materializing a
# (len(banded), max_width) grid in one shot
_SLAB_GRID_CAP = 1 << 24


def _slab_setup(pxj, n, cacheable, q_x64, radius_deg, r2_hi):
    """Shared slab-phase setup (ONE copy for the banded count
    resolution and pair materialization): device x-sort, slab
    half-width = radius + f32 rounding + band, batched searchsorted.
    Returns (xs, order, los, widths)."""
    xs, order = _sorted_by_x_cached(pxj, n, cacheable)
    eps = float(np.sqrt(max(r2_hi, 0.0))) - radius_deg + 1e-4
    w = radius_deg + eps
    lohi = np.asarray(_slab_bounds(
        xs, jnp.asarray(q_x64.astype(np.float32)), np.float32(w)))
    return xs, order, lohi[0], lohi[1] - lohi[0]


def _resolve_band_counts(pxj, px64, py64, qx64, qy64, banded,
                         radius_deg, r2_hi, n, counts, cacheable):
    """Exact f64 resolution of queries with in-band pairs.

    The candidate set per banded query is its x-slab |x - qx| <= r+eps:
    px sorts ON DEVICE once (f32, padded rows to +inf), a batched
    searchsorted finds every slab, and padded gathers pull just the
    slab row ids to the host for a vectorized f64 distance check — no
    O(n) host work, no (k, n) band matrix. Gathers are bounded at
    _SLAB_GRID_CAP ids each, so wide radii chunk rather than allocate
    a queries x max-width grid."""
    xs, order, los, widths = _slab_setup(pxj, n, cacheable,
                                         qx64[banded], radius_deg,
                                         r2_hi)
    if not len(widths) or widths.max() == 0:
        return
    smax = 1 << int(widths.max() - 1).bit_length()  # pow2: few compiles
    r2 = radius_deg * radius_deg
    qchunk = max(1, _SLAB_GRID_CAP // smax)
    for s in range(0, len(banded), qchunk):
        sel = slice(s, s + qchunk)
        rows = np.asarray(_slab_rows(xs, order,
                                     jnp.asarray(los[sel]), smax))
        for i, qj in enumerate(banded[sel]):
            rr = rows[i, : widths[s + i]]
            rr = rr[rr < n]
            d2 = ((px64[rr] - qx64[qj]) ** 2
                  + (py64[rr] - qy64[qj]) ** 2)
            counts[qj] = int((d2 <= r2).sum())


def _as_device_f32(px64, py64, device_xy):
    """The join's large side on device: adopt caller-provided resident
    f32 columns (e.g. a store's scan_data.xhi/yhi, which are exactly
    f32(x)/f32(y) of the two-float split and may be capacity-padded
    past n) or upload once."""
    if device_xy is not None:
        pxj, pyj = device_xy
        return jnp.asarray(pxj), jnp.asarray(pyj)
    return (jnp.asarray(px64.astype(np.float32)),
            jnp.asarray(py64.astype(np.float32)))


def dwithin_join(px: np.ndarray, py: np.ndarray,
                 qx: np.ndarray, qy: np.ndarray,
                 radius_deg: float, chunk: int = 256,
                 counts_only: bool = False,
                 device_xy=None):
    """Radius join: for each query point, the points within radius_deg
    (planar degrees, matching the rewritten-DWithin semantics).

    Returns (counts[k], pairs) where pairs is an (m, 2) int array of
    (point_idx, query_idx), or (counts, None) with counts_only.

    ``counts_only`` reduces per-query counts fully on device (chunked
    by ``chunk`` queries per dispatch) with only banded queries
    resolved via x-slabs. The pairs path ignores ``chunk``: it runs
    entirely on x-slab candidates — in-band hits compact ON DEVICE and
    only O(candidates) indices cross to the host (a dense verdict
    grid would cost gigabytes of device->host transfer at 100k+ rows
    per side), then exact f64 filters the f32 band.

    ``device_xy`` passes already-device-resident f32 coordinate arrays
    for the large side (possibly capacity-padded beyond len(px); padded
    rows never match). Without it the coordinates upload per call —
    fine for one-off joins, but a store-backed caller should hand over
    its resident columns.
    """
    px64 = np.asarray(px, np.float64)
    py64 = np.asarray(py, np.float64)
    qx64 = np.asarray(qx, np.float64)
    qy64 = np.asarray(qy, np.float64)
    pxj, pyj = _as_device_f32(px64, py64, device_xy)
    n, k = len(px64), len(qx64)
    span = 360.0
    r2_hi, r2_lo = _f32_band(radius_deg, span)
    r2 = radius_deg * radius_deg

    counts = np.zeros(k, dtype=np.int64)
    pair_chunks: list[np.ndarray] = []

    if counts_only:
        nchunks = (k + chunk - 1) // chunk
        qxm = np.zeros((nchunks, chunk), np.float32)
        qym = np.zeros((nchunks, chunk), np.float32)
        validm = np.zeros((nchunks, chunk), bool)
        qxm.ravel()[:k] = qx64
        qym.ravel()[:k] = qy64
        validm.ravel()[:k] = True
        def_counts, band_counts = _dwithin_counts_all(
            pxj, pyj, jnp.asarray(qxm), jnp.asarray(qym),
            jnp.asarray(validm), np.float32(r2_hi), np.float32(r2_lo),
            np.int32(n))
        counts[:] = np.asarray(def_counts).ravel()[:k]
        band_counts = np.asarray(band_counts).ravel()[:k]
        # queries with in-band pairs re-resolve exactly from their
        # device-gathered x-slab candidates (see _resolve_band_counts)
        banded = np.flatnonzero(band_counts)
        if len(banded):
            _resolve_band_counts(pxj, px64, py64, qx64, qy64, banded,
                                 radius_deg, r2_hi, n, counts,
                                 cacheable=device_xy is not None)
        return counts, None

    # pair materialization via bounded x-slabs (same candidate shape as
    # _resolve_band_counts): the old path pulled a DENSE (n, chunk)
    # verdict matrix to the host per chunk — at 100k+ rows per side
    # that is gigabytes of device->host transfer; slabs move only
    # O(candidates) and the exact f64 check vectorizes over the grid
    if n == 0 or k == 0:
        return counts, np.empty((0, 2), dtype=np.int64)
    xs, order, los, widths = _slab_setup(pxj, n, device_xy is not None,
                                         qx64, radius_deg, r2_hi)
    if not len(widths) or widths.max() == 0:
        return counts, np.empty((0, 2), dtype=np.int64)
    smax = 1 << int(widths.max() - 1).bit_length()
    qchunk = max(1, _SLAB_GRID_CAP // smax)
    order_h = np.asarray(order)  # host copy (n int32) for row lookup
    for s in range(0, k, qchunk):
        end = min(s + qchunk, k)
        losj = jnp.asarray(los[s:end])
        wj = jnp.asarray(widths[s:end])
        qxc = jnp.asarray(qx64[s:end].astype(np.float32))
        qyc = jnp.asarray(qy64[s:end].astype(np.float32))
        total = int(_slab_cand_count(xs, order, losj, wj, qxc, qyc,
                                     pxj, pyj, np.float32(r2_hi), smax))
        if not total:
            continue
        cap = 1 << (total - 1).bit_length()
        flat = np.asarray(_slab_cand_flat(
            xs, order, losj, wj, qxc, qyc, pxj, pyj,
            np.float32(r2_hi), smax, cap))
        flat = flat[flat >= 0]
        qi = flat // smax
        ci = flat - qi * smax
        rows = order_h[np.minimum(los[s + qi] + ci, len(order_h) - 1)]
        ok = rows < n
        rows, qi = rows[ok], qi[ok]
        # exact f64 check on just the fetched candidates (the in-band
        # f32 verdict over-approximates)
        exact = ((px64[rows] - qx64[s + qi]) ** 2
                 + (py64[rows] - qy64[s + qi]) ** 2) <= r2
        if exact.any():
            pair_chunks.append(np.stack(
                [rows[exact], s + qi[exact]], axis=1).astype(np.int64))

    pairs = (np.concatenate(pair_chunks, axis=0) if pair_chunks
             else np.empty((0, 2), dtype=np.int64))
    if len(pairs):
        counts[:] = np.bincount(pairs[:, 1], minlength=k)
    return counts, pairs


# -- ST_Contains join ------------------------------------------------------

def _poly_edges(poly) -> np.ndarray:
    """One polygon/multipolygon's rings as an (e, 4) f64 segment list
    [x0 y0 x1 y1] — scan/gscan.pack_polygon's packing, host-side.
    Holes are included: crossing-number parity handles them uniformly.
    """
    rings: list[np.ndarray] = []
    for p in getattr(poly, "parts", [poly]):
        rings.append(np.asarray(p.shell, np.float64))
        for h in getattr(p, "holes", []):
            rings.append(np.asarray(h, np.float64))
    segs = []
    for ring in rings:
        a = ring[:-1] if np.allclose(ring[0], ring[-1]) else ring
        b = np.roll(a, -1, axis=0)
        segs.append(np.concatenate([a, b], axis=1))
    return (np.concatenate(segs, axis=0) if segs
            else np.zeros((0, 4), np.float64))


def _poly_pad(k: int) -> int:
    """Polygon-batch shape class: pow2 up to 1024, then the next 1024
    multiple — bounds padding waste at large k while keeping the
    compile-cache class family small."""
    return next_pow2(k) if k <= 1024 else ((k + 1023) // 1024) * 1024


def pack_polygon_batch(polygons, pad_to: int | None = None):
    """Stack every polygon's edges into one batched-geometry layout:
    (kp, ne, 4) f32 edges + (kp, ne) valid + (kp, 4) f32 envelopes,
    pow2-padded on the edge dim and padded to ``pad_to`` polygons.
    Padding rows carry an inverted envelope and no edges — they match
    nothing. Shared by the slab kernel and the mesh shard_map kernel.
    """
    k = len(polygons)
    kp = max(pad_to or k, k, 1)
    elist = [_poly_edges(p) for p in polygons]
    ne = next_pow2(max((len(e) for e in elist), default=1) or 1)
    edges = np.zeros((kp, ne, 4), np.float32)
    evalid = np.zeros((kp, ne), dtype=bool)
    boxes = np.full((kp, 4), 1e9, np.float32)
    boxes[:, 2:] = -1e9
    for i, e in enumerate(elist):
        edges[i, : len(e)] = e
        evalid[i, : len(e)] = True
        boxes[i] = polygons[i].envelope.as_tuple()
    return edges, evalid, boxes


def _pip_body(x, y, edges, evalid):
    """f32 crossing-number + uncertainty band for a coordinate block vs
    ONE polygon's padded edges — scan/gscan._pip_kernel's arithmetic,
    kept identical so both device PIP paths share one exactness
    contract (band rows re-check on host in f64)."""
    x0 = edges[None, :, 0]
    y0 = edges[None, :, 1]
    x1 = edges[None, :, 2]
    y1 = edges[None, :, 3]
    pxc = x[:, None]
    pyc = y[:, None]
    cond = (y0 > pyc) != (y1 > pyc)
    dy = jnp.where(y1 == y0, jnp.float32(1e-30), y1 - y0)
    xint = x0 + (pyc - y0) * (x1 - x0) / dy
    cross = cond & (pxc < xint) & evalid[None, :]
    inside = (jnp.sum(cross, axis=1) % 2) == 1

    ex = x1 - x0
    ey = y1 - y0
    len2 = ex * ex + ey * ey
    t = jnp.clip(((pxc - x0) * ex + (pyc - y0) * ey)
                 / jnp.where(len2 == 0, jnp.float32(1.0), len2), 0.0, 1.0)
    dxv = pxc - (x0 + t * ex)
    dyv = pyc - (y0 + t * ey)
    d2 = dxv * dxv + dyv * dyv
    d2 = jnp.where(evalid[None, :], d2, jnp.float32(np.inf))
    band = jnp.min(d2, axis=1) < jnp.float32(EDGE_EPS * EDGE_EPS)
    return inside, band


@functools.partial(jax.jit, static_argnames=("smax", "band_cap"))
def _contains_counts_all(xs, order, los, widths, boxes, edges, evalid,
                         px, py, nrows, smax, band_cap):
    """ALL polygons in ONE dispatch: lax.map over the padded polygon
    batch; each step gathers its x-slab candidates, runs the bbox test
    and the f32 crossing-number PIP, and reduces on device to
    (definite_count, band_count, up to band_cap band row ids). Only
    O(kp * band_cap) scalars reach the host — never the (n, k)
    verdict matrix that made the old path transfer-bound."""
    eps = jnp.float32(EDGE_EPS)
    cols = jnp.arange(smax)

    def one(args):
        lo, width, bx, e, ev = args
        pos = jnp.clip(lo + cols, 0, xs.shape[0] - 1)
        rows = order[pos]
        x = px[rows]
        y = py[rows]
        ok = (cols < width) & (rows < nrows)
        inbox = (ok & (x >= bx[0] - eps) & (x <= bx[2] + eps)
                 & (y >= bx[1] - eps) & (y <= bx[3] + eps))
        inside, band = _pip_body(x, y, e, ev)
        definite = inbox & inside & ~band
        banded = inbox & band
        bpos = jnp.flatnonzero(banded, size=band_cap, fill_value=-1)
        brow = jnp.where(bpos >= 0,
                         rows[jnp.clip(bpos, 0, smax - 1)], -1)
        return (jnp.sum(definite, dtype=jnp.int32),
                jnp.sum(banded, dtype=jnp.int32),
                brow.astype(jnp.int32))

    return jax.lax.map(one, (los, widths, boxes, edges, evalid))


@functools.partial(jax.jit, static_argnames=("smax", "cap"))
def _contains_band_rows(xs, order, lo, width, bx, e, ev, px, py, nrows,
                        smax, cap):
    """Band-row re-extraction for ONE polygon whose band overflowed the
    batched kernel's band_cap (rare: band rows are points within
    EDGE_EPS of the boundary)."""
    eps = jnp.float32(EDGE_EPS)
    cols = jnp.arange(smax)
    pos = jnp.clip(lo + cols, 0, xs.shape[0] - 1)
    rows = order[pos]
    x = px[rows]
    y = py[rows]
    ok = (cols < width) & (rows < nrows)
    inbox = (ok & (x >= bx[0] - eps) & (x <= bx[2] + eps)
             & (y >= bx[1] - eps) & (y <= bx[3] + eps))
    _, band = _pip_body(x, y, e, ev)
    bpos = jnp.flatnonzero(inbox & band, size=cap, fill_value=-1)
    return jnp.where(bpos >= 0, rows[jnp.clip(bpos, 0, smax - 1)], -1)


def _contains_cand_mask(xs, order, los, widths, boxes, px, py, nrows,
                        smax):
    """Shared bbox-candidate grid for the pairs path (the count and
    compact kernels must never desynchronize — same contract as
    _slab_cand_mask)."""
    eps = jnp.float32(EDGE_EPS)
    pos = jnp.clip(los[:, None] + jnp.arange(smax)[None, :], 0,
                   xs.shape[0] - 1)
    rows = order[pos]
    x = px[rows]
    y = py[rows]
    ok = ((jnp.arange(smax)[None, :] < widths[:, None])
          & (rows < nrows))
    return (ok & (x >= boxes[:, None, 0] - eps)
            & (x <= boxes[:, None, 2] + eps)
            & (y >= boxes[:, None, 1] - eps)
            & (y <= boxes[:, None, 3] + eps))


@functools.partial(jax.jit, static_argnames=("smax",))
def _contains_cand_count(xs, order, los, widths, boxes, px, py, nrows,
                         smax):
    return jnp.sum(_contains_cand_mask(xs, order, los, widths, boxes,
                                       px, py, nrows, smax),
                   dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("smax", "cap"))
def _contains_cand_flat(xs, order, los, widths, boxes, px, py, nrows,
                        smax, cap):
    cand = _contains_cand_mask(xs, order, los, widths, boxes, px, py,
                               nrows, smax)
    return jnp.flatnonzero(cand.ravel(), size=cap, fill_value=-1)


def _contains_slab_setup(xs, boxes64):
    """Per-polygon x-slabs from envelope centers: slab half-width =
    envelope half-width + 2*EDGE_EPS, which dominates both the bbox
    widening eps and the f32 rounding of center/half (~1.5e-5 deg), so
    every point passing the widened f32 bbox test lies in its slab."""
    cxs = (boxes64[:, 0] + boxes64[:, 2]) * 0.5
    half = (boxes64[:, 2] - boxes64[:, 0]) * 0.5 + 2.0 * EDGE_EPS
    lohi = np.asarray(_slab_bounds(
        xs, jnp.asarray(cxs.astype(np.float32)),
        jnp.asarray(half.astype(np.float32))))
    return lohi[0], lohi[1] - lohi[0]


def contains_join(polygons, px: np.ndarray, py: np.ndarray,
                  counts_only: bool = False, device_xy=None):
    """ST_Contains join: points vs many polygons (BASELINE config #5).

    Counts path: ONE fused dispatch — lax.map over the pow2-padded
    polygon batch; per polygon an x-slab candidate gather (the dwithin
    slab machinery; the device x-sort caches per resident buffer), the
    f32 crossing-number PIP with gscan's EDGE_EPS uncertainty band, and
    a device reduce to (definite, band) counts plus band row ids. Only
    O(k) counts and O(band) rows cross to the host; band rows re-check
    in exact f64 (closed-boundary contains_points semantics), so counts
    are exact by the same contract as scan/gscan.points_in_polygon.
    The replaced implementation fetched a dense (n, 64) bbox matrix to
    the host per polygon chunk — gigabytes of device->host transfer at
    100M rows, which is what regressed config 5.

    Pairs path: device count-then-compact of bbox candidates per slab
    grid chunk (O(candidates) transfer), exact host f64 PIP per
    candidate.

    ``device_xy`` passes resident f32 columns (see dwithin_join).
    """
    from .st_functions import contains_points
    px64 = np.asarray(px, np.float64)
    py64 = np.asarray(py, np.float64)
    k = len(polygons)
    n = len(px64)
    counts = np.zeros(k, dtype=np.int64)
    empty = None if counts_only else np.empty((0, 2), dtype=np.int64)
    if k == 0 or n == 0:
        return counts, empty

    boxes64 = np.array([p.envelope.as_tuple() for p in polygons],
                       np.float64).reshape(k, 4)
    pxj, pyj = _as_device_f32(px64, py64, device_xy)
    xs, order = _sorted_by_x_cached(pxj, n, device_xy is not None)
    los, widths = _contains_slab_setup(xs, boxes64)
    wmax = int(widths.max()) if len(widths) else 0
    if wmax == 0:
        return counts, empty
    smax = 1 << (wmax - 1).bit_length()

    if counts_only:
        kp = _poly_pad(k)
        edges, evalid, boxes32 = pack_polygon_batch(polygons, pad_to=kp)
        losp = np.zeros(kp, los.dtype)
        widthsp = np.zeros(kp, widths.dtype)
        losp[:k] = los
        widthsp[:k] = widths
        band_cap = 256
        dc, bc, brows = _contains_counts_all(
            xs, order, jnp.asarray(losp), jnp.asarray(widthsp),
            jnp.asarray(boxes32), jnp.asarray(edges),
            jnp.asarray(evalid), pxj, pyj, np.int32(n), smax, band_cap)
        counts[:] = np.asarray(dc)[:k]
        bc = np.asarray(bc)[:k]
        brows = np.asarray(brows)[:k]
        for j in np.flatnonzero(bc):
            rows_j = brows[j]
            rows_j = rows_j[rows_j >= 0]
            if int(bc[j]) > band_cap:
                cap = 1 << (int(bc[j]) - 1).bit_length()
                rows_j = np.asarray(_contains_band_rows(
                    xs, order, np.int32(los[j]), np.int32(widths[j]),
                    jnp.asarray(boxes32[j]), jnp.asarray(edges[j]),
                    jnp.asarray(evalid[j]), pxj, pyj, np.int32(n),
                    smax, cap))
                rows_j = rows_j[rows_j >= 0]
            hit = contains_points(polygons[j], px64[rows_j],
                                  py64[rows_j])
            counts[j] += int(hit.sum())
        return counts, None

    # pairs: bbox candidates compact on device per slab-grid chunk,
    # then the exact host PIP decides each candidate in f64 (no band
    # machinery needed — every candidate is checked exactly)
    pair_chunks: list[np.ndarray] = []
    qchunk = max(1, _SLAB_GRID_CAP // smax)
    order_h = np.asarray(order)
    boxes32 = boxes64.astype(np.float32)
    for s in range(0, k, qchunk):
        end = min(s + qchunk, k)
        losj = jnp.asarray(los[s:end])
        wj = jnp.asarray(widths[s:end])
        bxj = jnp.asarray(boxes32[s:end])
        total = int(_contains_cand_count(xs, order, losj, wj, bxj,
                                         pxj, pyj, np.int32(n), smax))
        if not total:
            continue
        cap = 1 << (total - 1).bit_length()
        flat = np.asarray(_contains_cand_flat(
            xs, order, losj, wj, bxj, pxj, pyj, np.int32(n), smax, cap))
        flat = flat[flat >= 0]
        qi = flat // smax
        ci = flat - qi * smax
        rows = order_h[np.minimum(los[s + qi] + ci, len(order_h) - 1)]
        ok = rows < n
        rows, qi = rows[ok], qi[ok]
        for j in range(s, end):
            sel = rows[qi == j - s]
            if not len(sel):
                continue
            hit = contains_points(polygons[j], px64[sel], py64[sel])
            sel = sel[hit]
            counts[j] = len(sel)
            if len(sel):
                pair_chunks.append(np.stack(
                    [sel, np.full(len(sel), j)], axis=1).astype(np.int64))
    pairs = (np.concatenate(pair_chunks, axis=0) if pair_chunks
             else np.empty((0, 2), dtype=np.int64))
    return counts, pairs


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_kernel(px, py, qx, qy, k: int, nrows):
    """Fused MULTI-query top-k: qx/qy are a pow2-padded (Q,) query
    batch; lax.map runs the per-query two-stage top-k sequentially
    inside ONE compiled program, so a Q-query KNN pays one kernel
    launch (and one host fetch) instead of Q. The body compiles once
    per (capacity, Q-class, k-class) triple and keys stably into the
    persistent compilation cache."""
    rv = jnp.arange(px.shape[0]) < nrows

    def one(q):
        qxi, qyi = q
        d2 = (px - qxi) ** 2 + (py - qyi) ** 2
        # capacity-padded resident columns: padded rows never win
        d2 = jnp.where(rv, d2, jnp.inf)
        n = d2.shape[0]
        bs = 16384
        if n > 4 * bs:
            # two-stage exact top-k: per-block top-k batched over
            # blocks (the vectorized shape the TPU sorts fast), then a
            # final top-k over nb*k candidates — a single flat top_k
            # over 50M+ elements lowers to a full-array sort and
            # dominates the whole query
            nb = (n + bs - 1) // bs
            pad = nb * bs - n
            d2p = jnp.pad(d2, (0, pad), constant_values=jnp.inf)
            kb = min(k, bs)
            neg, loc = jax.lax.top_k(-d2p.reshape(nb, bs), kb)
            cand_idx = (jnp.arange(nb)[:, None] * bs + loc).ravel()
            neg2, loc2 = jax.lax.top_k(neg.ravel(), k)
            return -neg2, cand_idx[loc2]
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx

    return jax.lax.map(one, (qx, qy))


def knn_batched(px: np.ndarray, py: np.ndarray,
                qx, qy, k: int, device_xy=None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Multi-query KNN: ONE fused device dispatch answers all Q query
    points (the reference KNearestNeighborSearchProcess takes a
    *collection* of query features for the same reason — per-query
    overhead dominates). Returns (distances (Q, k), indices (Q, k)),
    each row ascending by exact f64 distance.

    The query batch pads to a pow2 (scan/zscan.stack_points) and the
    candidate count to the pow2 class next_pow2(k + 32), so every
    (capacity, Q, k) shape class keys stably into the persistent
    compilation cache and a prewarmed table answers its first query
    without compiling.

    Ties are ID-STABLE: XLA's top_k prefers the lower index on equal
    values, and the host f64 re-rank sorts (distance, id)
    lexicographically — equal-distance points at the k boundary resolve
    to the smallest row ids, deterministically, in the batched and
    single-query paths alike.
    """
    px64 = np.asarray(px, np.float64)
    py64 = np.asarray(py, np.float64)
    qx64 = np.atleast_1d(np.asarray(qx, np.float64))
    qy64 = np.atleast_1d(np.asarray(qy, np.float64))
    nq = len(qx64)
    n = len(px64)
    k = min(k, n)
    if nq == 0 or k <= 0:
        return (np.zeros((nq, max(k, 0))),
                np.zeros((nq, max(k, 0)), np.int64))
    pxj, pyj = _as_device_f32(px64, py64, device_xy)
    kpad = min(next_pow2(k + 32), int(pxj.shape[0]))
    qxp, qyp, _ = stack_points(qx64, qy64)
    d2, idx = _knn_kernel(pxj, pyj, jnp.asarray(qxp), jnp.asarray(qyp),
                          kpad, np.int32(n))
    idx = np.asarray(idx)[:nq].astype(np.int64)
    # f32 distances can tie/misorder within ~1e-5 deg: the k + 32
    # candidate slack absorbs the misordering and the host re-ranks the
    # window in f64. Capacity padding can surface idx >= n only when
    # kpad exceeds n; those slots rank last and never reach the first
    # k <= n positions.
    safe = np.minimum(idx, n - 1)
    dx = px64[safe] - qx64[:, None]
    dy = py64[safe] - qy64[:, None]
    exact = np.sqrt(dx * dx + dy * dy)
    exact[idx >= n] = np.inf
    dists = np.empty((nq, k), np.float64)
    ids = np.empty((nq, k), np.int64)
    for i in range(nq):
        top = np.lexsort((idx[i], exact[i]))[:k]
        dists[i] = exact[i][top]
        ids[i] = idx[i][top]
    return dists, ids


def knn(px: np.ndarray, py: np.ndarray, qx: float, qy: float,
        k: int, device_xy=None) -> tuple[np.ndarray, np.ndarray]:
    """k nearest points to (qx, qy): full-scan distance + device top_k.

    The reference's KNNQuery iteratively expands a geohash spiral
    (process/knn/KNNQuery.scala:27) to avoid touching all rows; at TPU
    scan rates the full scan IS the fast path — one fused kernel, no
    iteration. Returns (distances_deg, indices) sorted ascending.

    This is the batched path with Q = 1 (same kernel shape classes,
    same id-stable tiebreak — see knn_batched). ``device_xy`` passes
    resident f32 columns (see dwithin_join) so a store-backed KNN
    never re-uploads its table.
    """
    d, ids = knn_batched(px, py, float(qx), float(qy), k,
                         device_xy=device_xy)
    return d[0], ids[0]


def prewarm_join_kernels(px64, py64, device_xy=None,
                         radius_deg: float = 0.25,
                         query_counts=(1024,), knn_batches=(1, 8),
                         knn_k: int = 100) -> None:
    """Compile (or load from the persistent compilation cache) the
    dwithin/KNN kernel family for this table's capacity class.

    Called from DataStore ingest (``geomesa.join.prewarm``) the way the
    z-scan path eagerly builds its index, so the FIRST join/KNN query
    pays a cache hit instead of a multi-second XLA compile. Dummy
    queries spread across the x-domain so the slab width — and its pow2
    shape class — matches what domain-wide query batches see. The
    dwithin counts kernel's shape class is (ceil(nq/256), 256); the
    1024 default compiles the four-chunk class the canonical 1k-query
    join workload lands in.
    """
    n = len(px64)
    if n == 0:
        return
    from ..obs.runtime import runtime
    cap = 1 << max(int(n - 1).bit_length(), 0)
    for nq in query_counts:
        qx = np.linspace(-170.0, 170.0, nq)
        qy = np.zeros(nq)
        # a prewarm IS the compile for its shape class: report it as a
        # miss so the runtime plane sees where traces come from
        runtime.note_plan_probe("join", ("dwithin", cap, int(nq)),
                                hit=False)
        dwithin_join(px64, py64, qx, qy, radius_deg, counts_only=True,
                     device_xy=device_xy)
    for q in knn_batches:
        runtime.note_plan_probe("join", ("knn", cap, int(q)), hit=False)
        knn_batched(px64, py64, np.zeros(q), np.zeros(q),
                    min(knn_k, n), device_xy=device_xy)
