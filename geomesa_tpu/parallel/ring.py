"""Ring collectives: distributed join + KNN over the device mesh.

The reference scales joins by spatially partitioning both sides and
joining partition-aligned pairs on Spark executors
(GeoMesaSparkSQL.scala:228-289,312-360 zipPartitions sweepline); its
KNN is an iterative geohash-spiral (knn/KNNQuery.scala:27). On a TPU
mesh the same work becomes ring pipelines (the ring-attention shape):

- **Ring join**: left side stays sharded and resident; the right side's
  shard rotates around the ring via ``ppermute``. After ``n_devices``
  steps every (left-shard, right-shard) block pair has met exactly
  once, with compute and ICI transfer overlapped — no all-gather
  memory spike, communication cost = one right-shard per step over
  ICI (SURVEY.md §2.6 "TPU-native equivalent").
- **KNN**: shard-local top-k prune (f32), ``all_gather`` of the tiny
  per-shard candidate sets, exact f64 re-rank on host.

f32 distance arithmetic is conservative: pairs within ``band`` of the
radius are counted separately so callers can resolve them exactly on
host (same two-tier contract as analytics/join.dwithin_join).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxcache import ensure_compile_cache

ensure_compile_cache()
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_dwithin_counts", "distributed_knn", "shard_points",
           "shard_points_split"]


def shard_points(x: np.ndarray, y: np.ndarray, mesh: Mesh, fill=1e9):
    """Pad to equal shards and device_put sharded f32 coords.

    Returns (xj, yj, valid, n): pad rows get `fill` (far outside any
    realistic query) and valid=False."""
    n = len(x)
    k = mesh.devices.size
    n_padded = ((n + k - 1) // k) * k
    pad = n_padded - n

    def prep(a):
        a = np.asarray(a, np.float64).astype(np.float32)
        return np.concatenate([a, np.full(pad, fill, np.float32)]) if pad else a

    valid = np.ones(n_padded, dtype=bool)
    valid[n:] = False
    sharding = NamedSharding(mesh, P("data"))
    put = functools.partial(jax.device_put, device=sharding)
    return put(prep(x)), put(prep(y)), put(valid), n


@functools.lru_cache(maxsize=32)
def _ring_dwithin_fn(mesh: Mesh, r_in2: float, r_out2: float):
    k = mesh.shape["data"]
    perm = [(i, (i + 1) % k) for i in range(k)]

    def body(lx, ly, lvalid, rx, ry, rvalid):
        def block(rx, ry, rvalid, sure, band):
            d2 = ((lx[:, None] - rx[None, :]) ** 2
                  + (ly[:, None] - ry[None, :]) ** 2)
            ok = rvalid[None, :]
            sure = sure + jnp.sum((d2 <= r_in2) & ok, axis=1,
                                  dtype=jnp.int32)
            band = band + jnp.sum((d2 > r_in2) & (d2 <= r_out2) & ok,
                                  axis=1, dtype=jnp.int32)
            return sure, band

        def step(_, carry):
            rx, ry, rvalid, sure, band = carry
            sure, band = block(rx, ry, rvalid, sure, band)
            rx = lax.ppermute(rx, "data", perm)
            ry = lax.ppermute(ry, "data", perm)
            rvalid = lax.ppermute(rvalid, "data", perm)
            return rx, ry, rvalid, sure, band

        # the carry must be marked device-varying over the mesh axis to
        # match the loop outputs under shard_map
        zeros = lax.pcast(jnp.zeros(lx.shape, jnp.int32), "data",
                          to="varying")
        # k-1 [compute, rotate] steps, then the final block without the
        # rotation (its permuted output would be discarded)
        rx, ry, rvalid, sure, band = lax.fori_loop(
            0, k - 1, step, (rx, ry, rvalid, zeros, zeros))
        sure, band = block(rx, ry, rvalid, sure, band)
        return jnp.where(lvalid, sure, 0), jnp.where(lvalid, band, 0)

    specs = (P("data"),) * 6
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=(P("data"), P("data"))))


def ring_dwithin_counts(lx, ly, lvalid, rx, ry, rvalid, mesh: Mesh,
                        radius_deg: float, coord_span: float = 360.0):
    """Per-left-point neighbor counts within `radius_deg` (planar) of
    any right point, via the ring pipeline.

    Returns (sure, band_counts) host int32 arrays over the padded left
    length: `sure` pairs are definitely within radius in f64 terms;
    left rows with band_counts > 0 have pairs inside the f32 error band
    around the radius and need a host f64 recheck for exact totals.
    The band is derived from f32 eps and `coord_span` (the coordinate
    magnitude bound — 360 for degrees; pass the actual span for
    projected coordinates) via the same rule as
    analytics/join._f32_band, so the contract holds at any scale.
    """
    from ..utils.fp import f32_band
    r2_hi, r2_lo = f32_band(radius_deg, coord_span)
    fn = _ring_dwithin_fn(mesh, float(r2_lo), float(r2_hi))
    sure, bandc = fn(lx, ly, lvalid, rx, ry, rvalid)
    return np.asarray(sure), np.asarray(bandc)


def shard_points_split(x: np.ndarray, y: np.ndarray, mesh: Mesh,
                       fill=1e9):
    """Two-float sharded coords: ((xhi, xlo, yhi, ylo), valid, n).

    The (hi, lo) pairs reconstruct f64 to ~1e-12 deg on host, so exact
    re-ranks never need a full host coordinate copy — candidate coords
    travel back with the candidates themselves (tiny transfers), which
    is what keeps distributed KNN distributed at 50M+ rows."""
    from ..scan.zscan import split_two_float
    n = len(x)
    k = mesh.devices.size
    n_padded = ((n + k - 1) // k) * k
    pad = n_padded - n

    def padded(a):
        a = np.asarray(a, np.float64)
        return np.concatenate([a, np.full(pad, fill)]) if pad else a

    xhi, xlo = split_two_float(padded(x))
    yhi, ylo = split_two_float(padded(y))
    valid = np.ones(n_padded, dtype=bool)
    valid[n:] = False
    sharding = NamedSharding(mesh, P("data"))
    put = functools.partial(jax.device_put, device=sharding)
    return ((put(xhi), put(xlo), put(yhi), put(ylo)), put(valid), n)


@functools.lru_cache(maxsize=32)
def _knn_prune_split_fn(mesh: Mesh, k: int):
    """Shard-local prune that also ships each candidate's two-float
    coords back — the exact re-rank needs only these 4k floats per
    shard, not the whole table."""
    def body(xhi, xlo, yhi, ylo, pvalid, q):
        d2 = (xhi - q[0]) ** 2 + (yhi - q[1]) ** 2
        d2 = jnp.where(pvalid, d2, jnp.float32(np.inf))
        neg_top, idx = lax.top_k(-d2, k)
        shard = lax.axis_index("data")
        gids = shard.astype(jnp.int32) * xhi.shape[0] + idx.astype(jnp.int32)
        take = lambda a: jnp.take(a, idx)
        return (-neg_top, gids, take(xhi), take(xlo), take(yhi), take(ylo))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data"),) * 5 + (P(),),
        out_specs=(P("data"),) * 6))


@functools.lru_cache(maxsize=32)
def _knn_prune_fn(mesh: Mesh, k: int):
    def body(px, py, pvalid, q):
        d2 = (px - q[0]) ** 2 + (py - q[1]) ** 2
        d2 = jnp.where(pvalid, d2, jnp.float32(np.inf))
        neg_top, idx = lax.top_k(-d2, k)
        # global row ids: shard offset + local index
        shard = lax.axis_index("data")
        gids = shard.astype(jnp.int32) * px.shape[0] + idx.astype(jnp.int32)
        # each shard emits its k candidates; the (k * n_devices)-row
        # sharded outputs gather host-side (tiny transfer)
        return -neg_top, gids

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data"), P("data"), P()),
        out_specs=(P("data"), P("data"))))


def distributed_knn(px, py, pvalid, mesh: Mesh, n: int,
                    qx: float, qy: float, k: int,
                    host_x: np.ndarray | None = None,
                    host_y: np.ndarray | None = None,
                    split=None) -> np.ndarray:
    """k nearest rows to (qx, qy): device prune to k candidates per
    shard, gather the tiny candidate sets, exact re-rank on host.

    Each shard over-fetches (2k + 16 candidates, clamped to the shard
    length) so f32 ranking ties at the k-th boundary don't drop a true
    f64 top-k member; the result is exact unless more than 2k + 16
    points of one shard land inside the f32 error band of the k-th
    distance (vanishing for real data; the reference's geohash-spiral
    KNN is likewise approximate at its precision floor,
    knn/KNNQuery.scala:27).

    Exact re-rank sources, in preference order:
    - ``split`` (from shard_points_split, pass px=py=None): candidates
      return WITH their two-float coords, reconstructed host-side to
      ~1e-12 deg — no host coordinate copy at any scale;
    - ``host_x/host_y``: full f64 host arrays (small tables only);
    - neither: the f32 device distances rank as-is.
    Returns global row indices, nearest first.
    """
    kk = min(k, max(n, 1))
    size = mesh.devices.size
    shard_len = (split[0] if split is not None else px).shape[0] // size
    fetch = min(2 * kk + 16, max(shard_len, 1))
    q = jnp.asarray(np.array([qx, qy], np.float32))
    if split is not None:
        fn = _knn_prune_split_fn(mesh, fetch)
        dists, gids, cxh, cxl, cyh, cyl = fn(*split, pvalid, q)
        dists = np.asarray(dists)
        gids = np.asarray(gids)
        mask = (dists < np.inf) & (gids < n)
        keep = gids[mask]
        cx = (np.asarray(cxh, np.float64)
              + np.asarray(cxl, np.float64))[mask]
        cy = (np.asarray(cyh, np.float64)
              + np.asarray(cyl, np.float64))[mask]
        d2 = (cx - qx) ** 2 + (cy - qy) ** 2
        order = np.argsort(d2, kind="stable")
        return keep[order][:kk]
    fn = _knn_prune_fn(mesh, fetch)
    dists, gids = fn(px, py, pvalid, q)
    dists = np.asarray(dists)
    gids = np.asarray(gids)
    mask = (dists < np.inf) & (gids < n)
    keep = gids[mask]
    if host_x is not None and host_y is not None:
        d2 = ((host_x[keep] - qx) ** 2 + (host_y[keep] - qy) ** 2)
        order = np.argsort(d2, kind="stable")
    else:
        order = np.argsort(dists[mask], kind="stable")
    return keep[order][:kk]
