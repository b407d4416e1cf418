"""Fused spatio-temporal scan kernel: the TPU analog of the reference's
server-side iterator stack (Z3Iterator + KryoLazyFilterTransformIterator,
accumulo/iterators/Z3Iterator.scala:47-60 + index/filters/Z3Filter.scala).

Instead of per-row z-key decode + int compares on tablet servers, the
whole batch is filtered in one XLA program:

- coordinates live on device as *round-down two-float* pairs
  (hi = float32 rounded toward -inf, lo = float32(x - hi) in [0, ulp)),
  so bbox comparisons against query bounds split the same way are exact
  in float64 terms up to a ~1e-12 deg residual; points sharing a hi cell
  with a query bound are flagged for host float64 recheck, making the
  final mask EXACTLY the double-precision result;
- times live as (days-since-epoch int32, millis-in-day int32) pairs —
  exact epoch millis without 64-bit device ints;
- query boxes and time intervals are padded to fixed shapes (next power
  of two) so jit traces are reused across queries.

No f64, no i64, no data-dependent shapes inside jit.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jaxcache import ensure_compile_cache

ensure_compile_cache()

__all__ = ["BatchedScanQuery", "DeviceScanData", "ScanQuery",
           "batch_hit_rows", "build_scan_data", "extend_scan_data",
           "hit_rows", "make_query", "next_pow2", "patch_hit_rows",
           "scan_codes", "scan_mask", "scan_mask_at", "split_two_float",
           "stack_points", "stack_queries", "MILLIS_PER_DAY"]

MILLIS_PER_DAY = 86_400_000


def split_two_float(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """f64 -> (hi, lo) f32 pair with hi = round-toward-neg-inf(x) and
    lo = f32(x - hi) >= 0. Lexicographic (hi, lo) compare then mirrors
    the f64 order to within f32-rounding of the residual."""
    x = np.asarray(x, dtype=np.float64)
    hi = x.astype(np.float32)
    over = hi.astype(np.float64) > x
    hi = np.where(over, np.nextafter(hi, np.float32(-np.inf)), hi)
    lo = (x - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


@dataclasses.dataclass
class DeviceScanData:
    """Device-resident columns for the spatio-temporal scan.

    Arrays may be longer than ``n`` (capacity padding): the write path
    allocates power-of-two capacity and appends in place with
    dynamic_update_slice, so incremental writes keep STATIC shapes —
    no per-flush XLA recompiles of the scan or the append. Kernels mask
    rows >= n."""
    xhi: jax.Array
    xlo: jax.Array
    yhi: jax.Array
    ylo: jax.Array
    tday: jax.Array
    tms: jax.Array
    n: int

    @property
    def cap(self) -> int:
        return int(self.xhi.shape[0])

    @property
    def nbytes(self) -> int:
        return self.cap * (4 * 4 + 2 * 4)


def _split_time(millis) -> tuple[np.ndarray, np.ndarray]:
    millis = np.asarray(millis, dtype=np.int64)
    tday = (millis // MILLIS_PER_DAY).astype(np.int32)
    tms = (millis - tday.astype(np.int64) * MILLIS_PER_DAY).astype(np.int32)
    return tday, tms


def build_scan_data(x: np.ndarray, y: np.ndarray, millis: np.ndarray,
                    device=None, cap: int | None = None,
                    xy_split=None) -> DeviceScanData:
    """Host f64 coords + epoch millis -> device arrays, zero-padded to
    ``cap`` rows when given (capacity headroom for in-place appends).
    ``xy_split`` passes precomputed (xhi, xlo, yhi, ylo) pairs so a
    caller that also needs host copies splits once (and never fetches
    them back off the device — a 2x column transfer at 100M rows)."""
    if xy_split is not None:
        xhi, xlo, yhi, ylo = xy_split
    else:
        xhi, xlo = split_two_float(x)
        yhi, ylo = split_two_float(y)
    tday, tms = _split_time(millis)
    n = len(xhi)
    if cap is not None and cap > n:
        def padded(a):
            return np.pad(a, (0, cap - n))
        xhi, xlo, yhi, ylo, tday, tms = (
            padded(a) for a in (xhi, xlo, yhi, ylo, tday, tms))
    put = functools.partial(jax.device_put, device=device)
    return DeviceScanData(put(xhi), put(xlo), put(yhi), put(ylo),
                          put(tday), put(tms), n)


@jax.jit
def _update1(a, u, i):
    return jax.lax.dynamic_update_slice(a, u, (i,))


def extend_scan_data(data: DeviceScanData, x, y, millis,
                     xy_split=None) -> DeviceScanData | None:
    """Append rows in place within existing capacity, or None when the
    capacity is exhausted (caller rebuilds with fresh headroom). The
    delta is padded to a power of two so the device program is reused
    across write bursts of any size. ``xy_split`` passes precomputed
    (xhi, xlo, yhi, ylo) two-float pairs to avoid re-splitting."""
    d = len(x)
    if d == 0:
        return data
    k = next_pow2(d)
    if data.n + k > data.cap:
        return None
    if xy_split is None:
        xhi, xlo = split_two_float(np.asarray(x, dtype=np.float64))
        yhi, ylo = split_two_float(np.asarray(y, dtype=np.float64))
    else:
        xhi, xlo, yhi, ylo = xy_split
    tday, tms = _split_time(millis)

    def padded(a):
        return jnp.asarray(np.pad(a, (0, k - d)))
    i = data.n  # python int traces as a dynamic scalar: no retrace
    return DeviceScanData(
        _update1(data.xhi, padded(xhi), i), _update1(data.xlo, padded(xlo), i),
        _update1(data.yhi, padded(yhi), i), _update1(data.ylo, padded(ylo), i),
        _update1(data.tday, padded(tday), i), _update1(data.tms, padded(tms), i),
        data.n + d)


class ScanQuery:
    """Padded query: K spatial boxes + B time intervals.

    boxes: (K, 8) f32 [xmin_hi, xmin_lo, xmax_hi, xmax_lo,
                       ymin_hi, ymin_lo, ymax_hi, ymax_lo]
    box_valid: (K,) bool
    times: (B, 4) i32 [day_lo, ms_lo, day_hi, ms_hi], inclusive bounds
    time_valid: (B,) bool; time_any: no time constraint at all

    The device arrays upload LAZILY on first access: selective queries
    resolved entirely on host (the index fast path) never touch the
    device, so building a ScanQuery must not cost device_put round
    trips. ``host_*`` fields are the exact f64/i64 originals for
    boundary rechecks and host evaluation.
    """

    def __init__(self, boxes: np.ndarray, box_valid: np.ndarray,
                 times: np.ndarray, time_valid: np.ndarray,
                 time_any: bool, n_boxes: int, host_boxes: np.ndarray,
                 host_box_his: np.ndarray, host_intervals: np.ndarray):
        self._np = (np.asarray(boxes), np.asarray(box_valid),
                    np.asarray(times), np.asarray(time_valid))
        self._dev = None
        self.time_any = time_any
        self.n_boxes = n_boxes
        self.host_boxes = host_boxes
        self.host_box_his = host_box_his
        self.host_intervals = host_intervals

    def _device(self):
        if self._dev is None:
            self._dev = tuple(jnp.asarray(a) for a in self._np)
        return self._dev

    @property
    def boxes(self) -> jax.Array:
        return self._device()[0]

    @property
    def box_valid(self) -> jax.Array:
        return self._device()[1]

    @property
    def times(self) -> jax.Array:
        return self._device()[2]

    @property
    def time_valid(self) -> jax.Array:
        return self._device()[3]

    @property
    def boxes_np(self) -> np.ndarray:
        """Padded boxes as host numpy (no device round trip)."""
        return self._np[0]

    @property
    def box_valid_np(self) -> np.ndarray:
        return self._np[1]

    @property
    def times_np(self) -> np.ndarray:
        return self._np[2]

    @property
    def time_valid_np(self) -> np.ndarray:
        return self._np[3]


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def make_query(boxes_f64, intervals_ms) -> ScanQuery:
    """Build a padded ScanQuery.

    boxes_f64: list of (xmin, ymin, xmax, ymax) float64 tuples.
    intervals_ms: list of (lo_millis, hi_millis) INCLUSIVE int bounds,
      or None/[] for no time constraint.
    """
    boxes_f64 = list(boxes_f64)
    k = max(next_pow2(max(len(boxes_f64), 1)), 1)
    boxes = np.zeros((k, 8), dtype=np.float32)
    valid = np.zeros(k, dtype=bool)
    host_boxes = np.zeros((len(boxes_f64), 4), dtype=np.float64)
    host_his = np.zeros((len(boxes_f64), 4), dtype=np.float32)
    for i, (xmin, ymin, xmax, ymax) in enumerate(boxes_f64):
        xmin_hi, xmin_lo = split_two_float(np.float64(xmin))
        xmax_hi, xmax_lo = split_two_float(np.float64(xmax))
        ymin_hi, ymin_lo = split_two_float(np.float64(ymin))
        ymax_hi, ymax_lo = split_two_float(np.float64(ymax))
        boxes[i] = (xmin_hi, xmin_lo, xmax_hi, xmax_lo,
                    ymin_hi, ymin_lo, ymax_hi, ymax_lo)
        host_boxes[i] = (xmin, ymin, xmax, ymax)
        host_his[i] = (xmin_hi, xmax_hi, ymin_hi, ymax_hi)
        valid[i] = True

    intervals_ms = list(intervals_ms or [])
    time_any = not intervals_ms
    b = max(next_pow2(max(len(intervals_ms), 1)), 1)
    times = np.zeros((b, 4), dtype=np.int32)
    tvalid = np.zeros(b, dtype=bool)
    for i, (lo, hi) in enumerate(intervals_ms):
        lo, hi = int(lo), int(hi)
        times[i] = (lo // MILLIS_PER_DAY, lo % MILLIS_PER_DAY,
                    hi // MILLIS_PER_DAY, hi % MILLIS_PER_DAY)
        tvalid[i] = True

    host_iv = np.asarray(intervals_ms, dtype=np.int64).reshape(-1, 2)
    return ScanQuery(boxes, valid, times, tvalid, time_any,
                     len(boxes_f64), host_boxes, host_his, host_iv)


# -- the kernel ------------------------------------------------------------

def _ge_two_float(hi, lo, b_hi, b_lo):
    """(hi, lo) >= (b_hi, b_lo) lexicographically."""
    return (hi > b_hi) | ((hi == b_hi) & (lo >= b_lo))


def _le_two_float(hi, lo, b_hi, b_lo):
    return (hi < b_hi) | ((hi == b_hi) & (lo <= b_lo))


def _mask_body(xhi, xlo, yhi, ylo, tday, tms,
               boxes, box_valid, times, time_valid, time_any: bool,
               n_valid=None, flag_boundary: bool = False):
    """The two-float verdict per row: bool, or with ``flag_boundary`` a
    uint8 code whose bit 0 is the verdict and bit 1 the boundary flag of
    ``_cand_body`` (the same byte a row comes down either way)."""
    # spatial: any valid box contains the point — (n, K) broadcast
    bx = boxes[None, :, :]                      # (1, K, 8)
    sx = (_ge_two_float(xhi[:, None], xlo[:, None], bx[..., 0], bx[..., 1])
          & _le_two_float(xhi[:, None], xlo[:, None], bx[..., 2], bx[..., 3])
          & _ge_two_float(yhi[:, None], ylo[:, None], bx[..., 4], bx[..., 5])
          & _le_two_float(yhi[:, None], ylo[:, None], bx[..., 6], bx[..., 7]))
    hit = jnp.any(sx & box_valid[None, :], axis=1)
    # capacity-padded rows (>= n_valid) are never matches
    if n_valid is not None:
        hit = hit & (jnp.arange(xhi.shape[0]) < n_valid)
    if not time_any:
        tx = times[None, :, :]                  # (1, B, 4)
        after_lo = ((tday[:, None] > tx[..., 0])
                    | ((tday[:, None] == tx[..., 0])
                       & (tms[:, None] >= tx[..., 1])))
        before_hi = ((tday[:, None] < tx[..., 2])
                     | ((tday[:, None] == tx[..., 2])
                        & (tms[:, None] <= tx[..., 3])))
        hit = hit & jnp.any(after_lo & before_hi & time_valid[None, :],
                            axis=1)
    if not flag_boundary:
        return hit
    cand = _cand_body(xhi, yhi, boxes, box_valid, n_valid)
    return hit.astype(jnp.uint8) | (cand.astype(jnp.uint8) << 1)


_scan_mask = functools.partial(
    jax.jit, static_argnames=("time_any", "flag_boundary"))(_mask_body)


@functools.partial(jax.jit, static_argnames=("time_any",))
def _gather_scan_mask(xhi, xlo, yhi, ylo, tday, tms, idx,
                      boxes, box_valid, times, time_valid, time_any: bool):
    """Scan only the gathered candidate rows (index-pruned path)."""
    def g(a):
        return jnp.take(a, idx, mode="clip")
    return _mask_body(g(xhi), g(xlo), g(yhi), g(ylo), g(tday), g(tms),
                      boxes, box_valid, times, time_valid, time_any)


def scan_mask_at(data: DeviceScanData, q: ScanQuery,
                 rows: np.ndarray) -> np.ndarray:
    """Run the fused scan over just ``rows`` (original-order indices from
    the z-key index); returns a host bool[len(rows)] mask.

    The row list is padded to the next power of two so jit traces are
    reused across queries (pad rows gather row 0 and are sliced off).
    """
    m = len(rows)
    if m == 0:
        return np.zeros(0, dtype=bool)
    k = next_pow2(m)
    # pad in the rows' own dtype (row counts are capped at int32 range
    # by ZKeyIndex._perm_dtype; device gathers are 32-bit)
    idx = np.zeros(k, dtype=rows.dtype)
    idx[:m] = rows
    out = _gather_scan_mask(data.xhi, data.xlo, data.yhi, data.ylo,
                            data.tday, data.tms, jnp.asarray(idx),
                            q.boxes, q.box_valid, q.times, q.time_valid,
                            q.time_any)
    return np.asarray(out)[:m]


def scan_mask(data: DeviceScanData, q: ScanQuery) -> jax.Array:
    """Run the fused scan; returns a device bool[cap] mask whose
    capacity-padding tail (rows >= data.n) is always False."""
    n_valid = None if data.cap == data.n else data.n
    return _scan_mask(data.xhi, data.xlo, data.yhi, data.ylo,
                      data.tday, data.tms,
                      q.boxes, q.box_valid, q.times, q.time_valid,
                      q.time_any, n_valid)


def scan_codes(data: DeviceScanData, q: ScanQuery) -> jax.Array:
    """``scan_mask`` with the boundary flags: a device uint8[cap] code a
    row, bit 0 the two-float verdict and bit 1 set where the row's
    hi-cell equals a query bound's (``boundary_candidates`` on the
    device). Capacity-padding rows read 0."""
    n_valid = None if data.cap == data.n else data.n
    return _scan_mask(data.xhi, data.xlo, data.yhi, data.ylo,
                      data.tday, data.tms,
                      q.boxes, q.box_valid, q.times, q.time_valid,
                      q.time_any, n_valid, flag_boundary=True)


_F32_TINY = np.finfo(np.float32).tiny


def _on_cell(hi: np.ndarray, b: np.float32) -> np.ndarray:
    """Rows whose hi-cell equals the bound's hi-cell ``b`` as the device
    compares them: it flushes f32 subnormals to zero, so a bound in the
    zero cell shares it with every subnormal hi."""
    if abs(b) < _F32_TINY:
        return np.abs(hi) < _F32_TINY
    return hi == b


def boundary_candidates(data_xhi: np.ndarray, data_yhi: np.ndarray,
                        q: ScanQuery) -> np.ndarray:
    """Host-side: indices of points whose hi-cell equals any query bound's
    hi-cell — the only points where the two-float compare can differ from
    exact f64. Typically a vanishing fraction of n (~n * 2^-23)."""
    mask = np.zeros(len(data_xhi), dtype=bool)
    for i in range(q.n_boxes):
        his = q.host_box_his[i]
        mask |= _on_cell(data_xhi, his[0]) | _on_cell(data_xhi, his[1])
        mask |= _on_cell(data_yhi, his[2]) | _on_cell(data_yhi, his[3])
    return np.flatnonzero(mask)


_native_nonzero = None  # None = unprobed, False = unavailable


def _nonzero_lib():
    """ctypes handle to the native mask compaction (native/src/mask.cpp),
    or None when the library does not load."""
    global _native_nonzero
    if _native_nonzero is None:
        import ctypes
        from ..native import symbols
        lib = symbols({"geomesa_nonzero_u8": (
            ctypes.c_int64, [ctypes.c_void_p, ctypes.c_int64,
                             ctypes.c_void_p, ctypes.c_int64])})
        _native_nonzero = lib if lib is not None else False
    return _native_nonzero or None


def hit_rows(mask: np.ndarray) -> np.ndarray:
    """``np.flatnonzero`` of a 1-d bool or uint8 row mask: the sorted
    int64 indices of its nonzero bytes, compacted natively without a
    branch on the data where the library loads. numpy branches on each
    byte, which costs 2-5x on a mask whose hits are scattered."""
    lib = _nonzero_lib()
    if lib is None or mask.ndim != 1 or mask.dtype not in (np.bool_,
                                                           np.uint8):
        return np.flatnonzero(mask)
    mask = np.ascontiguousarray(mask)
    cap = int(np.count_nonzero(mask)) + 1
    out = np.empty(cap, dtype=np.int64)
    k = lib.geomesa_nonzero_u8(mask.ctypes.data, len(mask),
                               out.ctypes.data, cap)
    if k < 0:
        raise RuntimeError("native mask compaction overran its output")
    return out[:k]


def _exact_hits(cand_idx: np.ndarray, x: np.ndarray, y: np.ndarray,
                millis: np.ndarray, q: ScanQuery) -> np.ndarray:
    """Exact f64/i64 verdict for each candidate row index."""
    cx, cy = x[cand_idx], y[cand_idx]
    ok = np.zeros(len(cand_idx), dtype=bool)
    for i in range(q.n_boxes):
        xmin, ymin, xmax, ymax = q.host_boxes[i]
        ok |= (cx >= xmin) & (cx <= xmax) & (cy >= ymin) & (cy <= ymax)
    if not q.time_any:
        cm = millis[cand_idx]
        t_ok = np.zeros(len(cand_idx), dtype=bool)
        for lo, hi in q.host_intervals:
            t_ok |= (cm >= lo) & (cm <= hi)
        ok &= t_ok
    return ok


def exact_patch(mask: np.ndarray, cand_idx: np.ndarray,
                x: np.ndarray, y: np.ndarray, millis: np.ndarray,
                q: ScanQuery) -> np.ndarray:
    """Fully re-evaluate boundary candidates in exact f64/i64 semantics
    and patch their mask bits, making the overall result exact."""
    if len(cand_idx) == 0:
        return mask
    ok = _exact_hits(cand_idx, x, y, millis, q)
    mask = mask.copy()
    mask[cand_idx] = ok
    return mask


# -- micro-batched multi-query scan ---------------------------------------
#
# N concurrent queries become ONE device launch: each query's padded
# boxes/intervals are stacked along a leading pow2 batch dim and the
# scalar-query kernel is vmapped over it. Per-query `time_any` is a
# static argument and may differ within a batch, so time-unconstrained
# queries get a CATCH-ALL interval (all representable days) and the
# batched kernel always runs the temporal compare.

_CATCH_ALL_INTERVAL = (-(2 ** 30), 0, 2 ** 30, MILLIS_PER_DAY)


class BatchedScanQuery:
    """Qp stacked queries padded to common box/interval counts.

    boxes: (Qp, K, 8) f32; box_valid: (Qp, K) bool
    times: (Qp, B, 4) i32; time_valid: (Qp, B) bool

    ``queries`` keeps the original ScanQuery objects (exact f64 bounds
    for per-query boundary patches); Qp - n_queries tail rows are pure
    padding with box_valid all False (they match nothing).
    """

    def __init__(self, boxes: np.ndarray, box_valid: np.ndarray,
                 times: np.ndarray, time_valid: np.ndarray,
                 queries: list[ScanQuery]):
        self._np = (np.asarray(boxes), np.asarray(box_valid),
                    np.asarray(times), np.asarray(time_valid))
        self._dev = None
        self.queries = queries

    @property
    def n_queries(self) -> int:
        return len(self.queries)

    def _device(self):
        if self._dev is None:
            self._dev = tuple(jnp.asarray(a) for a in self._np)
        return self._dev

    @property
    def boxes(self) -> jax.Array:
        return self._device()[0]

    @property
    def box_valid(self) -> jax.Array:
        return self._device()[1]

    @property
    def times(self) -> jax.Array:
        return self._device()[2]

    @property
    def time_valid(self) -> jax.Array:
        return self._device()[3]


def stack_queries(queries: list[ScanQuery],
                  min_batch: int = 1) -> BatchedScanQuery:
    """Stack padded ScanQueries into one BatchedScanQuery.

    Box/interval dims are padded to the max across the batch (already
    pow2 per query, so the max is pow2 too); the batch dim is padded to
    a power of two (at least ``min_batch``) so jit traces are reused
    across occupancy levels."""
    if not queries:
        raise ValueError("stack_queries needs at least one query")
    k = max(q.boxes_np.shape[0] for q in queries)
    b = max(q.times_np.shape[0] for q in queries)
    qp = max(next_pow2(len(queries)), min_batch)
    boxes = np.zeros((qp, k, 8), dtype=np.float32)
    box_valid = np.zeros((qp, k), dtype=bool)
    times = np.zeros((qp, b, 4), dtype=np.int32)
    time_valid = np.zeros((qp, b), dtype=bool)
    for i, q in enumerate(queries):
        bk = q.boxes_np.shape[0]
        boxes[i, :bk] = q.boxes_np
        box_valid[i, :bk] = q.box_valid_np
        if q.time_any:
            times[i, 0] = _CATCH_ALL_INTERVAL
            time_valid[i, 0] = True
        else:
            tb = q.times_np.shape[0]
            times[i, :tb] = q.times_np
            time_valid[i, :tb] = q.time_valid_np
    return BatchedScanQuery(boxes, box_valid, times, time_valid,
                            list(queries))


def stack_points(qx, qy, min_batch: int = 1
                 ) -> tuple[np.ndarray, np.ndarray, int]:
    """Stack query POINTS into one pow2-padded f32 batch — the
    point-query analog of ``stack_queries`` (multi-query KNN, batched
    proximity). Returns ``(qx_pad, qy_pad, nq)`` where the batch dim is
    the next power of two >= max(nq, min_batch); padding rows repeat the
    first query so they are valid coordinates (callers slice results
    back to ``nq`` — a repeated query costs nothing extra in a fused
    kernel, while garbage coordinates could produce NaN/inf work)."""
    qx = np.atleast_1d(np.asarray(qx, np.float64))
    qy = np.atleast_1d(np.asarray(qy, np.float64))
    if qx.shape != qy.shape or qx.ndim != 1:
        raise ValueError("stack_points needs matching 1-d coordinates")
    nq = len(qx)
    if nq == 0:
        raise ValueError("stack_points needs at least one query point")
    qp = max(next_pow2(nq), max(min_batch, 1))
    qxp = np.full(qp, qx[0], dtype=np.float32)
    qyp = np.full(qp, qy[0], dtype=np.float32)
    qxp[:nq] = qx.astype(np.float32)
    qyp[:nq] = qy.astype(np.float32)
    return qxp, qyp, nq


def _cand_body(xhi, yhi, boxes, box_valid, n_valid=None):
    """Boundary-candidate mask: rows whose hi-cell equals any valid
    box bound's hi-cell (the only rows where the two-float compare can
    disagree with exact f64). Device analog of boundary_candidates."""
    bx = boxes[None, :, :]
    c = ((xhi[:, None] == bx[..., 0]) | (xhi[:, None] == bx[..., 2])
         | (yhi[:, None] == bx[..., 4]) | (yhi[:, None] == bx[..., 6]))
    cand = jnp.any(c & box_valid[None, :], axis=1)
    if n_valid is not None:
        cand = cand & (jnp.arange(xhi.shape[0]) < n_valid)
    return cand


@jax.jit
def _batch_mask_cand(xhi, xlo, yhi, ylo, tday, tms,
                     boxes, box_valid, times, time_valid, n_valid):
    def one(bx, bv, tx, tv):
        return (_mask_body(xhi, xlo, yhi, ylo, tday, tms,
                           bx, bv, tx, tv, time_any=False, n_valid=n_valid),
                _cand_body(xhi, yhi, bx, bv, n_valid))
    return jax.vmap(one)(boxes, box_valid, times, time_valid)


@jax.jit
def _batch_count(mask):
    return jnp.sum(mask, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("size",))
def _batch_nonzero(mask, size: int):
    # one row at a time (lax.map, not vmap): nonzero's cumsum/scatter
    # temporaries are O(cap) instead of O(Q * cap) — vmapped, 32 queries
    # over 100M rows asked a v5e for 39 GB of HBM
    def one(row):
        return jnp.nonzero(row, size=size, fill_value=row.shape[0])[0]
    return jax.lax.map(one, mask)


def batch_hit_rows(data: DeviceScanData, bq: BatchedScanQuery
                   ) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Fused scan + on-device compaction: per-query (sorted hit rows,
    boundary-candidate rows).

    Transfers O(Qp * max_hits) instead of O(Qp * cap) — counts are
    fetched first (a 2*Qp-int sync), then hits/candidates are compacted
    to the next pow2 of the largest per-query count so the compaction
    kernel's trace is reused across batches in the same hit-size class.
    Boundary candidates are found ON DEVICE inside the same launch, so
    the per-query O(n) host candidate scan of the scalar path is
    amortized away entirely."""
    mask, cand = _batch_mask_cand(
        data.xhi, data.xlo, data.yhi, data.ylo, data.tday, data.tms,
        bq.boxes, bq.box_valid, bq.times, bq.time_valid, jnp.int32(data.n))
    counts = np.asarray(_batch_count(mask))
    ccounts = np.asarray(_batch_count(cand))
    size = next_pow2(max(int(counts.max()), 1))
    csize = next_pow2(max(int(ccounts.max()), 1))
    idx = np.asarray(_batch_nonzero(mask, size))
    cidx = np.asarray(_batch_nonzero(cand, csize))
    hits = [idx[i, :counts[i]] for i in range(bq.n_queries)]
    cands = [cidx[i, :ccounts[i]] for i in range(bq.n_queries)]
    return hits, cands


def patch_hit_rows(rows: np.ndarray, q: ScanQuery,
                   x: np.ndarray, y: np.ndarray, millis: np.ndarray,
                   cand: np.ndarray) -> np.ndarray:
    """Boundary patch in row-index space: re-evaluate the (vanishing)
    set of hi-cell boundary candidates ``cand`` in exact f64/i64 and
    add/remove them from ``rows``, making the hit set exactly the f64
    result."""
    if len(cand) == 0:
        return rows
    ok = _exact_hits(cand, x, y, millis, q)
    add = cand[ok]
    drop = cand[~ok]
    if len(drop):
        rows = np.setdiff1d(rows, drop, assume_unique=False)
    if len(add):
        rows = np.union1d(rows, add)
    return rows
