"""Pallas TPU kernel for the fused spatio-temporal scan.

The XLA path (zscan._scan_mask) is already HBM-bound; this Pallas
version exists for the count-only hot query (`pallas_scan_count`),
which accumulates the hit count across row blocks in a (1,1) output
without ever writing the n-row mask back to HBM — the "server-side
aggregate" shape (BaseAggregatingIterator,
accumulo/iterators/: aggregate on the tablet, ship only the partial)
taken all the way down to the kernel level.

Layout: columns are padded and reshaped to (rows, 128) f32/i32 tiles;
the grid walks row blocks of BLOCK_R x 128 (double-buffered HBM->VMEM
streaming is implicit in the BlockSpec pipeline). Inside a block the
kernel loops over SUB_R-row slices, so its temporaries (one bool tile
per compare, per box) stay a fixed fraction of the 16 MiB scoped VMEM
for any box count: evaluated over the whole block they overflowed it
for K >= 4 on a v5e. Query boxes/times are small VMEM-resident tables;
invalid padding slots carry impossible bounds so the kernel needs no
validity masks.

Numerics are identical to zscan: two-float lexicographic compares for
space, (day, ms) int32 pairs for time — so `pallas_scan_mask` is
bit-identical to the XLA kernel and shares its host boundary patch.

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jaxcache import ensure_compile_cache
from .zscan import MILLIS_PER_DAY, ScanQuery, split_two_float

ensure_compile_cache()

__all__ = ["PallasScanData", "build_pallas_data", "pallas_scan_mask",
           "pallas_scan_count", "pallas_query_tables", "BLOCK_R"]

LANES = 128
BLOCK_R = 1024  # rows per grid step: six 512 KiB input tiles, double-buffered
SUB_R = 256     # rows per in-kernel slice; a multiple of the int8 tile (32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@dataclasses.dataclass
class PallasScanData:
    """(rows, 128)-tiled device columns; pad points carry coords/times
    that no query can match."""
    xhi: jax.Array
    xlo: jax.Array
    yhi: jax.Array
    ylo: jax.Array
    tday: jax.Array
    tms: jax.Array
    n: int
    rows: int


def build_pallas_data(x: np.ndarray, y: np.ndarray,
                      millis: np.ndarray) -> PallasScanData:
    n = len(x)
    rows = -(-n // LANES)
    rows = -(-rows // BLOCK_R) * BLOCK_R
    n_padded = rows * LANES

    def tile(a, fill, dtype):
        out = np.full(n_padded, fill, dtype)
        out[:n] = a
        return jnp.asarray(out.reshape(rows, LANES))

    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    millis = np.asarray(millis, np.int64)
    xhi, xlo = split_two_float(x)
    yhi, ylo = split_two_float(y)
    tday = (millis // MILLIS_PER_DAY).astype(np.int32)
    tms = (millis - tday.astype(np.int64) * MILLIS_PER_DAY).astype(np.int32)
    return PallasScanData(
        tile(xhi, 1e9, np.float32), tile(xlo, 0, np.float32),
        tile(yhi, 1e9, np.float32), tile(ylo, 0, np.float32),
        tile(tday, -1, np.int32), tile(tms, 0, np.int32), n, rows)


def pallas_query_tables(q: ScanQuery) -> tuple[jax.Array, jax.Array]:
    """ScanQuery -> (boxes (K,8) f32, times (B,4) i32) with invalid
    slots folded into impossible bounds (no validity masks needed)."""
    boxes = np.array(q.boxes_np, np.float32, copy=True)
    valid = q.box_valid_np
    boxes[~valid, 0] = np.inf    # xmin_hi = +inf -> never >= it
    boxes[~valid, 2] = -np.inf
    times = np.array(q.times_np, np.int32, copy=True)
    tvalid = q.time_valid_np
    times[~tvalid, 0] = np.iinfo(np.int32).max  # day_lo -> never after
    times[~tvalid, 2] = np.iinfo(np.int32).min
    return jnp.asarray(boxes), jnp.asarray(times)


def _ge2(hi, lo, bhi, blo):
    return (hi > bhi) | ((hi == bhi) & (lo >= blo))


def _le2(hi, lo, bhi, blo):
    return (hi < bhi) | ((hi == bhi) & (lo <= blo))


def _block_mask(xhi, xlo, yhi, ylo, tday, tms, boxes_ref, times_ref,
                k: int, b: int, time_any: bool):
    m = jnp.zeros(xhi.shape, jnp.bool_)
    for i in range(k):  # static unroll: K is the padded pow2 box count
        m |= (_ge2(xhi, xlo, boxes_ref[i, 0], boxes_ref[i, 1])
              & _le2(xhi, xlo, boxes_ref[i, 2], boxes_ref[i, 3])
              & _ge2(yhi, ylo, boxes_ref[i, 4], boxes_ref[i, 5])
              & _le2(yhi, ylo, boxes_ref[i, 6], boxes_ref[i, 7]))
    if not time_any:
        t = jnp.zeros(xhi.shape, jnp.bool_)
        for j in range(b):
            after = ((tday > times_ref[j, 0])
                     | ((tday == times_ref[j, 0]) & (tms >= times_ref[j, 1])))
            before = ((tday < times_ref[j, 2])
                      | ((tday == times_ref[j, 2]) & (tms <= times_ref[j, 3])))
            t |= after & before
        m &= t
    return m


def _col_specs():
    col = pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    small = pl.BlockSpec(memory_space=pltpu.VMEM)
    return [small, small] + [col] * 6, col


def _slice_mask(refs, r0, boxes_ref, times_ref, k, b, time_any):
    """Mask of rows [r0, r0 + SUB_R) of the current block."""
    sl = pl.ds(pl.multiple_of(r0, SUB_R), SUB_R)
    return _block_mask(*(r[sl, :] for r in refs), boxes_ref, times_ref,
                       k, b, time_any)


@functools.partial(jax.jit, static_argnames=("k", "b", "time_any", "rows"))
def _mask_call(xhi, xlo, yhi, ylo, tday, tms, boxes, times,
               k: int, b: int, time_any: bool, rows: int):
    def kernel(boxes_ref, times_ref, *refs):
        *cols, out_ref = refs

        def body(s, carry):
            r0 = s * SUB_R
            m = _slice_mask(cols, r0, boxes_ref, times_ref, k, b, time_any)
            out_ref[pl.ds(pl.multiple_of(r0, SUB_R), SUB_R), :] = \
                m.astype(jnp.int8)
            return carry

        jax.lax.fori_loop(0, BLOCK_R // SUB_R, body, 0)

    in_specs, col = _col_specs()
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        grid=(rows // BLOCK_R,),
        in_specs=in_specs,
        out_specs=col,
        interpret=_interpret(),
    )(boxes, times, xhi, xlo, yhi, ylo, tday, tms)


@functools.partial(jax.jit, static_argnames=("k", "b", "time_any", "rows"))
def _count_call(xhi, xlo, yhi, ylo, tday, tms, boxes, times,
                k: int, b: int, time_any: bool, rows: int):
    def kernel(boxes_ref, times_ref, *refs):
        *cols, out_ref = refs

        def body(s, acc):
            m = _slice_mask(cols, s * SUB_R, boxes_ref, times_ref, k, b,
                            time_any)
            return acc + jnp.sum(m, dtype=jnp.int32)

        partial = jax.lax.fori_loop(0, BLOCK_R // SUB_R, body,
                                    jnp.int32(0))

        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[0, 0] = 0

        out_ref[0, 0] += partial

    in_specs, _ = _col_specs()
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=(rows // BLOCK_R,),
        in_specs=in_specs,
        # every grid step maps to the same output block -> sequential
        # accumulation across steps; SMEM because the store is scalar
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        interpret=_interpret(),
    )(boxes, times, xhi, xlo, yhi, ylo, tday, tms)


def pallas_scan_mask(data: PallasScanData, q: ScanQuery) -> np.ndarray:
    """bool[n] mask, bit-identical to zscan.scan_mask (apply the same
    host boundary patch for exact f64 results)."""
    boxes, times = pallas_query_tables(q)
    out = _mask_call(data.xhi, data.xlo, data.yhi, data.ylo,
                     data.tday, data.tms, boxes, times,
                     int(boxes.shape[0]), int(times.shape[0]),
                     q.time_any, data.rows)
    return np.asarray(out).reshape(-1)[: data.n].astype(bool)


def pallas_scan_count(data: PallasScanData, q: ScanQuery) -> int:
    """Fused scan + count: the mask never touches HBM; one int32 comes
    back. Pad rows can't match (out-of-domain coords), so no
    correction is needed beyond the standard host boundary adjustment
    callers apply for exact f64 counts."""
    boxes, times = pallas_query_tables(q)
    out = _count_call(data.xhi, data.xlo, data.yhi, data.ylo,
                      data.tday, data.tms, boxes, times,
                      int(boxes.shape[0]), int(times.shape[0]),
                      q.time_any, data.rows)
    return int(np.asarray(out)[0, 0])
