"""Bytes the mesh store's dense-tier kernels must move, from shapes, and
their roofline share (``roofline.share`` over the ``peaks.json`` entry).

A ``mesh-dense`` request runs two kernels over the row-sharded columns:
the shard-mapped z3 pass, which reads every row's six scan columns and
writes its mask byte (the single chip's dense pass, ``roofline.py``), and
the compaction of that mask into the hit rows, which reads the mask and
writes ``cap`` int32 row indices, ``cap`` being the hit count padded to a
power of two. Each request's ``rows`` and ``cap`` are the attrs of its
``mesh-scan`` span. A kernel's seconds are its device time summed over
the chips' planes, so the share is of the four chips' bandwidth together.

Kernel names are the jit names as the device trace prints them (PERF.md).
"""

from __future__ import annotations

import roofline

SCAN_KERNEL = "jit__mesh_scan_mask"
COMPACT_KERNEL = "jit__mask_hit_rows"


def scan_bytes(rows: int) -> int:
    """The shard-mapped z3 pass reads six 4-byte columns a row and writes
    one mask byte: 25 bytes a row."""
    return roofline.zscan_dense_bytes(rows)


def compact_bytes(rows: int, cap: int) -> int:
    """The compaction reads one mask byte a row and writes ``cap`` int32
    row indices."""
    return rows * roofline.MASK_BYTES + cap * roofline.INDEX_BYTES


def scans(run) -> list[dict]:
    """The attrs of every ``mesh-scan`` span of the window."""
    return [s.get("attrs", {}) for t in run.spans for s in t
            if s["kind"] == "mesh-scan"]


def share(run, kernel: str, nbytes: int) -> float | None:
    """Percent of the HBM roofline of ``kernel`` moving ``nbytes`` in the
    window; None where the trace holds no time of it."""
    if run.trace is None:
        return None
    secs = sum(run.trace.kernel_s(names=(kernel,)).values())
    return roofline.share(nbytes, secs, run.peak)
