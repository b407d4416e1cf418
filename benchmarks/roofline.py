"""Bytes each scan kernel must move, from shapes, and its roofline share.

The z3 scan and residual kernels compare 32-bit lanes and do a few integer
or float compares per byte read, far below the v5e's ops per byte, so HBM
bandwidth bounds all of them: the least time is bytes / peak bytes per
second. Counts are what the algorithm has to touch, not what a padded or
unfused implementation happens to read, so padding and extra passes show
as a lower share.

Kernel names are the jit names as the device trace prints them (PERF.md).
"""

from __future__ import annotations

import json
import os

# xhi, xlo, yhi, ylo (f32), tday, tms (i32): the device scan columns
ZSCAN_COL_BYTES = 6 * 4
MASK_BYTES = 1
INDEX_BYTES = 4                 # a gathered row index (int32)

DENSE_KERNEL = "jit__mask_body"
GATHERED_KERNEL = "jit__gather_scan_mask"
ZSCAN_KERNELS = (DENSE_KERNEL, GATHERED_KERNEL)

# bytes per row of an attribute column as the device residual holds it:
# a 64-bit number is a (hi, lo) pair of 32-bit words plus a validity byte;
# a string is an int32 dictionary code
RESIDUAL_COL_BYTES = {"Double": 9, "Float": 9, "Integer": 9, "Long": 9,
                      "Date": 9, "String": 4}


def zscan_dense_bytes(rows: int) -> int:
    """The dense kernel reads every row's six columns and writes its mask."""
    return rows * (ZSCAN_COL_BYTES + MASK_BYTES)


def zscan_gathered_bytes(candidates: int) -> int:
    """The gathered kernel reads each candidate's index and six columns and
    writes one mask byte per candidate."""
    return candidates * (INDEX_BYTES + ZSCAN_COL_BYTES + MASK_BYTES)


def residual_bytes(rows: int, attr_types: list[str]) -> int:
    """The dense device residual reads each predicate's column over every
    row and writes one mask."""
    return rows * (sum(RESIDUAL_COL_BYTES[t] for t in attr_types)
                   + MASK_BYTES)


def peaks(kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def share(nbytes: float, seconds: float, peak: dict) -> float | None:
    """Percent of the bandwidth roofline: least time over measured time."""
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / seconds
