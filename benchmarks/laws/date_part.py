"""A number derived from the date column ``of``: ``yyyymm``, ``yyyy``,
``yyyymmdd`` (shifted by 0 to ``lag_days`` days, as GDELT's DATEADDED
trails SQLDATE) or ``fraction`` (year + day of year / 365, 4 decimals)."""

import numpy as np


def make(rng, spec, n, ctx):
    days = ctx.cols[spec["of"]] // 86_400_000
    if spec.get("lag_days"):
        days = days + rng.integers(0, spec["lag_days"] + 1, n)
    first = int(days.min())
    # each part for the few distinct days, then looked up per row
    lut = _part(np.arange(first, int(days.max()) + 1), spec["part"])
    return lut[days - first]


def _part(days, part):
    day = days.astype("datetime64[D]")
    y = day.astype("datetime64[Y]")
    year = y.astype(np.int64) + 1970
    if part == "yyyy":
        return year
    month = day.astype("datetime64[M]").astype(np.int64) % 12 + 1
    if part == "yyyymm":
        return year * 100 + month
    if part == "yyyymmdd":
        dom = (day - day.astype("datetime64[M]")).astype(np.int64) + 1
        return year * 10000 + month * 100 + dom
    if part == "fraction":
        doy = (day - y).astype(np.int64)
        return np.round(year + doy / 365.0, 4)
    raise ValueError(f"unknown date part {part!r}")
