"""Columns of a configuration, generated from ``--seed`` with vectorised numpy.

A configuration file (``configs/<name>.json``) names a schema and, for each
column, a law and its parameters. A law is a module ``laws/<law>.py``, found
by name, with ``make(rng, spec, n, ctx)``: ``ctx.cols`` holds the columns
made before it and ``ctx.specs`` every column's spec. A law returns a numeric
array, ``(codes, sorted vocab)`` for a string column, or ``(x, y, groups)``
for the point column, ``groups`` naming contiguous row ranges (``lane``,
``noise``, ``place``) that a traffic generator can centre queries on.
A later configuration with a new law adds a file under ``laws/``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MS_DAY = 86_400_000


@dataclasses.dataclass
class Table:
    """Generated columns. ``cols`` maps attribute -> array (a point is an
    (x, y) pair, a string column is (codes, sorted vocab)); every array is
    read-only, so a program that wrote into its input would fail instead of
    changing what the reference reads."""
    n: int
    ids: np.ndarray              # object array of str, row i -> ids[i]
    cols: dict
    types: dict                  # attribute -> schema type name
    groups: dict                 # group name -> (start, stop) rows
    geom: str
    dtg: str | None


@functools.lru_cache(maxsize=None)
def law(name: str):
    """The module of a law, ``laws/<name>.py``."""
    path = os.path.join(HERE, "laws", name + ".py")
    spec = importlib.util.spec_from_file_location(f"law_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def make(rng, spec: dict, n: int, ctx):
    return law(spec["law"]).make(rng, spec, n, ctx)


def day_ms(day: str) -> int:
    return int(np.datetime64(day, "ms").astype(np.int64))


def schema(spec: str) -> dict:
    """attribute -> type name, from a GeoMesa spec string; the default
    geometry is the attribute marked ``*``."""
    out = {}
    for part in spec.split(","):
        name, typ = part.split(":")[:2]
        out[name] = typ
    return out


def generate(config: dict, seed: int, rows: int | None = None) -> Table:
    """The configuration's table for ``seed``; ``rows`` cuts the row count
    (the CPU rehearsal's cut; runs on the chip use the file's count)."""
    n = int(rows if rows is not None else config["rows"])
    rng = np.random.default_rng([seed, 1])
    types_ = {k.lstrip("*"): v for k, v in schema(config["spec"]).items()}
    geom = next(k.lstrip("*") for k in schema(config["spec"])
                if k.startswith("*"))
    dtg = next((k for k, v in types_.items() if v == "Date"), None)
    ctx = types.SimpleNamespace(cols={}, specs=config["columns"])
    groups: dict = {}
    for name, spec in config["columns"].items():
        v = make(rng, spec, n, ctx)
        if name == geom:
            x, y, groups = v
            v = (x, y)
        ctx.cols[name] = v
    base = config["id_base"]
    ids = np.fromiter(map(str, range(base, base + n)), dtype=object, count=n)
    ids.flags.writeable = False
    for v in ctx.cols.values():
        for a in (v if isinstance(v, tuple) else (v,)):
            a.flags.writeable = False
    return Table(n, ids, ctx.cols, types_, groups, geom, dtg)


def to_store(table: Table) -> dict:
    """The ``write_dict`` payload: strings as an Arrow dictionary array
    (codes plus vocab, no per-row Python strings)."""
    import pyarrow as pa
    out = {}
    for name, typ in table.types.items():
        v = table.cols[name]
        if typ == "String":
            codes, vocab = v
            out[name] = pa.DictionaryArray.from_arrays(
                pa.array(codes), pa.array(vocab.astype(str)))
        else:
            out[name] = v
    return out


def encode(values: np.ndarray, codes: np.ndarray) -> tuple:
    """(codes into the sorted vocab, sorted vocab) of a string column whose
    ``codes`` index ``values``."""
    vocab = np.asarray(values, dtype=object)
    order = np.argsort(vocab)
    inv = np.empty(len(order), dtype=np.int32)
    inv[order] = np.arange(len(order), dtype=np.int32)
    return inv[codes], vocab[order]


LUT_BITS = 20


def weighted(rng, weights, n: int) -> np.ndarray:
    """``n`` draws of indices with the given (unnormalised) weights, by a
    table of 2**20 equal-probability buckets (a value rarer than one in a
    million may never be drawn)."""
    cdf = np.cumsum(np.asarray(weights, dtype=np.float64))
    lut = np.searchsorted(cdf / cdf[-1], (np.arange(1 << LUT_BITS) + 0.5)
                          / (1 << LUT_BITS), side="right")
    lut = np.minimum(lut, len(cdf) - 1).astype(np.int32)
    return lut[rng.integers(0, 1 << LUT_BITS, n, dtype=np.int32)]
