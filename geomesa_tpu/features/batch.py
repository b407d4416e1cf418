"""Columnar feature model: struct-of-arrays batches.

Replaces the reference's row-oriented SimpleFeature + Kryo lazy
serialization (geomesa-features/.../kryo/KryoBufferSimpleFeature.scala):
on TPU the natural layout is struct-of-arrays — one numpy/jax array per
attribute with validity masks, dictionary-encoded strings, epoch-millis
dates and split-out point coordinates. The "lazy attribute access" trick
(read only the attributes a filter needs) becomes simply: kernels touch
only the columns they reference.

Host-side numpy here; the in-memory store builds device views (normalized
int32 grids, two-float coords) at index-build time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable

import numpy as np

try:  # ingest hands string columns over as Arrow arrays when it can
    import pyarrow as pa
except Exception:  # pragma: no cover — arrow-less fallback stays live
    pa = None

from ..geometry import Geometry, Point, parse_wkt
from .sft import SimpleFeatureType

__all__ = ["FeatureBatch", "Column", "NumericColumn", "BoolColumn",
           "DateColumn", "StringColumn", "PointColumn", "GeometryColumn"]


class Column:
    """Base column; length n with a validity mask."""

    name: str
    n: int
    valid: np.ndarray  # bool[n]

    def take(self, idx: np.ndarray) -> "Column":
        raise NotImplementedError

    def value(self, i: int):
        raise NotImplementedError


@dataclasses.dataclass
class NumericColumn(Column):
    name: str
    values: np.ndarray          # f64 or i64
    valid: np.ndarray

    @property
    def n(self) -> int:
        return len(self.values)

    def take(self, idx) -> "NumericColumn":
        return NumericColumn(self.name, self.values[idx], self.valid[idx])

    def value(self, i: int):
        if not self.valid[i]:
            return None
        v = self.values[i]
        return float(v) if self.values.dtype.kind == "f" else int(v)


@dataclasses.dataclass
class BoolColumn(Column):
    name: str
    values: np.ndarray          # bool
    valid: np.ndarray

    @property
    def n(self) -> int:
        return len(self.values)

    def take(self, idx) -> "BoolColumn":
        return BoolColumn(self.name, self.values[idx], self.valid[idx])

    def value(self, i: int):
        return bool(self.values[i]) if self.valid[i] else None


@dataclasses.dataclass
class DateColumn(Column):
    """Dates as epoch millis int64 (reference stores java Dates)."""
    name: str
    millis: np.ndarray
    valid: np.ndarray

    @property
    def n(self) -> int:
        return len(self.millis)

    def take(self, idx) -> "DateColumn":
        return DateColumn(self.name, self.millis[idx], self.valid[idx])

    def value(self, i: int):
        return int(self.millis[i]) if self.valid[i] else None


@dataclasses.dataclass
class StringColumn(Column):
    """Dictionary-encoded strings: codes int32 into vocab; -1 = null.

    The dictionary is the device-side representation too — string
    predicates compile to integer compares against looked-up codes
    (the ArrowFilterOptimizer trick, arrow/filter/ArrowFilterOptimizer.scala:36).
    """
    name: str
    codes: np.ndarray           # int32, -1 for null
    vocab: np.ndarray           # object array of unique strings, sorted

    @property
    def n(self) -> int:
        return len(self.codes)

    @property
    def valid(self) -> np.ndarray:  # type: ignore[override]
        return self.codes >= 0

    def take(self, idx) -> "StringColumn":
        return StringColumn(self.name, self.codes[idx], self.vocab)

    def value(self, i: int):
        c = self.codes[i]
        return None if c < 0 else str(self.vocab[c])

    def code_of(self, s: str) -> int:
        """Vocab code for s, or -1 if absent (planner-side lookup)."""
        i = np.searchsorted(self.vocab, s)
        if i < len(self.vocab) and self.vocab[i] == s:
            return int(i)
        return -1

    @classmethod
    def from_strings(cls, name: str, values: Iterable) -> "StringColumn":
        arr = np.asarray(list(values), dtype=object)
        mask = np.array([v is not None for v in arr], dtype=bool)
        filled = np.where(mask, arr, "")
        vocab, codes = np.unique(filled.astype(str), return_inverse=True)
        codes = codes.astype(np.int32)
        codes[~mask] = -1
        return cls(name, codes, vocab.astype(object))

    @classmethod
    def from_arrow(cls, name: str, arr) -> "StringColumn":
        """Dictionary-encode in C, then remap codes to the sorted-vocab
        order ``code_of``'s searchsorted contract requires. Sorting the
        (small) vocab beats argsorting every row."""
        if arr.null_count:
            return cls.from_strings(
                name, np.asarray(arr.to_numpy(zero_copy_only=False),
                                 dtype=object))
        d = arr.dictionary_encode()
        codes = np.asarray(d.indices.to_numpy(zero_copy_only=False),
                           dtype=np.int32)
        vocab = np.asarray(d.dictionary.to_numpy(zero_copy_only=False),
                           dtype=object)
        order = np.argsort(vocab)
        inv = np.empty(len(order), dtype=np.int32)
        inv[order] = np.arange(len(order), dtype=np.int32)
        return cls(name, inv[codes], vocab[order])


@dataclasses.dataclass
class PointColumn(Column):
    """Point geometry: x/y float64 pairs (the hot layout)."""
    name: str
    x: np.ndarray
    y: np.ndarray
    valid: np.ndarray

    @property
    def n(self) -> int:
        return len(self.x)

    def take(self, idx) -> "PointColumn":
        return PointColumn(self.name, self.x[idx], self.y[idx], self.valid[idx])

    def value(self, i: int):
        return Point(self.x[i], self.y[i]) if self.valid[i] else None


@dataclasses.dataclass
class GeometryColumn(Column):
    """Arbitrary geometries, host-side objects + cached bboxes.

    Packed device buffers (vertex arrays + offsets) are built lazily by
    the scan layer for the geometries a kernel actually needs.
    """
    name: str
    geoms: list  # list[Geometry | None]
    bounds: np.ndarray  # (n, 4) xmin ymin xmax ymax; nan for null

    @property
    def n(self) -> int:
        return len(self.geoms)

    @property
    def valid(self) -> np.ndarray:  # type: ignore[override]
        return ~np.isnan(self.bounds[:, 0])

    def take(self, idx) -> "GeometryColumn":
        idx = np.asarray(idx)
        if idx.dtype == bool:
            idx = np.flatnonzero(idx)
        return GeometryColumn(self.name, [self.geoms[i] for i in idx],
                              self.bounds[idx])

    def value(self, i: int):
        return self.geoms[i]

    @classmethod
    def from_geoms(cls, name: str, geoms: Iterable) -> "GeometryColumn":
        gl = [g if g is None or isinstance(g, Geometry) else parse_wkt(str(g))
              for g in geoms]
        bounds = np.full((len(gl), 4), np.nan)
        for i, g in enumerate(gl):
            if g is not None and not g.is_empty:
                e = g.envelope
                bounds[i] = (e.xmin, e.ymin, e.xmax, e.ymax)
        return cls(name, gl, bounds)


def _column_for(spec_type: str, name: str, data) -> Column:
    n = len(data)
    if spec_type == "Point":
        if isinstance(data, tuple):
            x, y = data
            x = np.asarray(x, dtype=np.float64)
            y = np.asarray(y, dtype=np.float64)
            valid = ~(np.isnan(x) | np.isnan(y))
            return PointColumn(name, x, y, valid)
        xs = np.full(n, np.nan)
        ys = np.full(n, np.nan)
        for i, g in enumerate(data):
            if g is None:
                continue
            if isinstance(g, Point):
                xs[i], ys[i] = g.x, g.y
            else:
                p = parse_wkt(str(g))
                xs[i], ys[i] = p.x, p.y  # type: ignore[union-attr]
        return PointColumn(name, xs, ys, ~np.isnan(xs))
    if spec_type in ("LineString", "Polygon", "MultiPoint", "MultiLineString",
                     "MultiPolygon", "GeometryCollection", "Geometry"):
        return GeometryColumn.from_geoms(name, data)
    if spec_type == "String" or spec_type == "UUID":
        if pa is not None and isinstance(data, (pa.Array, pa.ChunkedArray)):
            if isinstance(data, pa.ChunkedArray):
                data = data.combine_chunks()
            return StringColumn.from_arrow(name, data)
        return StringColumn.from_strings(name, data)
    if spec_type == "Date":
        arr = np.asarray(data)
        if arr.dtype.kind == "M":
            millis = arr.astype("datetime64[ms]").astype(np.int64)
            valid = ~np.isnat(arr)
        elif arr.dtype == object:
            valid = np.array([v is not None for v in arr], dtype=bool)
            millis = np.array(
                [int(np.datetime64(v, "ms").astype(np.int64)) if v is not None
                 else 0 for v in arr], dtype=np.int64)
        else:
            millis = arr.astype(np.int64)
            valid = np.ones(n, dtype=bool)
        return DateColumn(name, millis, valid)
    if spec_type == "Boolean":
        arr = np.asarray(data)
        if arr.dtype == object:
            valid = np.array([v is not None for v in arr], dtype=bool)
            vals = np.array([bool(v) for v in np.where(valid, arr, False)], dtype=bool)
        else:
            vals = arr.astype(bool)
            valid = np.ones(n, dtype=bool)
        return BoolColumn(name, vals, valid)
    # numeric
    dtype = np.float64 if spec_type in ("Double", "Float") else np.int64
    arr = np.asarray(data)
    if arr.dtype == object:
        valid = np.array([v is not None for v in arr], dtype=bool)
        vals = np.array([v if v is not None else 0 for v in arr], dtype=dtype)
    else:
        vals = arr.astype(dtype)
        valid = (~np.isnan(arr) if arr.dtype.kind == "f"
                 else np.ones(n, dtype=bool))
    return NumericColumn(name, vals, valid)


class FeatureBatch:
    """A batch of features: ids + one column per schema attribute."""

    def __init__(self, sft: SimpleFeatureType, ids: np.ndarray | None,
                 columns: dict[str, Column], n: int | None = None):
        """``ids`` None (with the length ``n``) makes a projection that
        holds no ids: see ``take``."""
        self.sft = sft
        self.ids = None if ids is None else np.asarray(ids, dtype=object)
        self.columns = columns
        ns = {c.n for c in columns.values()}
        ns.add(n if self.ids is None else len(self.ids))
        if len(ns) > 1 or None in ns:
            raise ValueError(f"column length mismatch: {ns}")
        self._n = ns.pop()

    @property
    def n(self) -> int:
        return self._n

    def __len__(self) -> int:
        return self.n

    def col(self, name: str) -> Column:
        return self.columns[name]

    @classmethod
    def from_dict(cls, sft: SimpleFeatureType, ids,
                  data: dict[str, Any]) -> "FeatureBatch":
        """Build from {attribute: array-like}; Point columns accept a
        (x_array, y_array) tuple or an iterable of Point/WKT."""
        columns = {}
        for a in sft.attributes:
            if a.name not in data:
                raise KeyError(f"missing column: {a.name}")
            columns[a.name] = _column_for(a.type.name, a.name, data[a.name])
        return cls(sft, np.asarray(ids, dtype=object), columns)

    def take(self, idx, columns=None, ids: bool = True) -> "FeatureBatch":
        """The rows ``idx``. ``columns`` (names) projects the gather onto
        those columns, in schema order; a name the batch lacks is left
        out, so reading it raises in ``col`` as it would on a full take.
        ``ids=False`` gathers no ids (``ids`` is None, ``n`` still the
        row count)."""
        idx = np.asarray(idx)
        cols = (self.columns.items() if columns is None else
                [(k, c) for k, c in self.columns.items() if k in columns])
        n = int(np.count_nonzero(idx)) if idx.dtype == bool else len(idx)
        return FeatureBatch(self.sft, self.ids[idx] if ids else None,
                            {k: c.take(idx) for k, c in cols}, n=n)

    def feature(self, i: int) -> dict[str, Any]:
        """Row view (for result iteration / debugging)."""
        out = {"id": self.ids[i]}
        for name, c in self.columns.items():
            out[name] = c.value(i)
        return out

    def concat(self, other: "FeatureBatch") -> "FeatureBatch":
        return FeatureBatch.concat_all([self, other])

    @classmethod
    def concat_all(cls, batches: list["FeatureBatch"]) -> "FeatureBatch":
        """Single-pass multi-way concatenation: each column is copied
        once and string vocabs merge with one np.unique, so folding a
        burst of k small writes is O(total), not O(k * total)."""
        if not batches:
            raise ValueError("nothing to concatenate")
        if len(batches) == 1:
            return batches[0]
        first = batches[0]
        for b in batches[1:]:
            if b.sft != first.sft:
                raise ValueError("schema mismatch")
        cols: dict[str, Column] = {}
        for name, c in first.columns.items():
            parts = [b.columns[name] for b in batches]
            if isinstance(c, StringColumn):
                # one vocab merge: re-unique all vocabs, remap each code
                # array through its inverse segment, keep -1 nulls
                sizes = [len(p.vocab) for p in parts]
                vocab, inverse = np.unique(
                    np.concatenate([p.vocab for p in parts]).astype(str),
                    return_inverse=True)
                offs = np.cumsum([0] + sizes)
                codes = [
                    np.where(p.codes >= 0,
                             inverse[offs[i]:offs[i + 1]][
                                 np.maximum(p.codes, 0)], -1)
                    for i, p in enumerate(parts)]
                cols[name] = StringColumn(
                    name, np.concatenate(codes).astype(np.int32),
                    vocab.astype(object))
            elif isinstance(c, GeometryColumn):
                geoms: list = []
                for p in parts:
                    geoms.extend(p.geoms)  # type: ignore[union-attr]
                cols[name] = GeometryColumn(
                    name, geoms, np.vstack([p.bounds for p in parts]))
            elif isinstance(c, PointColumn):
                cols[name] = PointColumn(
                    name, np.concatenate([p.x for p in parts]),
                    np.concatenate([p.y for p in parts]),
                    np.concatenate([p.valid for p in parts]))
            elif isinstance(c, DateColumn):
                cols[name] = DateColumn(
                    name, np.concatenate([p.millis for p in parts]),
                    np.concatenate([p.valid for p in parts]))
            else:
                cols[name] = type(c)(
                    name,
                    np.concatenate([p.values for p in parts]),  # type: ignore[attr-defined]
                    np.concatenate([p.valid for p in parts]))
        return FeatureBatch(first.sft,
                            np.concatenate([b.ids for b in batches]), cols)

    # -- arrow interchange ------------------------------------------------

    def to_arrow(self):
        """Convert to a pyarrow RecordBatch (the host interchange format,
        mirroring geomesa-arrow's SimpleFeatureVector encoding)."""
        import pyarrow as pa
        from ..geometry.wkt import to_wkt
        arrays = [pa.array(self.ids.astype(str))]
        names = ["__fid__"]
        for a in self.sft.attributes:
            c = self.columns[a.name]
            names.append(a.name)
            if isinstance(c, PointColumn):
                arrays.append(pa.StructArray.from_arrays(
                    [pa.array(c.x), pa.array(c.y)], ["x", "y"]))
            elif isinstance(c, GeometryColumn):
                vals = [to_wkt(g) if g is not None else None for g in c.geoms]
                arrays.append(pa.array(vals, type=pa.string()))
            elif isinstance(c, StringColumn):
                null = c.codes < 0
                arrays.append(pa.DictionaryArray.from_arrays(
                    np.where(null, 0, c.codes).astype(np.int32),
                    pa.array(c.vocab.astype(str)), mask=null))
            elif isinstance(c, DateColumn):
                arrays.append(pa.array(
                    np.where(c.valid, c.millis, 0), type=pa.timestamp("ms"),
                    mask=~c.valid))
            else:
                arrays.append(pa.array(c.values, mask=~c.valid))
        return pa.RecordBatch.from_arrays(arrays, names)

    @classmethod
    def from_arrow(cls, sft: SimpleFeatureType, rb) -> "FeatureBatch":
        ids = np.asarray(rb.column("__fid__").to_pylist(), dtype=object)
        data: dict[str, Any] = {}
        for a in sft.attributes:
            col = rb.column(a.name)
            if a.type.name == "Point":
                flat = col.flatten()
                data[a.name] = (np.asarray(flat[0]), np.asarray(flat[1]))
            elif a.type.name == "Date":
                arr = col.to_pandas()
                data[a.name] = arr.values
            else:
                data[a.name] = col.to_pylist()
        return cls.from_dict(sft, ids, data)
