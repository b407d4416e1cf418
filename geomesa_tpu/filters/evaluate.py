"""Vectorized filter evaluation: Filter AST x FeatureBatch -> bool mask.

This is the exact float64 reference evaluator — the correctness oracle
for TPU kernels (differential testing) and the engine for residual
rechecks and small in-memory scans.  Equivalent in role to the
reference's FastFilterFactory-compiled evaluators running inside
KryoLazyFilterTransformIterator (accumulo/iterators/...:37), but
columnar: each node evaluates against whole columns at once.

String predicates exploit dictionary encoding: the predicate runs over
the (small) vocab, then maps through the code array — the
ArrowFilterOptimizer trick (arrow/filter/ArrowFilterOptimizer.scala:36).
"""

from __future__ import annotations

import fnmatch
import re

import numpy as np

from ..features.batch import (BoolColumn, DateColumn, FeatureBatch,
                              GeometryColumn, NumericColumn, PointColumn,
                              StringColumn)
from ..geometry import Envelope, Point
from . import ast
from .helper import dwithin_degrees, like_vocab_mask, to_millis

__all__ = ["evaluate", "take_for"]


def evaluate(f: ast.Filter, batch: FeatureBatch) -> np.ndarray:
    """Evaluate filter over a batch; returns bool[n]."""
    return _eval(f, batch)


def take_for(f: ast.Filter, batch: FeatureBatch, idx) -> FeatureBatch:
    """The rows ``idx`` of ``batch`` holding only what ``f`` reads: the
    columns it names, and the ids only under a ``FidFilter``. So
    ``evaluate(f, take_for(f, batch, idx))`` equals
    ``evaluate(f, batch.take(idx))`` at a fraction of the gather."""
    fids = any(isinstance(node, ast.FidFilter) for node in ast.walk(f))
    return batch.take(idx, columns=ast.props_of(f), ids=fids)


def _eval(f: ast.Filter, b: FeatureBatch) -> np.ndarray:
    n = b.n
    if isinstance(f, ast.Include):
        return np.ones(n, dtype=bool)
    if isinstance(f, ast.Exclude):
        return np.zeros(n, dtype=bool)
    if isinstance(f, ast.And):
        out = np.ones(n, dtype=bool)
        for c in f.children:
            out &= _eval(c, b)
        return out
    if isinstance(f, ast.Or):
        out = np.zeros(n, dtype=bool)
        for c in f.children:
            out |= _eval(c, b)
        return out
    if isinstance(f, ast.Not):
        return ~_eval(f.child, b)
    if isinstance(f, ast.FidFilter):
        return np.isin(b.ids.astype(str), np.asarray(f.ids, dtype=str))
    if isinstance(f, ast.Compare):
        return _compare(f, b)
    if isinstance(f, ast.Between):
        lo = ast.Compare(ast.CompareOp.GE, f.prop, f.lo)
        hi = ast.Compare(ast.CompareOp.LE, f.prop, f.hi)
        return _compare(lo, b) & _compare(hi, b)
    if isinstance(f, ast.Like):
        return _like(f, b)
    if isinstance(f, ast.IsNull):
        return ~b.col(f.prop).valid
    if isinstance(f, ast.InList):
        col = b.col(f.prop)
        if isinstance(col, StringColumn):
            codes = [col.code_of(str(v)) for v in f.values]
            codes = [c for c in codes if c >= 0]
            return np.isin(col.codes, codes) if codes else np.zeros(n, bool)
        vals = _values(col)
        return np.isin(vals, np.asarray(list(f.values))) & col.valid
    if isinstance(f, ast.BBox):
        return _bbox(f, b)
    if isinstance(f, ast.DWithin):
        return _dwithin(f, b)
    if isinstance(f, ast.SpatialPredicate):
        return _spatial(f, b)
    if isinstance(f, (ast.During, ast.Before, ast.After, ast.TEquals)):
        return _temporal(f, b)
    raise TypeError(f"cannot evaluate {type(f).__name__}")


def _values(col) -> np.ndarray:
    if isinstance(col, NumericColumn):
        return col.values
    if isinstance(col, DateColumn):
        return col.millis
    if isinstance(col, BoolColumn):
        return col.values
    raise TypeError(f"no raw values for {type(col).__name__}")


def _compare(f: ast.Compare, b: FeatureBatch) -> np.ndarray:
    col = b.col(f.prop)
    op = f.op
    if isinstance(col, StringColumn):
        # evaluate on the vocab, then map through codes
        vocab = col.vocab.astype(str)
        v = str(f.value)
        vres = {
            ast.CompareOp.EQ: vocab == v,
            ast.CompareOp.NE: vocab != v,
            ast.CompareOp.LT: vocab < v,
            ast.CompareOp.GT: vocab > v,
            ast.CompareOp.LE: vocab <= v,
            ast.CompareOp.GE: vocab >= v,
        }[op]
        ok = np.zeros(b.n, dtype=bool)
        valid = col.codes >= 0
        ok[valid] = vres[col.codes[valid]]
        return ok
    vals = _values(col)
    v = f.value
    if isinstance(col, DateColumn) and isinstance(v, str):
        v = to_millis(v)
    res = {
        ast.CompareOp.EQ: vals == v,
        ast.CompareOp.NE: vals != v,
        ast.CompareOp.LT: vals < v,
        ast.CompareOp.GT: vals > v,
        ast.CompareOp.LE: vals <= v,
        ast.CompareOp.GE: vals >= v,
    }[op]
    return res & col.valid


def _like(f: ast.Like, b: FeatureBatch) -> np.ndarray:
    col = b.col(f.prop)
    if not isinstance(col, StringColumn):
        raise TypeError("LIKE requires a string attribute")
    vocab_ok = like_vocab_mask(f.pattern, f.case_sensitive, col.vocab)
    ok = np.zeros(b.n, dtype=bool)
    valid = col.codes >= 0
    ok[valid] = vocab_ok[col.codes[valid]]
    return ok


def _geom_xy(b: FeatureBatch, prop: str):
    col = b.col(prop)
    if isinstance(col, PointColumn):
        return col.x, col.y, col.valid, None
    if isinstance(col, GeometryColumn):
        return None, None, col.valid, col
    raise TypeError(f"{prop} is not a geometry column")


def _bbox(f: ast.BBox, b: FeatureBatch) -> np.ndarray:
    x, y, valid, gc = _geom_xy(b, f.prop)
    if gc is None:
        return ((x >= f.xmin) & (x <= f.xmax)
                & (y >= f.ymin) & (y <= f.ymax) & valid)
    # bbox-vs-envelope prefilter, exact intersects per candidate
    env = Envelope(f.xmin, f.ymin, f.xmax, f.ymax)
    bx = gc.bounds
    cand = ((bx[:, 0] <= env.xmax) & (bx[:, 2] >= env.xmin)
            & (bx[:, 1] <= env.ymax) & (bx[:, 3] >= env.ymin))
    out = np.zeros(b.n, dtype=bool)
    box = env.to_polygon()
    for i in np.flatnonzero(cand):
        out[i] = gc.geoms[i] is not None and box.intersects(gc.geoms[i])
    return out


def _spatial(f: ast.SpatialPredicate, b: FeatureBatch) -> np.ndarray:
    x, y, valid, gc = _geom_xy(b, f.prop)
    g = f.geom
    if gc is None:
        # vectorized fast paths for point columns
        if isinstance(f, (ast.Intersects, ast.Within)) and hasattr(g, "contains_points"):
            return g.contains_points(x, y) & valid
        if isinstance(f, ast.Disjoint) and hasattr(g, "contains_points"):
            return ~g.contains_points(x, y) & valid
        env = g.envelope
        cand = (x >= env.xmin) & (x <= env.xmax) & (y >= env.ymin) \
            & (y <= env.ymax) & valid
        if isinstance(f, ast.Disjoint):
            out = np.ones(b.n, dtype=bool) & valid
        else:
            out = np.zeros(b.n, dtype=bool)
        for i in np.flatnonzero(cand):
            p = Point(x[i], y[i])
            out[i] = _apply_pred(f, p, g)
        return out
    out = np.zeros(b.n, dtype=bool)
    env = g.envelope
    bx = gc.bounds
    if isinstance(f, ast.Disjoint):
        cand = np.flatnonzero(valid)
    else:
        cand = np.flatnonzero(
            valid & (bx[:, 0] <= env.xmax) & (bx[:, 2] >= env.xmin)
            & (bx[:, 1] <= env.ymax) & (bx[:, 3] >= env.ymin))
    for i in cand:
        out[i] = _apply_pred(f, gc.geoms[i], g)
    return out


def _apply_pred(f: ast.SpatialPredicate, feature_geom, query_geom) -> bool:
    if isinstance(f, ast.Intersects):
        return feature_geom.intersects(query_geom)
    if isinstance(f, ast.Disjoint):
        return not feature_geom.intersects(query_geom)
    if isinstance(f, ast.Contains):
        # ECQL CONTAINS(attr, g): the feature geometry contains g
        return feature_geom.contains(query_geom)
    if isinstance(f, ast.Within):
        return query_geom.contains(feature_geom)
    # DE-9IM-derived predicates (full JTS semantics; the envelope
    # prefilter above already rejected the cheap negatives)
    from ..geometry import relate as _rel
    if isinstance(f, ast.Touches):
        return _rel.touches(feature_geom, query_geom)
    if isinstance(f, ast.GeomEquals):
        return _rel.topo_equals(feature_geom, query_geom)
    if isinstance(f, ast.Crosses):
        return _rel.crosses(feature_geom, query_geom)
    if isinstance(f, ast.Overlaps):
        return _rel.overlaps(feature_geom, query_geom)
    raise TypeError(type(f).__name__)


def _dwithin(f: ast.DWithin, b: FeatureBatch) -> np.ndarray:
    deg = dwithin_degrees(f.geom, f.distance, f.units)
    x, y, valid, gc = _geom_xy(b, f.prop)
    if gc is None and isinstance(f.geom, Point):
        dx = x - f.geom.x
        dy = y - f.geom.y
        return (dx * dx + dy * dy <= deg * deg) & valid
    env = f.geom.envelope.buffer(deg)
    out = np.zeros(b.n, dtype=bool)
    if gc is None:
        cand = np.flatnonzero((x >= env.xmin) & (x <= env.xmax)
                              & (y >= env.ymin) & (y <= env.ymax) & valid)
        for i in cand:
            out[i] = Point(x[i], y[i]).dwithin(f.geom, deg)
    else:
        bx = gc.bounds
        cand = np.flatnonzero(
            valid & (bx[:, 0] <= env.xmax) & (bx[:, 2] >= env.xmin)
            & (bx[:, 1] <= env.ymax) & (bx[:, 3] >= env.ymin))
        for i in cand:
            out[i] = gc.geoms[i].dwithin(f.geom, deg)
    return out


def _temporal(f, b: FeatureBatch) -> np.ndarray:
    col = b.col(f.prop)
    if not isinstance(col, DateColumn):
        raise TypeError(f"{f.prop} is not a date column")
    ms = col.millis
    if isinstance(f, ast.During):
        return (ms > f.start) & (ms < f.end) & col.valid
    if isinstance(f, ast.Before):
        return (ms < f.time) & col.valid
    if isinstance(f, ast.After):
        return (ms > f.time) & col.valid
    if isinstance(f, ast.TEquals):
        return (ms == f.time) & col.valid
    raise TypeError(type(f).__name__)
