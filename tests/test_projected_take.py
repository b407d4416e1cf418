"""Projected gather for host filter evaluation: ``take_for`` gathers only
the columns a filter names (and the ids only under a ``FidFilter``), and
``evaluate`` over it answers exactly as over a full ``batch.take``."""

import numpy as np
import pytest

from geomesa_tpu.features import FeatureBatch, parse_spec
from geomesa_tpu.filters import evaluate, parse_ecql
from geomesa_tpu.filters.evaluate import take_for
from geomesa_tpu.index.api import Query
from geomesa_tpu.store import InMemoryDataStore

SPEC = ("*geom:Point:srid=4326,dtg:Date,v:Integer,score:Double,"
        "name:String,tag:String,flag:Boolean,note:String")
DAY = 86_400_000
N = 4_000


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(30)
    x = rng.uniform(-30, 30, N)
    y = rng.uniform(-30, 30, N)
    x[::97] = np.nan                                  # invalid points
    score = rng.normal(0, 2, N)
    score[::13] = np.nan                              # invalid numbers
    dtg = rng.integers(0, 40 * DAY, N).astype(object)
    dtg[::29] = None                                  # null dates
    names = np.array([f"n{i}" for i in rng.integers(0, 9, N)], dtype=object)
    names[::11] = None                                # null codes (-1)
    return FeatureBatch.from_dict(
        parse_spec("mix", SPEC),
        np.array([f"f{i}" for i in range(N)], dtype=object),
        {"geom": (x, y),
         "dtg": dtg,
         "v": rng.integers(0, 100, N),
         "score": score,
         "name": names,
         "tag": np.array([f"t{i % 4}" for i in range(N)], dtype=object),
         "flag": rng.integers(0, 2, N).astype(bool),
         "note": np.array([f"w{i % 50}" for i in range(N)], dtype=object)})


# filter, the columns it must gather, whether it must gather the ids
CASES = {
    "in-strings-nulls": ("name IN ('n1', 'n3', 'zz')", {"name"}, False),
    "compare-string-nulls": ("name < 'n4'", {"name"}, False),
    "compare-numeric-nan": ("score >= 0.5", {"score"}, False),
    "between": ("score BETWEEN -1 AND 1", {"score"}, False),
    "like": ("name LIKE 'n%'", {"name"}, False),
    "ilike": ("tag ILIKE 'T1%'", {"tag"}, False),
    "is-null-string": ("name IS NULL", {"name"}, False),
    "is-null-numeric": ("score IS NULL", {"score"}, False),
    "during": ("dtg DURING 1970-01-05T00:00:00Z/1970-01-20T00:00:00Z",
               {"dtg"}, False),
    "bbox": ("BBOX(geom, -10, -5, 12, 20)", {"geom"}, False),
    "dwithin": ("DWITHIN(geom, POINT(3 4), 900, kilometers)",
                {"geom"}, False),
    "nested": ("BBOX(geom, -20, -20, 20, 20) AND (v < 40 OR NOT "
               "(name = 'n2' OR flag = true)) AND NOT score IS NULL",
               {"geom", "v", "name", "flag", "score"}, False),
    "fid": ("IN ('f1', 'f17', 'f3999', 'nope')", set(), True),
    "fid-nested": ("v > 50 OR IN ('f2', 'f400')", {"v"}, True),
    "include": ("INCLUDE", set(), False),
}


@pytest.mark.parametrize("case", [*CASES, "empty-idx", "unknown-attr"])
def test_projected_take_matches_full_take(batch, case):
    rng = np.random.default_rng(31)
    idx = np.union1d(rng.choice(N, N // 3, replace=False), [1, 2, 17, 400])
    if case == "unknown-attr":
        f = parse_ecql("v < 10 AND nope = 3")
        with pytest.raises(KeyError, match="nope"):
            evaluate(f, batch.take(idx))
        with pytest.raises(KeyError, match="nope"):
            evaluate(f, take_for(f, batch, idx))
        return
    ecql, cols, ids = CASES.get(case, CASES["nested"])
    if case == "empty-idx":
        idx = idx[:0]
    f = parse_ecql(ecql)
    sub = take_for(f, batch, idx)
    assert set(sub.columns) == cols
    assert (sub.ids is not None) is ids
    assert sub.n == len(idx)
    full = batch.take(idx)
    for name in cols:          # same values, validity and vocab
        a, b = sub.col(name), full.col(name)
        for k in ("values", "millis", "codes", "vocab", "x", "y", "valid"):
            if hasattr(b, k):
                np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    want = evaluate(f, full)
    got = evaluate(f, sub)
    assert got.dtype == bool and got.shape == (len(idx),)
    np.testing.assert_array_equal(got, want)
    if case not in ("empty-idx", "include"):
        assert 0 < want.sum() < len(idx)


def test_projection_by_mask_and_full_default(batch):
    mask = np.zeros(N, dtype=bool)
    mask[::7] = True
    sub = batch.take(mask, columns={"v"}, ids=False)
    assert sub.n == mask.sum() and sub.ids is None
    np.testing.assert_array_equal(sub.col("v").values,
                                  batch.col("v").values[mask])
    full = batch.take(np.arange(5))
    assert list(full.columns) == list(batch.columns)
    assert list(full.ids) == ["f0", "f1", "f2", "f3", "f4"]


# a store the size of a host-tier request: x, y, millis, two string
# columns (one indexed), numbers with NaNs, and columns no filter names
GSPEC = ("code:String:index=true,quad:Integer,tone:Double,actor:String,"
         "mentions:Integer,src:String,dtg:Date,*geom:Point:srid=4326")
GN = 30_000


@pytest.fixture(scope="module")
def gstore():
    rng = np.random.default_rng(3030)
    cols = {
        "code": np.array([f"{i:02d}" for i in rng.integers(1, 21, GN)],
                         dtype=object),
        "quad": rng.integers(1, 5, GN),
        "tone": rng.normal(0, 4, GN),
        "actor": np.array([f"a{i}" for i in rng.integers(0, 300, GN)],
                          dtype=object),
        "mentions": rng.integers(1, 60, GN),
        "src": np.array([f"http://s/{i}" for i in range(GN)], dtype=object),
        "dtg": rng.integers(0, 200 * DAY, GN).astype(np.int64),
        "geom": (rng.uniform(-180, 180, GN), rng.uniform(-90, 90, GN)),
    }
    cols["code"][::31] = None
    cols["tone"][::17] = np.nan
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("ev", GSPEC))
    ds.write_dict("ev", [f"e{i}" for i in range(GN)], cols)
    return ds, cols


def _ref(cols, box, days, preds):
    """Numpy over the full generated columns, no evaluator."""
    x, y = cols["geom"]
    ms = cols["dtg"]
    keep = ((x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
            & (ms > days[0] * DAY) & (ms < days[1] * DAY))
    for attr, op, val in preds:
        col = cols[attr]
        if op == "IN":
            keep &= np.isin(col.astype(str), list(val)) & (col != None)  # noqa: E711
        elif op == "=":
            keep &= col == val
        elif op == "<=":
            keep &= ~np.isnan(col) & (col <= val)
        else:
            keep &= col >= val
    return np.sort(np.array([f"e{i}" for i in np.flatnonzero(keep)]))


STORE_CASES = [
    ((-60, -30, 40, 35), (10, 120), [("code", "IN", ("03", "07", "11"))]),
    ((-100, -50, 20, 10), (0, 90),
     [("code", "IN", ("01", "02")), ("quad", "=", 3)]),
    ((0, 0, 90, 60), (30, 200),
     [("code", "IN", ("05",)), ("tone", "<=", 1.5), ("mentions", ">=", 20)]),
]


def _ecql(box, days, preds):
    parts = [f"BBOX(geom, {box[0]}, {box[1]}, {box[2]}, {box[3]})",
             f"dtg DURING {np.datetime64(days[0] * DAY, 'ms')}Z/"
             f"{np.datetime64(days[1] * DAY, 'ms')}Z"]
    for attr, op, val in preds:
        if op == "IN":
            parts.append(f"{attr} IN ({', '.join(repr(v) for v in val)})")
        else:
            parts.append(f"{attr} {op} {val}")
    return " AND ".join(parts)


@pytest.mark.parametrize("index", ["z3", "attr:code"])
@pytest.mark.parametrize("case", range(len(STORE_CASES)))
def test_store_host_residual_matches_numpy(gstore, index, case):
    ds, cols = gstore
    box, days, preds = STORE_CASES[case]
    lines = []
    res = ds.query(Query("ev", _ecql(box, days, preds),
                         hints={"QUERY_INDEX": index}),
                   explain_out=lines.append)
    assert res.plan.index == index
    text = "\n".join(lines)
    assert "Residual filter applied" in text
    assert "Device residual scan" not in text       # the host branch
    want = _ref(cols, box, days, preds)
    assert len(want) > 0
    np.testing.assert_array_equal(np.sort(res.ids.astype(str)), want)
