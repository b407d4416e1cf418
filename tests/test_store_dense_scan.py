"""Store queries through the scan tiers at their default knobs: wide
queries take the dense z3 pass, selective ones the host index, and every
answer holds the ids of the f64 filter, also after a write."""

import numpy as np
import pytest

from geomesa_tpu.features import FeatureBatch, parse_spec
from geomesa_tpu.filters import evaluate, parse_ecql
from geomesa_tpu.index.api import Query
from geomesa_tpu.store import InMemoryDataStore

MS = lambda s: int(np.datetime64(s, "ms").astype(np.int64))

N = 60_000


@pytest.fixture(scope="module")
def store():
    """(the store, the batch written to it)"""
    sft = parse_spec("pts", "dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(23)
    batch = FeatureBatch.from_dict(
        sft, np.array([f"p{i}" for i in range(N)], dtype=object), {
            "dtg": rng.integers(MS("2020-01-01"), MS("2020-06-01"), N),
            "geom": (rng.uniform(-180, 180, N), rng.uniform(-90, 90, N)),
        })
    ds = InMemoryDataStore()
    ds.create_schema(sft)
    ds.write("pts", batch)
    return ds, batch


QUERIES = [
    # wide boxes exceed the pruning threshold -> the dense tier
    ("BBOX(geom, -180, -90, 180, 0)", True),
    ("BBOX(geom, -180, -90, 0, 90) OR BBOX(geom, 10, 10, 180, 90)", True),
    ("BBOX(geom, -180, -90, 180, 90) AND "
     "dtg DURING 2020-01-05T00:00:00Z/2020-05-20T00:00:00Z", True),
    # selective queries ride the index-pruned tiers
    ("BBOX(geom, -10, -10, 10, 10)", False),
    ("BBOX(geom, -180, -90, 180, 90) AND "
     "dtg DURING 2020-02-01T00:00:00Z/2020-02-20T00:00:00Z", False),
]


@pytest.mark.parametrize("ecql,dense", QUERIES)
def test_query_answers_f64_ids(store, ecql, dense):
    ds, batch = store
    lines = []
    res = ds.query(Query("pts", ecql), explain_out=lines.append)
    assert any(ln.strip().startswith("Device scan:")
               for ln in lines) == dense, lines
    want = batch.ids[evaluate(parse_ecql(ecql), batch)]
    assert len(want) > 0
    assert set(res.ids.astype(str)) == set(want.astype(str))


def test_query_after_a_write_answers_the_new_rows():
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("t", "dtg:Date,*geom:Point:srid=4326"))
    ds.write_dict("t", ["a"], {"dtg": [MS("2020-01-05")],
                               "geom": ([1.0], [1.0])})
    ecql = ("BBOX(geom, -180, -90, 180, 90) AND "
            "dtg DURING 2020-01-01T00:00:00Z/2020-02-01T00:00:00Z")
    assert list(ds.query(ecql, "t").ids) == ["a"]
    ds.write_dict("t", ["b"], {"dtg": [MS("2020-01-06")],
                               "geom": ([2.0], [2.0])})
    assert sorted(ds.query(ecql, "t").ids) == ["a", "b"]
