"""Flag-gated Pallas production path: store queries under
geomesa.scan.kernel=pallas must return identical IDs to the XLA path
(the Z3Iterator fusion promoted to the hand-tiled kernel)."""

import numpy as np
import pytest

from geomesa_tpu.features import parse_spec
from geomesa_tpu.index.api import Query
from geomesa_tpu.store import InMemoryDataStore
from geomesa_tpu.store.memory import SCAN_KERNEL

MS = lambda s: int(np.datetime64(s, "ms").astype(np.int64))

N = 60_000


@pytest.fixture(scope="module")
def store():
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("pts", "dtg:Date,*geom:Point:srid=4326"))
    rng = np.random.default_rng(23)
    ds.write_dict("pts", [f"p{i}" for i in range(N)], {
        "dtg": rng.integers(MS("2020-01-01"), MS("2020-06-01"), N),
        "geom": (rng.uniform(-180, 180, N), rng.uniform(-90, 90, N)),
    })
    return ds


QUERIES = [
    # wide boxes exceed the pruning threshold -> DENSE path, flag applies
    ("BBOX(geom, -180, -90, 180, 0)", True),
    ("BBOX(geom, -180, -90, 0, 90) OR BBOX(geom, 10, 10, 180, 90)", True),
    ("BBOX(geom, -180, -90, 180, 90) AND "
     "dtg DURING 2020-01-05T00:00:00Z/2020-05-20T00:00:00Z", True),
    # selective queries ride the index-pruned tiers and must stay
    # correct with the flag set
    ("BBOX(geom, -10, -10, 10, 10)", False),
    ("BBOX(geom, -180, -90, 180, 90) AND "
     "dtg DURING 2020-02-01T00:00:00Z/2020-02-20T00:00:00Z", False),
]


@pytest.mark.parametrize("ecql,dense", QUERIES)
def test_pallas_flag_parity(store, ecql, dense):
    want = set(store.query(ecql, "pts").ids.astype(str))
    SCAN_KERNEL.set("pallas")
    try:
        lines = []
        res = store.query(Query("pts", ecql), explain_out=lines.append)
        if dense:
            assert any("Pallas device scan" in ln for ln in lines), lines
    finally:
        SCAN_KERNEL.set(None)
    assert set(res.ids.astype(str)) == want


def test_pallas_data_invalidated_by_writes():
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("t", "dtg:Date,*geom:Point:srid=4326"))
    rng = np.random.default_rng(24)
    ds.write_dict("t", ["a"], {"dtg": [MS("2020-01-05")],
                               "geom": ([1.0], [1.0])})
    SCAN_KERNEL.set("pallas")
    try:
        ecql = ("BBOX(geom, -180, -90, 180, 90) AND "
                "dtg DURING 2020-01-01T00:00:00Z/2020-02-01T00:00:00Z")
        assert ds.query(ecql, "t").n == 1
        ds.write_dict("t", ["b"], {"dtg": [MS("2020-01-06")],
                                   "geom": ([2.0], [2.0])})
        assert ds.query(ecql, "t").n == 2
    finally:
        SCAN_KERNEL.set(None)
