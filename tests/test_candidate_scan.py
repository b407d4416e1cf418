"""Candidate tier: the dense z3 pass patched over the whole table gives the
same ids as a scan of just the candidate rows (``zscan.scan_mask_at`` and
the patch on those rows) and as the f64 filter, with points on and one ulp
beside every box edge and time bound, over written and appended columns."""

import numpy as np
import pytest

from geomesa_tpu.filters import evaluate, parse_ecql
from geomesa_tpu.index.api import Query
from geomesa_tpu.index.zkeys import SCAN_BLOCK_THRESHOLD
from geomesa_tpu.scan import zscan
from geomesa_tpu.store.memory import HOST_SCAN_ROWS

MS = lambda s: int(np.datetime64(s, "ms").astype(np.int64))

T0, T1 = "2020-02-03T04:05:06.789Z", "2020-03-07T08:09:10.111Z"
WINDOW = f"dtg DURING {T0}/{T1}"
BOXES = [(-100.3, -60.7, 100.1, 60.3), (120.01, -20.2, 150.7, 33.3)]


def _edge_points(rng, boxes, lo, hi):
    """Points on, and one ulp either side of, each box edge and each
    time bound; the other coordinate and the time drawn inside."""
    xs, ys, ts = [], [], []
    for xmin, ymin, xmax, ymax in boxes:
        for e in (xmin, xmax, ymin, ymax):
            for v in (np.nextafter(e, -np.inf), e, np.nextafter(e, np.inf)):
                for _ in range(4):
                    x, y = rng.uniform(xmin, xmax), rng.uniform(ymin, ymax)
                    if e in (xmin, xmax):
                        x = v
                    else:
                        y = v
                    xs.append(x), ys.append(y)
                    ts.append(rng.integers(lo, hi))
        for t in (lo - 1, lo, lo + 1, hi - 1, hi, hi + 1):
            for _ in range(4):
                xs.append(rng.uniform(xmin, xmax))
                ys.append(rng.uniform(ymin, ymax))
                ts.append(t)
    return np.array(xs), np.array(ys), np.array(ts, dtype=np.int64)


def _row_scan(st, sq, rows):
    """A scan of just ``rows`` and the boundary patch on them alone."""
    sub = zscan.scan_mask_at(st.scan_data, sq, rows)
    col = st.batch.col("geom")
    xhi, _ = zscan.split_two_float(col.x[rows])
    yhi, _ = zscan.split_two_float(col.y[rows])
    cand = zscan.boundary_candidates(xhi, yhi, sq)
    sub = zscan.exact_patch(sub, cand, col.x[rows], col.y[rows],
                            st.batch.col("dtg").millis[rows], sq)
    return np.sort(rows[sub])


@pytest.fixture(scope="module")
def stores(point_store):
    rng = np.random.default_rng(24)
    n = 20_000
    lo, hi = MS(T0[:-1]), MS(T1[:-1])
    ex, ey, et = _edge_points(rng, BOXES, lo, hi)
    x = np.concatenate([rng.uniform(-180, 180, n), ex])
    y = np.concatenate([rng.uniform(-90, 90, n), ey])
    t = np.concatenate([rng.integers(MS("2020-01-01"), MS("2020-05-01"), n),
                        et])
    # appended, the edge points arrive in the last, in-place write
    return {a: point_store(x, y, t, appended=a) for a in (False, True)}


@pytest.fixture
def candidate_tier():
    HOST_SCAN_ROWS.set("10")
    SCAN_BLOCK_THRESHOLD.set("0.9")
    try:
        yield
    finally:
        for p in (HOST_SCAN_ROWS, SCAN_BLOCK_THRESHOLD):
            p.set(None)


@pytest.mark.parametrize("appended", [False, True],
                         ids=["written", "appended"])
@pytest.mark.parametrize("timed", [False, True], ids=["no-time", "time"])
@pytest.mark.parametrize("nboxes", [1, 2])
def test_candidate_tier_matches_row_scan_and_f64(stores, candidate_tier,
                                                 monkeypatch, appended, timed,
                                                 nboxes):
    ds = stores[appended]
    bbox = " OR ".join(f"BBOX(geom, {a}, {b}, {c}, {d})"
                       for a, b, c, d in BOXES[:nboxes])
    ecql = f"({bbox}) AND {WINDOW}" if timed else bbox
    seen = []
    tier = type(ds)._scan_gathered

    def spy(self, st, sq, rows, explain, nb, ni):
        idx = tier(self, st, sq, rows, explain, nb, ni)
        seen.append((st, sq, rows, idx))
        return idx

    monkeypatch.setattr(type(ds), "_scan_gathered", spy)
    lines = []
    res = ds.query(Query("pts", ecql), explain_out=lines.append)
    assert any(ln.strip().startswith("Index-pruned device scan:")
               for ln in lines), lines
    ((st, sq, rows, idx),) = seen
    assert len(rows) > 0
    assert (st.scan_data.cap > st.n) == appended
    np.testing.assert_array_equal(idx, _row_scan(st, sq, rows))
    want = np.flatnonzero(evaluate(parse_ecql(ecql), st.batch))
    np.testing.assert_array_equal(idx, want)
    assert set(res.ids.astype(str)) == set(st.batch.ids[want].astype(str))
