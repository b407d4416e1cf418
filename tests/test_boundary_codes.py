"""The dense z3 pass's code byte: bit 0 is the two-float verdict
(``scan_mask``), bit 1 flags exactly the rows ``boundary_candidates`` finds
on the host, the patched codes hold the f64 hits and no padding row, and
the store's device tiers answer from them with the ids of the f64 filter
and of the row-subset patch they replace, over appended columns too."""

import numpy as np
import pytest

from geomesa_tpu.filters import evaluate, parse_ecql
from geomesa_tpu.index.api import Query
from geomesa_tpu.index.zkeys import SCAN_BLOCK_THRESHOLD
from geomesa_tpu.obs import tracer
from geomesa_tpu.obs.trace import TRACE_SAMPLE
from geomesa_tpu.scan import zscan
from geomesa_tpu.store.memory import HOST_SCAN_ROWS

MS = lambda s: int(np.datetime64(s, "ms").astype(np.int64))

T0, T1 = "2020-02-03T04:05:06.789", "2020-03-07T08:09:10.111"
BOXES = [(-100.3, -60.7, 100.1, 60.3), (120.01, -20.2, 150.7, 33.3),
         (0.0, -85.5, 20.25, -70.125)]


def _edge_points(rng, boxes, lo, hi):
    """Points on, and one ulp either side of, each box edge and each
    time bound; the other coordinate and the time drawn inside."""
    xs, ys, ts = [], [], []
    for xmin, ymin, xmax, ymax in boxes:
        for e in (xmin, xmax, ymin, ymax):
            for v in (np.nextafter(e, -np.inf), e, np.nextafter(e, np.inf)):
                for _ in range(3):
                    x, y = rng.uniform(xmin, xmax), rng.uniform(ymin, ymax)
                    if e in (xmin, xmax):
                        x = v
                    else:
                        y = v
                    xs.append(x), ys.append(y)
                    ts.append(rng.integers(lo, hi))
        for t in (lo - 1, lo, lo + 1, hi - 1, hi, hi + 1):
            for _ in range(3):
                xs.append(rng.uniform(xmin, xmax))
                ys.append(rng.uniform(ymin, ymax))
                ts.append(t)
    return np.array(xs), np.array(ys), np.array(ts, dtype=np.int64)


def _points(seed=28, n=6_000):
    rng = np.random.default_rng(seed)
    lo, hi = MS(T0), MS(T1)
    ex, ey, et = _edge_points(rng, BOXES, lo, hi)
    x = np.concatenate([rng.uniform(-180, 180, n), ex])
    y = np.concatenate([rng.uniform(-90, 90, n), ey])
    t = np.concatenate([rng.integers(MS("2020-01-01"), MS("2020-05-01"), n),
                        et])
    perm = rng.permutation(len(x))
    return x[perm], y[perm], t[perm]


def _query(nboxes, timed):
    return zscan.make_query(BOXES[:nboxes],
                            [(MS(T0), MS(T1))] if timed else None)


@pytest.fixture(scope="module")
def pts():
    return _points()


@pytest.mark.parametrize("timed", [False, True], ids=["no-time", "time"])
@pytest.mark.parametrize("nboxes", [1, 2, 3])
@pytest.mark.parametrize("padded", [False, True], ids=["full", "cap"])
def test_codes_are_verdict_and_host_flags(pts, nboxes, timed, padded):
    x, y, t = pts
    n = len(x)
    data = zscan.build_scan_data(x, y, t,
                                 cap=zscan.next_pow2(n + 1) if padded
                                 else None)
    assert (data.cap > n) == padded
    q = _query(nboxes, timed)
    codes = np.asarray(zscan.scan_codes(data, q))
    assert codes.dtype == np.uint8 and codes.shape == (data.cap,)
    assert codes.max() <= 3
    # bit 0 is the two-float verdict, bit for bit
    np.testing.assert_array_equal(codes & 1,
                                  np.asarray(zscan.scan_mask(data, q)))
    # bit 1 is the host's boundary test, row for row
    xhi, _ = zscan.split_two_float(x)
    yhi, _ = zscan.split_two_float(y)
    np.testing.assert_array_equal(np.flatnonzero(codes > 1),
                                  zscan.boundary_candidates(xhi, yhi, q))
    # the points on an edge and one ulp above it share the edge's hi-cell
    assert (codes > 1).sum() >= 4 * 2 * 3 * nboxes
    # capacity padding is neither a hit nor flagged
    assert not codes[n:].any()


def test_padding_is_not_flagged_on_a_bound_at_zero():
    # padding rows hold xhi = yhi = 0: a box edge at 0.0 shares their cell
    x = np.array([0.0, 5.0, 25.0])
    y = np.array([-80.0, 0.0, 0.0])
    t = np.zeros(3, dtype=np.int64)
    data = zscan.build_scan_data(x, y, t, cap=64)
    q = zscan.make_query([(0.0, 0.0, 10.0, 10.0)], None)
    codes = np.asarray(zscan.scan_codes(data, q))
    np.testing.assert_array_equal(codes[:3], [2, 3, 2])
    assert not codes[3:].any()


def test_subnormal_cells_share_the_zero_bound():
    # one f64 ulp below 0.0 splits to a subnormal f32 hi-cell, which the
    # device flushes to zero: the verdict there is the zero cell's, so the
    # host test flags those rows too, and the f64 recheck drops them
    x = np.array([-5e-324, -1e-40, 5e-324, 0.0, -1e-30, 3.0])
    y = np.full(6, 5.0)
    data = zscan.build_scan_data(x, y, np.zeros(6, dtype=np.int64))
    q = zscan.make_query([(0.0, 0.0, 10.0, 10.0)], None)
    codes = np.asarray(zscan.scan_codes(data, q))
    xhi, _ = zscan.split_two_float(x)
    yhi, _ = zscan.split_two_float(y)
    cand = zscan.boundary_candidates(xhi, yhi, q)
    np.testing.assert_array_equal(cand, [0, 1, 2, 3])
    np.testing.assert_array_equal(np.flatnonzero(codes > 1), cand)
    assert codes[5] == 1 and codes[4] == 0
    mask = zscan.exact_patch(codes, cand, x, y, np.zeros(6, np.int64), q)
    np.testing.assert_array_equal(np.flatnonzero(mask), [2, 3, 5])


# -- the patched codes against numpy f64 -------------------------------------

DAY = 86_400_000
PARITY = [
    ([(-80.0, 30.0, -60.0, 45.0)], [(20 * DAY, 50 * DAY)]),
    ([(-10.0, -10.0, 10.0, 10.0)], []),                      # no time
    ([(-80.0, 30.0, -60.0, 45.0), (0.0, 0.0, 30.0, 20.0),
      (100.0, -50.0, 140.0, -10.0)],                         # 3 boxes -> pad 4
     [(0, 10 * DAY), (90 * DAY, 99 * DAY)]),
    ([(-180.0, -90.0, 180.0, 90.0)], [(0, 100 * DAY)]),      # whole world
]


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(5)
    n = 300_001  # capacity-padded to 2^19 rows
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    t = rng.integers(0, 100 * DAY, n).astype(np.int64)
    return x, y, t, zscan.build_scan_data(x, y, t,
                                          cap=zscan.next_pow2(n + 1))


def _patched_hits(data, q, x, y, t):
    """scan_codes -> exact_patch of the flagged rows -> hit_rows."""
    codes = np.asarray(zscan.scan_codes(data, q))
    codes = zscan.exact_patch(codes, np.flatnonzero(codes > 1), x, y, t, q)
    return zscan.hit_rows(codes)


@pytest.mark.parametrize("boxes,intervals", PARITY)
def test_patched_codes_are_the_f64_hits(world, boxes, intervals):
    x, y, t, data = world
    want = np.zeros(len(x), dtype=bool)
    for xmin, ymin, xmax, ymax in boxes:
        want |= (x >= xmin) & (x <= xmax) & (y >= ymin) & (y <= ymax)
    if intervals:
        want &= np.any([(t >= lo) & (t <= hi) for lo, hi in intervals],
                       axis=0)
    got = _patched_hits(data, zscan.make_query(boxes, intervals), x, y, t)
    np.testing.assert_array_equal(got, np.flatnonzero(want))


def test_padding_rows_never_match(world):
    # whole-world query: every real row matches, no padding row does
    x, y, t, data = world
    assert data.cap > len(x)
    q = zscan.make_query([(-180.0, -90.0, 180.0, 90.0)], [])
    np.testing.assert_array_equal(_patched_hits(data, q, x, y, t),
                                  np.arange(len(x)))


# -- the store's device tiers -----------------------------------------------

@pytest.fixture(scope="module")
def stores(pts, point_store):
    return {a: point_store(*pts, appended=a) for a in (False, True)}


@pytest.fixture
def knobs():
    TRACE_SAMPLE.set("1")
    tracer.clear()
    try:
        yield
    finally:
        for p in (HOST_SCAN_ROWS, SCAN_BLOCK_THRESHOLD, TRACE_SAMPLE):
            p.set(None)
        tracer.clear()


def _his(st):
    """The two-float hi parts of the store's x and y columns."""
    col = st.batch.col("geom")
    return zscan.split_two_float(col.x)[0], zscan.split_two_float(col.y)[0]


def _old_dense(st, sq):
    """The dense tier before the codes: scan_mask + the host flag pass."""
    col = st.batch.col("geom")
    mask = np.asarray(zscan.scan_mask(st.scan_data, sq))[:st.n]
    cand = zscan.boundary_candidates(*_his(st), sq)
    return np.flatnonzero(zscan.exact_patch(
        mask, cand, col.x, col.y, st.batch.col("dtg").millis, sq))


def _old_gathered(st, sq, rows):
    """The candidate tier before the codes: a scan of just ``rows`` and
    the patch on them alone."""
    col = st.batch.col("geom")
    sub = zscan.scan_mask_at(st.scan_data, sq, rows)
    xhi, yhi = _his(st)
    cand = zscan.boundary_candidates(xhi[rows], yhi[rows], sq)
    sub = zscan.exact_patch(sub, cand, col.x[rows], col.y[rows],
                            st.batch.col("dtg").millis[rows], sq)
    return np.sort(rows[sub])


TIER_KNOBS = {"gathered": ("10", "0.9"), "dense": ("10", "0.00001")}


@pytest.mark.parametrize("appended", [False, True],
                         ids=["written", "appended"])
@pytest.mark.parametrize("tier", sorted(TIER_KNOBS))
@pytest.mark.parametrize("timed", [False, True], ids=["no-time", "time"])
@pytest.mark.parametrize("nboxes", [1, 3])
def test_device_tiers_answer_f64_ids(pts, stores, knobs, monkeypatch,
                                     appended, tier, timed, nboxes):
    ds = stores[appended]
    host, thr = TIER_KNOBS[tier]
    HOST_SCAN_ROWS.set(host)
    SCAN_BLOCK_THRESHOLD.set(thr)
    bbox = " OR ".join(f"BBOX(geom, {a}, {b}, {c}, {d})"
                       for a, b, c, d in BOXES[:nboxes])
    ecql = (f"({bbox}) AND dtg DURING {T0}Z/{T1}Z" if timed else bbox)
    seen = []
    name = f"_scan_{tier}"
    orig = getattr(type(ds), name)

    def spy(self, st, sq, *a):
        idx = orig(self, st, sq, *a)
        seen.append((st, sq, a[0] if tier == "gathered" else None, idx))
        return idx

    monkeypatch.setattr(type(ds), name, spy)
    with tracer.span("batcher-wait", "pts", root=True) as root:
        res = ds.query(Query("pts", ecql))
    ((st, sq, rows, idx),) = seen
    assert st.n == len(pts[0])
    assert (st.scan_data.cap > st.n) == appended
    want = np.flatnonzero(evaluate(parse_ecql(ecql), st.batch))
    assert len(want) > 0
    np.testing.assert_array_equal(idx, want)
    old = (_old_gathered(st, sq, rows) if tier == "gathered"
           else _old_dense(st, sq))
    np.testing.assert_array_equal(idx, old)
    assert set(res.ids.astype(str)) == set(st.batch.ids[want].astype(str))
    (patch,) = [s["attrs"] for s in tracer.get(root.trace_id)
                if s["kind"] == "boundary-patch"]
    flags = zscan.boundary_candidates(*_his(st), sq)
    assert patch["checked"] == len(flags) > 0


# -- the compaction of the patched mask into rows ----------------------------

@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
@pytest.mark.parametrize("dtype", [np.bool_, np.uint8])
@pytest.mark.parametrize("share", [0.0, 0.03, 0.5, 0.97, 1.0])
def test_hit_rows_is_flatnonzero(monkeypatch, native, dtype, share):
    if not native:
        monkeypatch.setattr(zscan, "_native_nonzero", False)
    elif zscan._nonzero_lib() is None:
        pytest.skip("the native library does not build here")
    rng = np.random.default_rng(28)
    for n in (0, 1, 7, 100_003):
        mask = (rng.random(n) < share).astype(dtype)
        got = zscan.hit_rows(mask)
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, np.flatnonzero(mask))
    # a strided view is compacted as its own rows
    mask = (rng.random(20_000) < share).astype(dtype)
    np.testing.assert_array_equal(zscan.hit_rows(mask[::3]),
                                  np.flatnonzero(mask[::3]))
