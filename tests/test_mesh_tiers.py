"""The mesh store's scan tiers against a plain numpy f64 reference.

``DistributedDataStore(data_mesh(4))`` holds AIS-shaped points (lane rows
first, then rows uniform over the globe, 100 days in ms) split by row
over four of the eight CPU devices. Each BBOX + DURING query runs in each
tier the thresholds can force (host exact, host candidates, mesh dense)
and must return exactly the ids of the reference: closed BBOX in f64,
DURING open at both ends in int64 ms. Rows sit exactly on, and one f64
ulp (one ms) either side of, every box edge and time bound. Each tier's
request writes its span under ``store-scan``.
"""

import jax
import numpy as np
import pytest

from geomesa_tpu.features import parse_spec
from geomesa_tpu.index.api import Query
from geomesa_tpu.index.zkeys import SCAN_BLOCK_THRESHOLD
from geomesa_tpu.obs import runtime, tracer
from geomesa_tpu.obs.trace import TRACE_SAMPLE
from geomesa_tpu.parallel import data_mesh, mesh
from geomesa_tpu.scan import zscan
from geomesa_tpu.store import DistributedDataStore
from geomesa_tpu.store.memory import HOST_SCAN_ROWS

SPEC = "mmsi:Integer,dtg:Date,*geom:Point:srid=4326"
DAY = 86_400_000
T0 = int(np.datetime64("2016-07-19", "ms").astype(np.int64))
N = 40_000
SHARD = N // 4

# name -> (box, (t0, t1) of DURING); the edges are not f32 values, so the
# two-float device compare and f64 disagree one ulp outside them
BOX = (-31.123456789012345, -12.987654321098765,
       47.555555555555557, 38.333333333333336)
WIN = (T0 + 11 * DAY + 12_345, T0 + 64 * DAY + 54_321)
CLUSTER = (100.0, -40.0)        # grid of points 0.001 deg apart
FAR = (T0 + 1500 * DAY, T0 + 1510 * DAY)   # a window only the cluster has
QUERIES = {
    "edges": (BOX, WIN),
    # all hits on one shard: the cluster's rows lie in shard 2 and its
    # times fall in a window no other row has
    "one-shard": ((99.9, -40.1, 100.1, -39.9), FAR),
    # candidates in the cluster's z-cells, no point inside the box
    "empty": ((100.0003, -39.9997, 100.0007, -39.9993), FAR),
}

# tier -> (host cap, block threshold); the explain line and span it gives
TIERS = {
    "host": ("1000000000", "0.9"),
    "host-candidates": ("0", "0.9"),
    "mesh-dense": ("0", "0"),
}
EXPLAIN = {"host": "Index-pruned host scan",
           "host-candidates": "Index-pruned host candidate scan",
           "mesh-dense": "Distributed scan over"}
SPANS = {"host": set(), "host-candidates": {"host-candidates"},
         "mesh-dense": {"mesh-scan", "mesh-patch"}}


def _edge_rows(rng, box, win, k=3):
    """Rows on, one ulp inside and one ulp outside each box edge, and on
    and one ms either side of each DURING bound; the other values inside
    the query."""
    xmin, ymin, xmax, ymax = box
    xs, ys, ts = [], [], []

    def inside(n):
        return (rng.uniform(xmin, xmax, n), rng.uniform(ymin, ymax, n),
                rng.integers(win[0] + 1, win[1], n))

    for axis, edge in ((0, xmin), (0, xmax), (1, ymin), (1, ymax)):
        for v in (edge, np.nextafter(edge, np.inf),
                  np.nextafter(edge, -np.inf)):
            x, y, t = inside(k)
            (x if axis == 0 else y)[:] = v
            xs.append(x), ys.append(y), ts.append(t)
    for bound in win:
        for dt in (-1, 0, 1):
            x, y, t = inside(k)
            t[:] = bound + dt
            xs.append(x), ys.append(y), ts.append(t)
    return np.concatenate(xs), np.concatenate(ys), np.concatenate(ts)


def _ais(rng, n):
    """Half the rows on 40 lanes of +-20 deg with 0.5 deg spread, then the
    rest uniform over the globe; times uniform over 100 days."""
    half = n // 2
    c = rng.uniform((-160, -60), (160, 60), (40, 2))
    a = rng.uniform(0, np.pi, 40)
    lane = rng.integers(0, 40, half)
    s = rng.uniform(-20, 20, half)
    x = c[lane, 0] + s * np.cos(a[lane]) + rng.normal(0, 0.5, half)
    y = c[lane, 1] + s * np.sin(a[lane]) + rng.normal(0, 0.5, half)
    x = np.clip(np.concatenate([x, rng.uniform(-180, 180, n - half)]),
                -180, 180)
    y = np.clip(np.concatenate([y, rng.uniform(-90, 90, n - half)]),
                -90, 90)
    return x, y, rng.integers(T0, T0 + 100 * DAY, n)


def _table(seed, n, cluster_at=None):
    """(x, y, millis) of ``n`` rows with the edge rows of ``BOX``/``WIN``
    at random rows, and the cluster's grid at rows ``cluster_at``."""
    rng = np.random.default_rng(seed)
    x, y, t = _ais(rng, n)
    ex, ey, et = _edge_rows(rng, BOX, WIN)
    at = rng.choice(n, len(ex), replace=False)
    if cluster_at is not None:
        at = at[~np.isin(at, cluster_at)]
        g = np.arange(len(cluster_at))
        x[cluster_at] = CLUSTER[0] + 0.001 * (g % 20 - 10)
        y[cluster_at] = CLUSTER[1] + 0.001 * (g // 20 - 5)
        t[cluster_at] = rng.integers(FAR[0] + 1, FAR[1], len(g))
    x[at], y[at], t[at] = ex[:len(at)], ey[:len(at)], et[:len(at)]
    return x, y, t


def _write(ds, first, x, y, t):
    ds.write_dict("ais", [f"r{first + i}" for i in range(len(x))],
                  {"mmsi": np.arange(len(x)) % 997, "dtg": t,
                   "geom": (x, y)})


class Table:
    def __init__(self, ds, x, y, t):
        self.ds, self.x, self.y, self.t = ds, x, y, t
        self.ids = np.array([f"r{i}" for i in range(len(x))], dtype=object)

    def reference(self, name):
        (xmin, ymin, xmax, ymax), (t0, t1) = QUERIES[name]
        m = ((self.x >= xmin) & (self.x <= xmax) & (self.y >= ymin)
             & (self.y <= ymax) & (self.t > t0) & (self.t < t1))
        return sorted(self.ids[m])


def _ecql(name):
    box, (t0, t1) = QUERIES[name]
    iso = [str(np.datetime64(int(v), "ms")) + "Z" for v in (t0, t1)]
    coords = ", ".join(repr(float(v)) for v in box)
    return f"BBOX(geom, {coords}) AND dtg DURING {iso[0]}/{iso[1]}"


@pytest.fixture(scope="module")
def one_segment():
    cluster = np.arange(2 * SHARD, 2 * SHARD + 200)   # from shard 2's top
    x, y, t = _table(5, N, cluster)
    ds = DistributedDataStore(data_mesh(4))
    ds.create_schema(parse_spec("ais", SPEC))
    _write(ds, 0, x, y, t)
    return Table(ds, x, y, t)


@pytest.fixture(scope="module")
def three_segments():
    """A base write, then two write bursts after the index is built: three
    device segments, so every tier sees row offsets."""
    cluster = np.arange(2 * SHARD, 2 * SHARD + 200)   # from shard 2's top
    parts = [_table(6, N, cluster), _table(7, 3_001), _table(8, 2_003)]
    ds = DistributedDataStore(data_mesh(4))
    ds.create_schema(parse_spec("ais", SPEC))
    first = 0
    for x, y, t in parts:
        _write(ds, first, x, y, t)
        first += len(x)
        ds.query(Query("ais", _ecql("edges")))    # builds, then extends
    assert len(ds._state("ais").segments) == 3
    x, y, t = (np.concatenate(c) for c in zip(*parts))
    return Table(ds, x, y, t)


@pytest.fixture
def tier():
    """Sets the thresholds of one tier; traces every query."""
    def force(name):
        host, thr = TIERS[name]
        HOST_SCAN_ROWS.set(host)
        SCAN_BLOCK_THRESHOLD.set(thr)
    TRACE_SAMPLE.set("1")
    tracer.clear()
    try:
        yield force
    finally:
        for p in (TRACE_SAMPLE, HOST_SCAN_ROWS, SCAN_BLOCK_THRESHOLD):
            p.set(None)
        tracer.clear()


def _query(table, name):
    """(result, explain lines, {kind: attrs} of store-scan's children)."""
    lines = []
    with tracer.span("batcher-wait", "ais", root=True) as root:
        res = table.ds.query(Query("ais", _ecql(name)),
                             explain_out=lines.append)
    spans = tracer.get(root.trace_id)
    (scan,) = [s for s in spans if s["kind"] == "store-scan"]
    kids = {s["kind"]: s.get("attrs", {}) for s in spans
            if s["parent_id"] == scan["span_id"]}
    return res, lines, kids


def _ran(tier_name, lines) -> bool:
    return any(ln.strip().startswith(EXPLAIN[tier_name]) for ln in lines)


def _check_spans(tier_name, res, kids, segments):
    assert SPANS[tier_name] <= set(kids)
    assert not (set().union(*SPANS.values()) - SPANS[tier_name]) & set(kids)
    if tier_name == "host-candidates":
        c = kids["host-candidates"]
        assert c["hits"] == res.n
        assert c["rows"] == kids["index-search"]["rows"] >= c["hits"]
    if tier_name == "mesh-dense":
        s, p = kids["mesh-scan"], kids["mesh-patch"]
        assert s["segments"] == segments and s["shards"] == 4
        assert sum(s["shard_hits"]) == s["hits"] <= s["cap"]
        if segments == 1:       # else the sum of each segment's cap
            assert s["cap"] == 0 or s["cap"] & (s["cap"] - 1) == 0
        assert s["d2h_bytes"] == 4 * s["cap"]
        assert p["checked"] >= p["added"] + p["removed"]
        assert res.n == s["hits"] + p["added"] - p["removed"]


@pytest.mark.parametrize("name", sorted(QUERIES))
@pytest.mark.parametrize("tier_name", sorted(TIERS))
def test_tier_is_id_exact(one_segment, tier, tier_name, name):
    tier(tier_name)
    res, lines, kids = _query(one_segment, name)
    assert _ran(tier_name, lines), lines
    assert sorted(res.ids) == one_segment.reference(name)
    _check_spans(tier_name, res, kids, 1)
    if tier_name == "mesh-dense":
        assert kids["mesh-scan"]["rows"] == N
        want = {"edges": None, "one-shard": [0, 0, res.n, 0],
                "empty": [0, 0, 0, 0]}[name]
        if want is not None:
            assert kids["mesh-scan"]["shard_hits"] == want


@pytest.mark.parametrize("tier_name", sorted(TIERS))
def test_segments_keep_their_row_offsets(three_segments, tier, tier_name):
    tier(tier_name)
    for name in ("edges", "one-shard"):
        res, lines, kids = _query(three_segments, name)
        assert _ran(tier_name, lines), lines
        assert sorted(res.ids) == three_segments.reference(name)
        assert res.n > 0
        _check_spans(tier_name, res, kids, 3)


def test_edge_rows_one_ulp_outside_are_removed(one_segment, tier):
    tier("mesh-dense")
    res, _lines, kids = _query(one_segment, "edges")
    assert kids["mesh-patch"]["removed"] > 0
    assert sorted(res.ids) == one_segment.reference("edges")


def test_patch_adds_and_removes_in_one_splice(one_segment, tier,
                                              monkeypatch):
    """A device that misses every other exact hit among the boundary
    candidates (and says so in its recomputed verdict): the patch adds
    them back in the same splice that drops the rows one ulp outside."""
    scan_mask, verdicts = mesh.distributed_scan_mask, mesh._boundary_verdicts

    def missed(data, q):
        cand = zscan.boundary_candidates(data.host_xhi, data.host_yhi, q)
        _dev, exact = verdicts(data, q, cand)
        return cand[exact][::2]

    def low_mask(data, q):
        out = scan_mask(data, q)
        m = np.asarray(out).copy()
        m[missed(data, q)] = False
        return jax.device_put(m, out.sharding)

    def low_verdicts(data, q, cand):
        dev, exact = verdicts(data, q, cand)
        return dev & ~np.isin(cand, missed(data, q)), exact

    monkeypatch.setattr(mesh, "distributed_scan_mask", low_mask)
    monkeypatch.setattr(mesh, "_boundary_verdicts", low_verdicts)
    tier("mesh-dense")
    res, _lines, kids = _query(one_segment, "edges")
    p = kids["mesh-patch"]
    assert p["added"] > 0 and p["removed"] > 0
    assert sorted(res.ids) == one_segment.reference("edges")
    assert res.n == kids["mesh-scan"]["hits"] + p["added"] - p["removed"]


@pytest.mark.parametrize("edits", [
    ([], []), ([], [0]), ([0], []), ([2], [4]), ([], [9, 11]), ([4], []),
    ([0, 2, 4], [0, 1, 4, 6, 9]),
], ids=["none", "add-first", "remove-first", "same-place", "add-last",
        "remove-last", "mixed"])
@pytest.mark.parametrize("off", [0, 1_000])
def test_splice_matches_set_arithmetic(edits, off):
    """(indices of ``rows`` to remove, rows to add)"""
    rows = np.array([2, 3, 5, 7, 8], dtype=np.int32)
    remove = rows[edits[0]].astype(np.int64)
    add = np.array(edits[1], dtype=np.int64)
    out = np.empty(len(rows) - len(remove) + len(add), dtype=np.int64)
    mesh._splice(rows, add, remove, off, out)
    want = np.union1d(np.setdiff1d(rows, remove), add) + off
    assert out.tolist() == want.tolist()


def test_dense_tier_reports_its_dispatch(one_segment, tier):
    tier("mesh-dense")
    before = runtime.snapshot()
    _res, _lines, kids = _query(one_segment, "edges")
    after = runtime.snapshot()
    n_padded = one_segment.ds._state("ais").segments[0].n_padded
    cls = f"mesh-dense/{n_padded}"
    n0 = before["dispatch"].get("scan", {}).get(cls, {}).get("count", 0)
    assert after["dispatch"]["scan"][cls]["count"] == n0 + 1
    assert (after["transfer"]["d2h_bytes"] - before["transfer"]["d2h_bytes"]
            >= kids["mesh-scan"]["d2h_bytes"] > 0)
