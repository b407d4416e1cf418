"""Per-step spans of a store query: every step of the scalar path is a
child of ``store-scan`` with its counters as attrs, the deferred id gather
is a linked trace of its own, each live span is a ``geomesa.<kind>`` event
in a profiler capture, nested as the spans are, and the scan tiers report
to the runtime collector."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomesa_tpu.features import FeatureBatch, parse_spec
from geomesa_tpu.index.api import Query
from geomesa_tpu.index.zkeys import SCAN_BLOCK_THRESHOLD
from geomesa_tpu.obs import runtime, trace, tracer
from geomesa_tpu.obs.trace import TRACE_SAMPLE, TRACE_SLOW_MS
from geomesa_tpu.scan.batcher import QueryBatcher
from geomesa_tpu.store import InMemoryDataStore
from geomesa_tpu.store.memory import HOST_SCAN_ROWS

pytestmark = pytest.mark.obs

SPEC = "*geom:Point:srid=4326,dtg:Date,v:Integer,name:String"
DAY = 86_400_000
WINDOW = "dtg DURING 1970-01-01T00:00:00Z/1970-02-01T00:00:00Z"


def _store(n):
    rng = np.random.default_rng(7)
    sft = parse_spec("pts", SPEC)
    ds = InMemoryDataStore()
    ds.create_schema(sft)
    ds.write("pts", FeatureBatch.from_dict(
        sft, np.array([f"f{i}" for i in range(n)], dtype=object),
        {"geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)),
         "dtg": rng.integers(0, 60 * DAY, n).astype(np.int64),
         "v": rng.integers(0, 100, n),
         "name": np.array([f"n{i % 5}" for i in range(n)], dtype=object)}))
    return ds


@pytest.fixture(scope="module")
def ds():
    return _store(20_000)


@pytest.fixture
def sampled():
    TRACE_SAMPLE.set("1")
    tracer.clear()
    try:
        yield tracer
    finally:
        for p in (TRACE_SAMPLE, TRACE_SLOW_MS, HOST_SCAN_ROWS,
                  SCAN_BLOCK_THRESHOLD):
            p.set(None)
        tracer.clear()


def _traced(ds, ecql):
    """One query under a root span: (result, the trace's span dicts)."""
    with tracer.span("batcher-wait", "pts", root=True) as root:
        res = ds.query(Query("pts", ecql))
    return res, tracer.get(root.trace_id)


def _children(spans, kind="store-scan"):
    (parent,) = [s for s in spans if s["kind"] == kind]
    return {s["kind"]: s for s in spans
            if s["parent_id"] == parent["span_id"]}


# host cap, block threshold, box -> tier
TIERS = {
    "host": ("100000", "0.9", "BBOX(geom, -5, -5, 5, 5)"),
    "gathered": ("10", "0.9", "BBOX(geom, -100, -60, 100, 60)"),
    "dense": ("10", "0.0001", "BBOX(geom, -100, -60, 100, 60)"),
}
KERNEL = {"host": None, "gathered": "gather-scan", "dense": "dense-scan"}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_each_tier_yields_its_steps(ds, sampled, tier):
    host, thr, box = TIERS[tier]
    HOST_SCAN_ROWS.set(host)
    SCAN_BLOCK_THRESHOLD.set(thr)
    res, spans = _traced(ds, f"{box} AND {WINDOW}")
    kids = _children(spans)
    want = {"plan", "index-search", "assemble"}
    if tier != "host":
        want |= {KERNEL[tier], "boundary-patch"}
    assert set(kids) == want
    search = kids["index-search"]["attrs"]
    assert search["outcome"] == {"host": "exact", "gathered": "candidates"
                                 }.get(tier, "dense")
    if tier == "host":
        assert search["rows"] == res.n
    assert kids["assemble"]["attrs"] == {"ids_eager": True}
    if tier == "gathered":
        g = kids["gather-scan"]["attrs"]
        assert g["candidates"] == search["rows"] > 0
        # the dense pass scans every row; its mask comes down, a byte a row
        assert g["padded"] >= 20_000 > g["candidates"]
        assert g["h2d_bytes"] == 0 and g["d2h_bytes"] == g["padded"]
    if tier == "dense":
        d = kids["dense-scan"]["attrs"]
        assert d["rows"] == 20_000 and d["d2h_bytes"] >= 20_000
    if tier != "host":
        patch = kids["boundary-patch"]["attrs"]
        assert set(patch) == {"checked"} and patch["checked"] >= 0


@pytest.mark.parametrize("tier", ["gathered", "dense"])
def test_kernel_tiers_report_dispatches(ds, sampled, tier):
    host, thr, box = TIERS[tier]
    HOST_SCAN_ROWS.set(host)
    SCAN_BLOCK_THRESHOLD.set(thr)
    before = runtime.snapshot()
    _res, spans = _traced(ds, f"{box} AND {WINDOW}")
    after = runtime.snapshot()
    attrs = _children(spans)[KERNEL[tier]]["attrs"]
    k = attrs.get("padded", attrs["d2h_bytes"])
    cls = f"{tier}/{k}"
    n0 = before["dispatch"].get("scan", {}).get(cls, {}).get("count", 0)
    assert after["dispatch"]["scan"][cls]["count"] == n0 + 1
    for way in ("h2d_bytes", "d2h_bytes"):
        assert (after["transfer"][way] - before["transfer"][way]
                >= attrs.get(way, 0))


@pytest.mark.parametrize("where, box", [
    ("host", "BBOX(geom, -20, -20, 20, 20)"),
    ("device", "BBOX(geom, -170, -80, 170, 80)"),
])
def test_residual_reports_where_it_ran(ds, sampled, where, box):
    HOST_SCAN_ROWS.set("100000000")
    res, spans = _traced(ds, f"{box} AND {WINDOW} AND v < 30")
    r = _children(spans)["residual"]["attrs"]
    assert r["where"] == where
    assert r["rows"] >= res.n > 0
    assert r["columns"] == (4 if where == "host" else 0)


def test_large_result_links_its_id_gather(sampled):
    big = _store(120_000)
    res, spans = _traced(big, "INCLUDE")
    assert res.n == 120_000
    assert _children(spans)["assemble"]["attrs"] == {"ids_eager": False}
    scan = next(s for s in spans if s["kind"] == "store-scan")
    assert not any(t["root_kind"] == "result-ids" for t in tracer.traces())
    ids = res.ids
    assert len(ids) == 120_000
    (t,) = [t for t in tracer.traces() if t["root_kind"] == "result-ids"]
    (gather,) = tracer.get(t["trace_id"])
    assert gather["parent_id"] is None
    assert gather["attrs"] == {"ids": 120_000}
    assert gather["links"] == [{"trace_id": scan["trace_id"],
                                "span_id": scan["span_id"]}]
    res.ids                             # read once: no second gather
    assert sum(t["root_kind"] == "result-ids"
               for t in tracer.traces()) == 1


def test_profiler_events_mirror_the_spans(ds, sampled, tmp_path):
    HOST_SCAN_ROWS.set("10")
    SCAN_BLOCK_THRESHOLD.set("0.9")
    ecql = f"BBOX(geom, -90, -50, 90, 50) AND {WINDOW} AND v < 50"
    _traced(ds, ecql)                    # compiled before the capture
    tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _res, spans = _traced(ds, ecql)
    finally:
        jax.profiler.stop_trace()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    events = [(e.name[len("geomesa."):], e.start_ns, e.start_ns
               + e.duration_ns)
              for p in jax.profiler.ProfileData.from_file(path).planes
              for ln in p.lines for e in ln.events
              if e.name.startswith("geomesa.")]
    assert sorted(k for k, *_ in events) == sorted(s["kind"] for s in spans)
    assert {s["kind"] for s in spans} == {
        "batcher-wait", "store-scan", "plan", "index-search", "gather-scan",
        "boundary-patch", "residual", "assemble"}
    ev = {k: (s, e) for k, s, e in events}
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        if s["parent_id"] is None:
            continue
        s0, e0 = ev[s["kind"]]
        p0, p1 = ev[by_id[s["parent_id"]]["kind"]]
        assert p0 <= s0 and e0 <= p1, s["kind"]


def test_tracing_off_builds_no_span_and_no_annotation(ds, monkeypatch):
    TRACE_SAMPLE.set("0")
    TRACE_SLOW_MS.set("0")
    built, opened = [], []
    init = trace.Span.__init__
    monkeypatch.setattr(trace.Span, "__init__",
                        lambda self, *a, **kw: (built.append(a),
                                                init(self, *a, **kw))[1])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **kw: opened.append(a))
    try:
        b = QueryBatcher(ds, max_batch=2)
        res = b.query(Query("pts", f"BBOX(geom, -100, -60, 100, 60) "
                                   f"AND {WINDOW} AND v < 50"))
        assert res.n > 0 and len(res.ids) == res.n
    finally:
        TRACE_SAMPLE.set(None)
        TRACE_SLOW_MS.set(None)
    assert built == [] and opened == []


def test_compile_is_noted_on_the_current_span(sampled):
    before = runtime.snapshot()["backend_compile"]["count"]
    with tracer.span("web", "compile", root=True) as root:
        jax.jit(lambda x: x * 7 - 3)(jnp.arange(13)).block_until_ready()
    (span,) = tracer.get(root.trace_id)
    notes = [a for a in span.get("annotations", ())
             if a["text"] == "compile"]
    assert notes and all(a["seconds"] >= 0 for a in notes)
    assert runtime.snapshot()["backend_compile"]["count"] \
        >= before + len(notes)
