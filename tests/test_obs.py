"""Observability plane: Dapper-style tracing (in-process + wire
propagation, slow-query always-capture, fan-in graft), fixed-bucket
histogram timers, Prometheus text exposition, and the unified
query-audit hook (enrichment, delegation suppression, principal)."""

import json
import re
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.audit import (AuditLogger, audit_query, delegated_scope,
                               global_audit, principal_scope)
from geomesa_tpu.audit.hook import AUDIT_PATH, _reset_global
from geomesa_tpu.features import FeatureBatch, parse_spec
from geomesa_tpu.index.api import Query
from geomesa_tpu.metrics import (MetricsRegistry, labeled_key,
                                 prometheus_text, split_key)
from geomesa_tpu.obs import TRACE_HEADER, tracer
from geomesa_tpu.obs.trace import (TRACE_MAX_SPANS, TRACE_PATH,
                                   TRACE_SAMPLE, TRACE_SLOW_MS)
from geomesa_tpu.scan.registry import batcher_registry
from geomesa_tpu.store import InMemoryDataStore

pytestmark = pytest.mark.obs

SPEC = "*geom:Point:srid=4326,dtg:Date,name:String"


def seeded_store(n=200, name="pts", audit=None, cls=InMemoryDataStore):
    rng = np.random.default_rng(11)
    sft = parse_spec(name, SPEC)
    ds = cls(audit=audit)
    ds.create_schema(sft)
    ds.write(name, FeatureBatch.from_dict(
        sft, np.array([f"f{i}" for i in range(n)], dtype=object),
        {"geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)),
         "dtg": rng.integers(0, 10**12, n).astype(np.int64),
         "name": np.array([f"n{i % 5}" for i in range(n)],
                          dtype=object)}))
    return ds


@pytest.fixture
def sampled():
    """Head-sampling on, ring cleared; everything restored after."""
    TRACE_SAMPLE.set("1.0")
    tracer.clear()
    try:
        yield tracer
    finally:
        TRACE_SAMPLE.set(None)
        TRACE_SLOW_MS.set(None)
        tracer.clear()


@pytest.fixture
def untraced():
    """Tracing fully off (sampling AND slow-capture)."""
    TRACE_SAMPLE.set("0")
    TRACE_SLOW_MS.set("0")
    tracer.clear()
    try:
        yield tracer
    finally:
        TRACE_SAMPLE.set(None)
        TRACE_SLOW_MS.set(None)
        tracer.clear()


# -- Prometheus text-format validator (exposition format 0.0.4) -----------

_PROM_TYPE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
    r"(counter|gauge|summary|histogram|untyped)$")
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\\\|\\"|\\n|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\\\|\\"|\\n|[^"\\])*")*\})?'
    r" [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN)$")


def assert_prometheus_parses(text: str):
    assert text.endswith("\n") or text == ""
    for ln in text.splitlines():
        if not ln:
            continue
        assert _PROM_TYPE.match(ln) or _PROM_SAMPLE.match(ln), (
            f"unparseable exposition line: {ln!r}")


# -- histogram timers ------------------------------------------------------

class TestHistogramTimers:
    def test_quantiles_from_known_distribution(self):
        reg = MetricsRegistry()
        # 90 fast + 10 slow: p50 must sit near 1ms, p99 near 100ms
        for _ in range(90):
            reg.observe("op", 0.001)
        for _ in range(10):
            reg.observe("op", 0.100)
        t = reg.snapshot()["timers"]["op"]
        assert t["count"] == 100
        # log-bucket interpolation is ~±20% within a sqrt(2) bucket
        assert 0.5 <= t["p50_ms"] <= 1.6
        assert 50 <= t["p99_ms"] <= 110
        assert t["max_ms"] == pytest.approx(100, rel=0.01)
        assert t["mean_ms"] == pytest.approx(10.9, rel=0.05)

    def test_p99_clamped_to_observed_max(self):
        reg = MetricsRegistry()
        for _ in range(50):
            reg.observe("op", 0.010)
        t = reg.snapshot()["timers"]["op"]
        assert t["p99_ms"] <= t["max_ms"]

    def test_time_context_manager_records(self):
        reg = MetricsRegistry()
        with reg.time("slept"):
            time.sleep(0.01)
        t = reg.snapshot()["timers"]["slept"]
        assert t["count"] == 1
        assert t["p50_ms"] >= 5

    def test_empty_timer_is_zero(self):
        reg = MetricsRegistry()
        reg.observe("op", 0.001)
        snap = reg.snapshot()["timers"]["op"]
        assert snap["p95_ms"] > 0
        reg2 = MetricsRegistry()
        assert reg2.snapshot()["timers"] == {}


class TestMetricLabels:
    def test_labeled_key_roundtrip(self):
        key = labeled_key("web.requests", {"route": "query", "code": 200})
        assert key == 'web.requests{code="200",route="query"}'
        base, body = split_key(key)
        assert base == "web.requests"
        assert body == 'code="200",route="query"'

    def test_unlabeled_key_passthrough(self):
        assert labeled_key("plain", None) == "plain"
        assert split_key("plain") == ("plain", "")

    def test_labels_partition_counters(self):
        reg = MetricsRegistry()
        reg.counter("hits", labels={"type": "a"})
        reg.counter("hits", 2, labels={"type": "b"})
        c = reg.snapshot()["counters"]
        assert c['hits{type="a"}'] == 1
        assert c['hits{type="b"}'] == 2

    def test_label_value_escaping(self):
        key = labeled_key("m", {"f": 'say "hi"\nback\\slash'})
        base, body = split_key(key)
        assert base == "m"
        assert '\\"hi\\"' in body and "\\n" in body and "\\\\" in body


# -- Prometheus exposition -------------------------------------------------

class TestPrometheusExposition:
    def test_counters_gauges_timers_render_and_parse(self):
        reg = MetricsRegistry()
        reg.counter("web.requests", 3, labels={"route": "query"})
        reg.gauge("cache.bytes", 1024)
        for _ in range(10):
            reg.observe("scan.latency", 0.002)
        text = reg.prometheus_text()
        assert_prometheus_parses(text)
        assert '# TYPE geomesa_web_requests_total counter' in text
        assert 'geomesa_web_requests_total{route="query"} 3.0' in text
        assert "geomesa_cache_bytes 1024.0" in text
        assert '# TYPE geomesa_scan_latency_seconds summary' in text
        assert 'quantile="0.99"' in text
        assert "geomesa_scan_latency_seconds_count 10.0" in text

    def test_type_emitted_once_per_family(self):
        reg = MetricsRegistry()
        reg.counter("hits", labels={"t": "a"})
        reg.counter("hits", labels={"t": "b"})
        text = reg.prometheus_text()
        assert text.count("# TYPE geomesa_hits_total counter") == 1

    def test_module_fn_accepts_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("c")
        assert "geomesa_c_total" in prometheus_text(reg.snapshot())


class TestNonFiniteGauges:
    """Satellite: inf/nan gauges must not corrupt JSON or Prometheus."""

    def test_snapshot_maps_nonfinite_to_null(self):
        reg = MetricsRegistry()
        reg.gauge("ewma.cold", float("inf"))
        reg.gauge("ewma.nan", float("nan"))
        reg.gauge("fine", 3.5)
        g = reg.snapshot()["gauges"]
        assert g["ewma.cold"] is None
        assert g["ewma.nan"] is None
        assert g["fine"] == 3.5
        # the whole snapshot must be strict JSON (no bare Infinity/NaN)
        encoded = json.dumps(reg.snapshot(), allow_nan=False)
        assert "Infinity" not in encoded

    def test_prometheus_drops_nonfinite(self):
        reg = MetricsRegistry()
        reg.gauge("ewma.cold", float("inf"))
        reg.gauge("fine", 1.0)
        text = reg.prometheus_text()
        assert_prometheus_parses(text)
        assert "ewma_cold" not in text
        assert "geomesa_fine 1.0" in text

    def test_delimited_reporter_skips_nonfinite(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("ewma.cold", float("nan"))
        reg.gauge("fine", 2.0)
        reg.counter("c", 4)
        out = tmp_path / "metrics.tsv"
        reg.report_delimited(str(out))
        content = out.read_text()
        assert "fine" in content and "nan" not in content.lower()


# -- audit logger (satellite: thread-safety) -------------------------------

class TestAuditLoggerConcurrency:
    def test_concurrent_writers_whole_lines(self, tmp_path):
        path = tmp_path / "audit.jsonl"
        log = AuditLogger(path=str(path))
        n_threads, per = 8, 200
        barrier = threading.Barrier(n_threads)

        def worker(t):
            barrier.wait()
            for i in range(per):
                log.record(f"type{t}", "INCLUDE", {}, 0.1, 0.2, i,
                           user=f"u{t}")

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        events = log.query()
        assert len(events) == n_threads * per
        # every persisted line decodes on its own: no torn/interleaved
        # writes under contention
        lines = path.read_text().splitlines()
        assert len(lines) == n_threads * per
        for ln in lines:
            e = json.loads(ln)
            assert e["type_name"].startswith("type")

    def test_ring_capacity_bounded(self):
        log = AuditLogger(capacity=10)
        for i in range(25):
            log.record("t", "INCLUDE", {}, 0, 0, i)
        events = log.query()
        assert len(events) == 10
        assert events[-1].hits == 24

    def test_query_filters(self):
        log = AuditLogger()
        log.record("a", "INCLUDE", {}, 0, 0, 1)
        log.record("b", "INCLUDE", {}, 0, 0, 2)
        assert [e.type_name for e in log.query("a")] == ["a"]


# -- unified audit hook ----------------------------------------------------

class TestAuditHook:
    def test_enriched_event_fields(self, sampled):
        log = AuditLogger()
        with tracer.span("web", "t", root=True):
            ok = audit_query(log, "memory", "pts", "INCLUDE", {}, 1.0,
                             2.0, 42, index="z2", rows_scanned=100)
        assert ok is True
        (e,) = log.query()
        assert e.surface == "memory"
        assert e.index == "z2"
        assert e.rows_scanned == 100 and e.hits == 42
        assert e.trace_id is not None
        assert tracer.get(e.trace_id) is not None

    def test_delegated_scope_suppresses(self):
        log = AuditLogger()
        with delegated_scope():
            ok = audit_query(log, "memory", "pts", "INCLUDE", {}, 0, 0, 1)
        assert ok is False
        assert log.query() == []

    def test_principal_enrichment(self):
        log = AuditLogger()
        with principal_scope("bearer:abc123"):
            audit_query(log, "memory", "pts", "INCLUDE", {}, 0, 0, 1)
        (e,) = log.query()
        assert e.user == "bearer:abc123"

    def test_flags_flow_from_trace_state(self, sampled):
        from geomesa_tpu.obs import set_flag
        log = AuditLogger()
        with tracer.span("web", "t", root=True):
            set_flag("cache_hit")
            set_flag("hedged")
            audit_query(log, "memory", "pts", "INCLUDE", {}, 0, 0, 1)
        (e,) = log.query()
        assert e.cache_hit is True and e.hedged is True

    def test_global_fallback_honors_audit_path(self, tmp_path):
        path = tmp_path / "global.jsonl"
        AUDIT_PATH.set(str(path))
        _reset_global()
        try:
            audit_query(None, "remote", "pts", "INCLUDE", {}, 0, 0, 3)
            assert len(global_audit().query()) == 1
            e = json.loads(path.read_text().splitlines()[0])
            assert e["surface"] == "remote" and e["hits"] == 3
        finally:
            AUDIT_PATH.set(None)
            _reset_global()

    def test_store_query_audits_once_with_scan_detail(self, untraced):
        log = AuditLogger()
        ds = seeded_store(audit=log)
        res = ds.query(Query("pts", "BBOX(geom, -50, -40, 50, 40)"))
        events = log.query()
        assert len(events) == 1
        e = events[0]
        assert e.surface == "memory"
        assert e.hits == res.n
        assert e.rows_scanned == 200
        assert e.index is not None
        assert e.trace_id is None  # tracing off never blocks auditing

    def test_batched_flag_comes_from_the_call(self, sampled):
        """A fused batch audits each query batched=True; a trace flag
        named ``batched`` (nothing sets one) does not make a scalar query
        batched."""
        from geomesa_tpu.obs import set_flag
        log = AuditLogger()
        ds = seeded_store(audit=log)
        qs = [Query("pts", "BBOX(geom, -60, -50, 0, 0)"),
              Query("pts", "BBOX(geom, 0, 0, 60, 50)")]
        ds.query_batched(qs)
        with tracer.span("web", "t", root=True):
            set_flag("batched")
            ds.query(qs[0])
        assert [e.batched for e in log.query()] == [True, True, False]


# -- trace core ------------------------------------------------------------

class TestTraceCore:
    def test_span_tree_parenting(self, sampled):
        with tracer.span("web", "GET /x", root=True) as w:
            with tracer.span("store-scan", "pts") as s:
                s.set_attr(rows=10)
        spans = tracer.get(w.trace_id)
        by_kind = {d["kind"]: d for d in spans}
        assert by_kind["store-scan"]["parent_id"] == by_kind["web"]["span_id"]
        assert by_kind["web"]["parent_id"] is None
        assert by_kind["store-scan"]["attrs"]["rows"] == 10

    def test_child_without_context_noops(self, sampled):
        sp = tracer.span("store-scan", "orphan")
        assert sp.span_id is None
        with sp:
            pass
        assert tracer.traces() == []

    def test_disabled_means_null_spans(self, untraced):
        sp = tracer.span("web", "x", root=True)
        assert sp.span_id is None
        with sp:
            pass
        assert tracer.traces() == []

    def test_sampling_probability_zero_drops(self):
        TRACE_SAMPLE.set("0")
        TRACE_SLOW_MS.set("60000")  # enabled, but nothing is that slow
        tracer.clear()
        try:
            with tracer.span("web", "fast", root=True):
                pass
            assert tracer.traces() == []
        finally:
            TRACE_SAMPLE.set(None)
            TRACE_SLOW_MS.set(None)

    def test_slow_capture_without_sampling(self):
        TRACE_SAMPLE.set("0")
        TRACE_SLOW_MS.set("10")
        tracer.clear()
        try:
            with tracer.span("web", "slow", root=True) as w:
                time.sleep(0.03)
            spans = tracer.get(w.trace_id)
            assert spans is not None and spans[0]["duration_ms"] >= 10
        finally:
            TRACE_SAMPLE.set(None)
            TRACE_SLOW_MS.set(None)
            tracer.clear()

    def test_annotations_and_error(self, sampled):
        try:
            with tracer.span("web", "boom", root=True) as w:
                w.annotate("checkpoint", step=1)
                raise ValueError("kaput")
        except ValueError:
            pass
        spans = tracer.get(w.trace_id)
        assert spans[0]["annotations"][0]["text"] == "checkpoint"
        assert "kaput" in spans[0]["error"]
        assert tracer.traces()[0]["error"] is True

    def test_ring_evicts_oldest_whole_traces(self, sampled):
        TRACE_MAX_SPANS.set("10")
        try:
            tids = []
            for i in range(20):
                with tracer.span("web", f"t{i}", root=True) as w:
                    pass
                tids.append(w.trace_id)
            summaries = tracer.traces(limit=100)
            assert sum(s["spans"] for s in summaries) <= 10
            kept = {s["trace_id"] for s in summaries}
            # newest survive, oldest evicted
            assert tids[-1] in kept and tids[0] not in kept
        finally:
            TRACE_MAX_SPANS.set(None)

    def test_inject_extract_roundtrip(self, sampled):
        with tracer.span("web", "x", root=True) as w:
            hdr = tracer.inject()
        tid, span_id, sampled_flag = tracer.extract(hdr)
        assert tid == w.trace_id and span_id == w.span_id
        assert sampled_flag is True
        assert tracer.extract(None) is None
        assert tracer.extract("garbage") is None

    def test_wire_continuation_joins_trace(self, sampled):
        with tracer.span("remote", "client-leg", root=True) as c:
            hdr = tracer.inject()

        def server_side():
            with tracer.span("web", "srv", root=True, remote=hdr) as s:
                assert s.trace_id == c.trace_id
        t = threading.Thread(target=server_side)
        t.start()
        t.join(10.0)
        spans = tracer.get(c.trace_id)
        kinds = {d["kind"] for d in spans}
        assert kinds == {"remote", "web"}  # both halves merged

    def test_wire_sampled_flag_keeps_downstream(self, untraced):
        # local sampling off, but the upstream decision rides the flag
        hdr = "aaaa0000bbbb1111:cccc2222dddd3333:1"
        with tracer.span("web", "srv", root=True, remote=hdr) as s:
            pass
        assert tracer.get("aaaa0000bbbb1111") is not None
        assert s.parent_id == "cccc2222dddd3333"

    def test_jsonl_export(self, sampled, tmp_path):
        out = tmp_path / "spans.jsonl"
        TRACE_PATH.set(str(out))
        try:
            with tracer.span("web", "exported", root=True):
                pass
            lines = out.read_text().splitlines()
            assert len(lines) == 1
            assert json.loads(lines[0])["name"] == "exported"
        finally:
            TRACE_PATH.set(None)


# -- batcher fan-in: links + graft ----------------------------------------

class _GatedStore(InMemoryDataStore):
    """Holds a marked scalar query in flight so the next batcher leader
    load-gates into its linger window (test_batcher.py idiom)."""

    hold: "threading.Event | None" = None

    def query(self, q, *args, **kwargs):
        if self.hold is not None and getattr(q, "hints", {}).get("_gate"):
            assert self.hold.wait(10.0), "gated query never released"
        return super().query(q, *args, **kwargs)


def _wait(pred, timeout: float = 10.0):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise AssertionError("timed out waiting for batcher state")
        time.sleep(0.001)


class TestBatcherFanIn:
    def test_coalesced_followers_get_dispatch_subtree(self, sampled):
        from geomesa_tpu.scan.batcher import QueryBatcher
        ds = seeded_store(cls=_GatedStore)
        # static linger: an adaptive one sizes the wait from the gap
        # before the first follower, and a loaded host can then dispatch
        # it alone before the second arrives
        b = QueryBatcher(ds, max_batch=2, linger_us=5_000_000,
                         adaptive=False)
        # gate a sacrificial dispatch in flight: the leader only lingers
        # for followers under load, so this makes coalescing
        # deterministic instead of a thread race
        ds.hold = threading.Event()
        gate = Query("pts", "BBOX(geom, -179.5, -89.5, -179.0, -89.0)")
        gate.hints["_gate"] = True
        warm = threading.Thread(target=b.query, args=(gate,))
        warm.start()
        try:
            _wait(lambda: b._in_flight >= 1)
            qs = [Query("pts", "BBOX(geom, -60, -50, 0, 0)"),
                  Query("pts", "BBOX(geom, 0, 0, 60, 50)")]
            results = [None, None]
            threads = []
            for i, q in enumerate(qs):
                t = threading.Thread(
                    target=lambda i=i, q=q:
                    results.__setitem__(i, b.query(q)))
                t.start()
                threads.append(t)
                if i == 0:
                    _wait(lambda: len(getattr(
                        b._queues.get("pts"), "items", ())) >= 1)
            for t in threads:
                t.join(30.0)
                assert not t.is_alive()
            assert all(r is not None for r in results)
            # the gated warm trace is still open, so exactly the two
            # coalesced callers' traces are finalized
            summaries = tracer.traces()
            assert len(summaries) == 2  # one trace per caller
            dispatch_ids = set()
            for s in summaries:
                assert {"batcher-wait", "dispatch",
                        "store-scan"} <= set(s["kinds"])
                spans = tracer.get(s["trace_id"])
                by_kind = {d["kind"]: d for d in spans}
                assert by_kind["dispatch"]["attrs"]["occupancy"] == 2
                # the recorded link resolves to the grafted dispatch copy
                wait_links = by_kind["batcher-wait"]["links"]
                assert any(
                    ln["span_id"] == by_kind["dispatch"]["span_id"]
                    for ln in wait_links)
                dispatch_ids.add(by_kind["dispatch"]["span_id"])
            # one fused dispatch: both traces hold the SAME dispatch span
            assert len(dispatch_ids) == 1
        finally:
            ds.hold.set()
            warm.join(10.0)
            ds.hold = None


# -- web tier end-to-end ---------------------------------------------------

class TestWebTracing:
    @pytest.fixture
    def server(self):
        from geomesa_tpu.web import GeoMesaWebServer
        batcher_registry.clear()
        log = AuditLogger()
        srv = GeoMesaWebServer(seeded_store(audit=log)).start()
        try:
            yield srv, log
        finally:
            srv.stop()
            batcher_registry.clear()

    def test_remote_query_builds_full_trace(self, sampled, server):
        from geomesa_tpu.store import RemoteDataStore
        srv, log = server
        client = RemoteDataStore("127.0.0.1", srv.port, hedge=False)
        with tracer.span("client", "e2e", root=True) as root:
            res = client.query(Query("pts", "BBOX(geom, -90, -60, 90, 60)"))
        spans = tracer.get(root.trace_id)
        kinds = {d["kind"] for d in spans}
        # client leg + server's web/batcher/dispatch/store tree, one id
        assert {"client", "remote", "web", "batcher-wait", "dispatch",
                "store-scan"} <= kinds
        assert all(d["trace_id"] == root.trace_id for d in spans)
        # the store's audit event resolves into the same trace
        (e,) = log.query()
        assert e.trace_id == root.trace_id
        assert e.hits == res.n

    def test_rest_trace_list_and_get(self, sampled, server):
        srv, _ = server
        out = srv.handle("GET", "/rest/query/pts",
                         {"cql": ["BBOX(geom, -10, -10, 10, 10)"]}, None)
        assert out[0] == 200
        out = srv.handle("GET", "/rest/trace", {}, None)
        assert out[0] == 200
        summaries = json.loads(out[2])
        assert summaries and "trace_id" in summaries[0]
        tid = summaries[0]["trace_id"]
        out = srv.handle("GET", f"/rest/trace/{tid}", {}, None)
        assert out[0] == 200
        full = json.loads(out[2])
        assert full["trace_id"] == tid
        assert {"kind", "span_id", "duration_ms"} <= set(full["spans"][0])

    def test_rest_trace_unknown_404(self, sampled, server):
        srv, _ = server
        out = srv.handle("GET", "/rest/trace/deadbeef", {}, None)
        assert out[0] == 404

    def test_rest_metrics_prometheus_parses(self, server):
        srv, _ = server
        srv.handle("GET", "/rest/query/pts", {"cql": ["INCLUDE"]}, None)
        status, ctype, body = srv.handle(
            "GET", "/rest/metrics", {"format": ["prometheus"]}, None)[:3]
        assert status == 200
        assert ctype.startswith("text/plain")
        assert_prometheus_parses(body)
        # default stays JSON
        status, ctype, body = srv.handle("GET", "/rest/metrics",
                                         {}, None)[:3]
        assert ctype == "application/json"
        json.loads(body)

    def test_bearer_principal_lands_in_audit(self, untraced, server):
        srv, log = server
        out = srv.handle("GET", "/rest/query/pts", {"cql": ["INCLUDE"]},
                         None, {"Authorization": "Bearer s3cret"})
        assert out[0] == 200
        e = log.query()[-1]
        assert e.user.startswith("bearer:")
        assert "s3cret" not in e.user  # digest, never the raw token

    def test_trace_header_continues_wire_trace(self, sampled, server):
        srv, _ = server
        hdr = "feedface00000001:cafe000000000002:1"
        out = srv.handle("GET", "/rest/query/pts", {"cql": ["INCLUDE"]},
                         None, {TRACE_HEADER: hdr})
        assert out[0] == 200
        spans = tracer.get("feedface00000001")
        assert spans is not None
        web = [d for d in spans if d["kind"] == "web"]
        assert web[0]["parent_id"] == "cafe000000000002"


# -- federation: one trace across cluster:// legs (satellite) --------------

class TestFederationTracing:
    @pytest.fixture
    def federation(self):
        from geomesa_tpu.cluster import ClusterDataStore
        from geomesa_tpu.resilience.hedge import HEDGE_MIN_DELAY_MS
        from geomesa_tpu.web import GeoMesaWebServer
        batcher_registry.clear()
        _reset_global()
        # floor the hedge delay above any leg duration: a speculative
        # duplicate would add a third shard-store audit event and a
        # second web span nondeterministically
        HEDGE_MIN_DELAY_MS.set("60000")
        sft = parse_spec("pts", SPEC)
        backends = [InMemoryDataStore(), InMemoryDataStore()]
        servers = [GeoMesaWebServer(b).start() for b in backends]
        cluster = None
        try:
            uri = "cluster://" + ",".join(
                f"127.0.0.1:{s.port}" for s in servers)
            cluster = ClusterDataStore.from_uri(uri, leg_deadline_s=30,
                                                hedge_ms=60_000)
            cluster.create_schema(sft)
            rng = np.random.default_rng(3)
            n = 120
            cluster.write("pts", FeatureBatch.from_dict(
                sft, np.array([f"f{i}" for i in range(n)], dtype=object),
                {"geom": (rng.uniform(-170, 170, n),
                          rng.uniform(-80, 80, n)),
                 "dtg": rng.integers(0, 10**12, n).astype(np.int64),
                 "name": np.array(["x"] * n, dtype=object)}))
            yield cluster, servers
        finally:
            if cluster is not None:
                cluster.close()
            for s in servers:
                s.stop()
            HEDGE_MIN_DELAY_MS.set(None)
            batcher_registry.clear()
            _reset_global()

    def test_one_trace_spans_coordinator_and_shards(self, sampled,
                                                    federation):
        cluster, servers = federation
        tracer.clear()
        ev0 = len(global_audit().query())
        with tracer.span("client", "fed-query", root=True) as root:
            res = cluster.query("INCLUDE", "pts")
        assert res.n == 120
        spans = tracer.get(root.trace_id)
        assert spans is not None
        kinds = {d["kind"] for d in spans}
        # coordinator legs AND both shard servers' trees share the id
        assert {"client", "scatter-leg", "web",
                "store-scan"} <= kinds
        assert len([d for d in spans if d["kind"] == "scatter-leg"]) == 2
        assert len([d for d in spans if d["kind"] == "web"]) == 2
        assert all(d["trace_id"] == root.trace_id for d in spans)
        # audit: ONE cluster-surface event for the logical query; the
        # shard stores audit their own halves; the coordinator's inner
        # remote legs are suppressed by delegated_scope
        events = global_audit().query()[ev0:]
        by_surface = {}
        for e in events:
            by_surface.setdefault(e.surface, []).append(e)
        assert len(by_surface.get("cluster", [])) == 1
        assert len(by_surface.get("memory", [])) == 2
        assert "remote" not in by_surface
        assert by_surface["cluster"][0].trace_id == root.trace_id

    def test_sampling_off_drops_spans_never_audit(self, untraced,
                                                  federation):
        cluster, _ = federation
        ev0 = len(global_audit().query())
        res = cluster.query("BBOX(geom, -90, -60, 90, 60)", "pts")
        assert tracer.traces() == []
        events = global_audit().query()[ev0:]
        surfaces = [e.surface for e in events]
        assert surfaces.count("cluster") == 1
        assert surfaces.count("memory") == 2
        (ce,) = [e for e in events if e.surface == "cluster"]
        assert ce.trace_id is None
        assert ce.hits == res.n


# -- tools trace CLI -------------------------------------------------------

class TestTraceCli:
    def test_list_and_get(self, sampled, capsys):
        from geomesa_tpu.tools.cli import main
        from geomesa_tpu.web import GeoMesaWebServer
        batcher_registry.clear()
        srv = GeoMesaWebServer(seeded_store()).start()
        try:
            srv.handle("GET", "/rest/query/pts", {"cql": ["INCLUDE"]},
                       None)
            rc = main(["trace", "list",
                       "--path", f"remote://127.0.0.1:{srv.port}"])
            assert rc == 0
            summaries = json.loads(capsys.readouterr().out)
            assert summaries
            tid = summaries[0]["trace_id"]
            rc = main(["trace", "get", "--id", tid,
                       "--path", f"remote://127.0.0.1:{srv.port}"])
            assert rc == 0
            full = json.loads(capsys.readouterr().out)
            assert full["trace_id"] == tid
            rc = main(["trace", "get", "--id", "nope",
                       "--path", f"remote://127.0.0.1:{srv.port}"])
            assert rc == 2
        finally:
            srv.stop()
            batcher_registry.clear()

    def test_requires_remote_path(self, capsys):
        from geomesa_tpu.tools.cli import main
        rc = main(["trace", "list", "--path", "/tmp/not-remote"])
        assert rc == 2
