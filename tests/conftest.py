"""Test configuration: force an 8-virtual-device CPU platform BEFORE jax
is imported anywhere, so sharding/mesh tests exercise real multi-device
code paths without TPU hardware (SURVEY.md section 4 test strategy)."""

import os

import numpy as np
import pytest

# force CPU even on a machine with a TPU: tests must be deterministic and
# exercise an 8-device mesh. The config flag is set too, after import and
# before any backend initializes. (tests/test_tpu_compile.py compiles for a
# described TPU without attaching one.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running benchmarks excluded from tier-1 "
        "runs (-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection suites driving the chaos "
        "proxy / broker kills (select with -m chaos)")
    config.addinivalue_line(
        "markers", "repl: replication suites (WAL shipping, replica "
        "catch-up, failover; select with -m repl)")
    config.addinivalue_line(
        "markers", "integrity: storage fault-tolerance suites (disk "
        "fault injection, checkpoint digests, scrub/quarantine, fsync "
        "poisoning; select with -m integrity — the randomized "
        "crash-consistency loop is additionally marked slow)")
    config.addinivalue_line(
        "markers", "cluster: sharded scatter-gather suites (z-prefix "
        "partitioning, hedged legs, partial-results contract, "
        "federation, chaos failover; select with -m cluster)")
    config.addinivalue_line(
        "markers", "bench_smoke: miniature end-to-end runs of the "
        "bench.py perf configs (4: batched KNN, 5: contains join) at "
        "toy sizes — exactness wiring, not performance")
    config.addinivalue_line(
        "markers", "cache: materialized pushdown-cache suites "
        "(LSN-keyed invalidation, single-flight, ETag/304, hot-tile "
        "refresh; select with -m cache)")
    config.addinivalue_line(
        "markers", "streaming: streaming result-plane suites (Arrow "
        "delta batches, chunked wire endpoints, k-way stream merge, "
        "continuous queries; select with -m streaming)")
    config.addinivalue_line(
        "markers", "geofence: device-resident standing-filter suites "
        "(filter compiler, fused rows x filters kernel, publisher "
        "device path, /rest/cq surfaces; select with -m geofence)")
    config.addinivalue_line(
        "markers", "ingest: ingest-firehose suites (vectorized "
        "converter parity vs the scalar oracle, group-commit pipeline, "
        "admission control / 429 backpressure; select with -m ingest)")
    config.addinivalue_line(
        "markers", "obs: observability suites (trace spans and wire "
        "propagation, histogram quantiles, Prometheus exposition, "
        "unified query audit; select with -m obs)")
    config.addinivalue_line(
        "markers", "health: runtime health plane suites (SLO burn-rate "
        "engine + react loop, stall watchdog, continuous profiler, "
        "runtime telemetry, metrics cardinality guard; select with "
        "-m health)")
    config.addinivalue_line(
        "markers", "sql: distributed SQL suites (partial-aggregate "
        "pushdown, broadcast spatial joins, plan surface, partial "
        "contract over SQL legs; select with -m sql)")
    config.addinivalue_line(
        "markers", "qos: multi-tenant QoS suites (weighted fair-share "
        "admission, per-tenant retry/hedge budgets, in-flight caps, "
        "ingest row buckets, cache byte budgets, noisy-neighbor "
        "isolation; select with -m qos)")
    config.addinivalue_line(
        "markers", "reshard: elastic-topology suites (online z-shard "
        "split/migration, epoch fencing, kill-point crash loop, "
        "SLO-driven autoscaler; select with -m reshard — the "
        "randomized kill-point soak is additionally marked slow)")
    config.addinivalue_line(
        "markers", "views: materialized-view suites (fold-state "
        "bit-identity vs from-scratch re-execution under randomized "
        "write/delete interleavings, MIN/MAX retraction reservoir, "
        "checkpoint restore, exactly-once delta subscribers; select "
        "with -m views)")
    config.addinivalue_line(
        "markers", "evolve: online reindex / schema-evolution suites "
        "(shadow builds with WAL-tail catch-up, atomic flip, "
        "kill-point crash+resume sweep, mid-drop write conflicts, "
        "REST/CLI surfaces; select with -m evolve — the randomized "
        "kill-point soak is additionally marked slow)")


@pytest.fixture(scope="session")
def point_store():
    """Factory: a store of type ``pts`` (``dtg:Date,*geom:Point``) over
    ``x, y, t`` with ids ``p<row>``. With ``appended`` the rows come in
    three writes, each followed by a whole-world query that builds the
    device columns: the second write outgrows them and they are rebuilt
    with power-of-two headroom, the third is appended into that headroom
    in place (``zscan.extend_scan_data``). Later queries then run over
    capacity-padded columns."""
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    def make(x, y, t, appended=False):
        n = len(x)
        ids = np.array([f"p{i}" for i in range(n)], dtype=object)
        ds = InMemoryDataStore()
        ds.create_schema(parse_spec("pts", "dtg:Date,*geom:Point:srid=4326"))
        cuts = [0, n // 2, n * 7 // 8, n] if appended else [0, n]
        for a, b in zip(cuts, cuts[1:]):
            ds.write_dict("pts", ids[a:b],
                          {"dtg": t[a:b], "geom": (x[a:b], y[a:b])})
            if appended:
                ds.query("BBOX(geom, -180, -90, 180, 90)", "pts")
        return ds
    return make
