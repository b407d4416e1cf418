#!/usr/bin/env python
"""Benchmark: the five BASELINE.md configs + the 100M-point north star.

Primary metric (unchanged from round 1): config #2, the fused Z3-style
BBOX+time device scan at 10M points, against a single-threaded
vectorized-numpy CPU baseline standing in for geomesa-memory/CQEngine
(the JVM stack is unavailable here; vectorized numpy is a *stronger*
CPU baseline than CQEngine's per-object iterator evaluation).

Additional configs (BASELINE.md table):
  #1  store-level BBOX query, 1M GDELT-like points (CQEngine analog)
  #3  ST_DWithin radius join, 10M points x 1k query points
  #4  KNN, 50M points, k=100
  #5  ST_Contains, 100M points vs 10k polygons (z2-index pruned path)
  #6  concurrent BBOX micro-batching, 10M points: aggregate queries/sec
      at concurrency {1, 8, 32, 128}, sequential per-query dispatch vs
      the coalesced `query_batched` path (one fused vmapped scan per
      admission batch; scan/batcher.py), plus the single-query p50
      through the QueryBatcher passthrough vs direct `query()`
  #7  durable ingest (wal/ subsystem): chunked 1M-row ingest into an
      InMemoryDataStore with durable_dir= at each fsync policy
      (never / interval / always) vs the non-durable baseline, plus
      crash-recovery time for the resulting 1M-row log and the
      checkpoint-bounded reopen
  #8  faulty network (resilience/ subsystem): the same BBOX query
      stream through RemoteDataStore clean vs through a ChaosProxy
      (1% connection resets + 10ms jitter) — must be id-identical
      with zero client-visible errors; breaker fast-fail latency
      against a black-holed endpoint; broker kill->restart recovery
      time for a long-polling SocketBus consumer
  north star: p50 latency of a 100M-point BBOX+time query through the
  in-memory store (index-pruned gather scan), reported as p50_ms_100m.

Timing methodology for kernels: a single scan is shorter than one
dispatch, so kernels are chained REPS times inside ONE jitted fori_loop
with a data dependency; the chain ends in a host fetch of its scalar
result, and a 1-rep chain is subtracted so dispatch and fetch drop out:
per-scan = (t(REPS) - t(1))/(REPS - 1). Store-level configs are timed
as wall-clock query latency (p50 over repetitions) — they include
planning, host index search, device dispatch and result materialization.

main() refuses to run anywhere but a TPU and records the platform,
device kind and device count it ran on.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "features/sec/chip",
   "vs_baseline": N, "p50_ms_100m": N, "configs": {...}}

Env knobs: GEOMESA_TPU_BENCH_N (10M), GEOMESA_TPU_BENCH_REPS (512),
GEOMESA_TPU_BENCH_TRIALS (3), GEOMESA_TPU_BENCH_CONFIGS
("1,2,3,4,5,6,7,8,9,10,northstar" — comma list to run a subset; the
`--only` CLI flag does the same and also accepts full result names,
e.g. `--only 9_replicated_reads`),
GEOMESA_TPU_BENCH_WAL_ROWS (1M — config #7 ingest/recovery size),
GEOMESA_TPU_BENCH_CHAOS_QUERIES (300 — config #8 stream length),
GEOMESA_TPU_BENCH_REPL_QUERIES (400 — config #9 read stream length),
GEOMESA_TPU_BENCH_STREAM_N (1M — config #14 streamed scan size),
GEOMESA_TPU_BENCH_LOAD_MAX (1.5 — 1-minute load-average ceiling: runs
on a busier host are flagged `load_ok: false` in the JSON),
GEOMESA_TPU_BENCH_LOAD_WAIT_S (0 — if > 0, wait up to this long for
the load to fall below the ceiling before starting),
GEOMESA_TPU_BENCH_LOAD_STRICT (0 — if set, refuse to run (exit 2)
instead of warning when the host is loaded).

Configs #4/#5 honor the analytics knobs (same resolution order):
  geomesa.knn.batch    / GEOMESA_KNN_BATCH    (true) — web-tier KNN
      coalescing through the QueryBatcher; the bench calls the array
      path directly, so this only gates the /rest/knn route
  geomesa.join.prewarm / GEOMESA_JOIN_PREWARM (true) — compile the
      dwithin/contains/KNN kernel family at ingest (>= 5M rows) so the
      first join query pays a persistent-cache load, not a compile.

Config #6 also honors the batcher's own knobs (utils/properties
resolution: thread-local override -> env var -> default):
  geomesa.batch.max.size      / GEOMESA_BATCH_MAX_SIZE      (32) —
      max queries per fused dispatch; <= 1 disables coalescing
  geomesa.batch.linger.micros / GEOMESA_BATCH_LINGER_MICROS (2000) —
      how long an admission-queue leader waits for followers
  geomesa.batch.linger.adaptive / GEOMESA_BATCH_LINGER_ADAPTIVE (true)
      — EWMA-derived linger clamped to [0, linger_us]; idle schemas
      pay ~zero linger, saturated ones grow batches
Config #7 honors the WAL's knobs (same resolution order):
  geomesa.wal.fsync           / GEOMESA_WAL_FSYNC           (always) —
      group-commit policy: always | interval | never
  geomesa.wal.segment.bytes   / GEOMESA_WAL_SEGMENT_BYTES   (64MiB) —
      segment rotation threshold
  geomesa.wal.interval.ms     / GEOMESA_WAL_INTERVAL_MS     (50) —
      flush cadence for the interval policy
Config #8 exercises the resilience layer's knobs (same resolution):
  geomesa.retry.attempts      / GEOMESA_RETRY_ATTEMPTS      (5) —
      max attempts per retryable call (1 disables retries)
  geomesa.retry.base.ms       / GEOMESA_RETRY_BASE_MS       (50) —
      full-jitter backoff base; sleep ~ U(0, min(cap, base*2^k))
  geomesa.retry.cap.ms        / GEOMESA_RETRY_CAP_MS        (2000) —
      backoff ceiling per attempt
  geomesa.retry.deadline      / GEOMESA_RETRY_DEADLINE      (30s) —
      total wall-clock budget across one call's attempts
  geomesa.breaker.failures    / GEOMESA_BREAKER_FAILURES    (5) —
      consecutive failures before an endpoint's circuit opens
  geomesa.breaker.reset.ms    / GEOMESA_BREAKER_RESET_MS    (5000) —
      open -> half-open probe delay
  geomesa.web.max.inflight    / GEOMESA_WEB_MAX_INFLIGHT    (unset) —
      server load-shedding cap; excess requests get 503 + Retry-After
  geomesa.web.retry.after.s   / GEOMESA_WEB_RETRY_AFTER_S   (1) —
      the backpressure hint a shed response carries
Config #13 (tail-latency serving tier) exercises the hedging and
shared-batcher knobs (same resolution):
  geomesa.hedge.enabled        / GEOMESA_HEDGE_ENABLED       (true) —
      speculative second attempts on idempotent GETs, p99-delayed
  geomesa.hedge.min.delay.ms   / GEOMESA_HEDGE_MIN_DELAY_MS  (10) —
      floor under the EWMA-derived hedge delay
  geomesa.batch.latency.budget.ms / GEOMESA_BATCH_LATENCY_BUDGET_MS
      (unset) — derive the effective batch cap from the per-shape
      dispatch-cost EWMA; unset keeps the static cap
  geomesa.batcher.registry.enabled / GEOMESA_BATCHER_REGISTRY_ENABLED
      (true) — process-wide shared batcher per store identity
Config #9 exercises the replication layer's knobs (same resolution):
  geomesa.repl.max.lag.lsn    / GEOMESA_REPL_MAX_LAG_LSN    (1000) —
      per-query staleness bound in log records
  geomesa.repl.max.lag.s      / GEOMESA_REPL_MAX_LAG_S      (10) —
      per-query staleness bound in seconds since full catch-up
  geomesa.repl.ack.replicas   / GEOMESA_REPL_ACK_REPLICAS   (1) —
      replicas that must apply a write before it is acknowledged
  geomesa.repl.promote.auto   / GEOMESA_REPL_PROMOTE_AUTO   (true) —
      promote the most-caught-up replica when the primary probe fails
  geomesa.breaker.window      / GEOMESA_BREAKER_WINDOW      (unset) —
      sliding error-rate breaker window (calls); unset keeps the
      consecutive-failures trip condition
The web tier's write gate (not benched, documented for completeness):
  geomesa.web.auth.token      / GEOMESA_WEB_AUTH_TOKEN      (unset) —
      opt-in shared bearer token for POST /rest/write, POST
      /rest/delete, DELETE /rest/schemas, POST /rest/wal/* and the
      `wal truncate` CLI.
"""

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N = int(os.environ.get("GEOMESA_TPU_BENCH_N", 10_000_000))
REPS = max(int(os.environ.get("GEOMESA_TPU_BENCH_REPS", 512)), 2)
TRIALS = max(int(os.environ.get("GEOMESA_TPU_BENCH_TRIALS", 3)), 1)
CONFIGS = set(os.environ.get("GEOMESA_TPU_BENCH_CONFIGS",
                             "1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,17,18,"
                             "19,20,21,22,23,24,northstar")
              .split(","))
MS_DAY = 86_400_000
N_BIG = int(os.environ.get("GEOMESA_TPU_BENCH_NBIG", 100_000_000))
T0_DAY, T1_DAY = 17_000, 17_100


def _p50(samples):
    return float(np.median(np.asarray(samples)))


def _pcts(samples) -> dict:
    """p50/p95/p99 of one latency-sample list — every latency-emitting
    config reports the tail, not just the median (hot-tile serving is
    a p99 story: one cold recompute in 100 requests IS the number)."""
    a = np.asarray(samples, dtype=np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99))}


# host-contention gate: r5 numbers swung 2-3x when another process
# shared the machine, so the bench refuses to pretend a loaded host is
# a clean run. Above LOAD_MAX the driver either waits (LOAD_WAIT_S),
# aborts (LOAD_STRICT), or runs anyway with a loud warning — and the
# JSON always carries load_ok so a contended round is visible after
# the fact.
LOAD_MAX = float(os.environ.get("GEOMESA_TPU_BENCH_LOAD_MAX", 1.5))
LOAD_WAIT_S = float(os.environ.get("GEOMESA_TPU_BENCH_LOAD_WAIT_S", 0))
LOAD_STRICT = os.environ.get("GEOMESA_TPU_BENCH_LOAD_STRICT",
                             "0").lower() in ("1", "true", "yes")


def _load_1m() -> float:
    try:
        return float(os.getloadavg()[0])
    except (OSError, AttributeError):  # platform without getloadavg
        return 0.0


def _load_gate() -> float:
    """Check the 1-minute load average before timing anything; returns
    the observed load (after any waiting)."""
    load = _load_1m()
    if load <= LOAD_MAX:
        return load
    if LOAD_WAIT_S > 0:
        deadline = time.monotonic() + LOAD_WAIT_S
        while load > LOAD_MAX and time.monotonic() < deadline:
            print(f"bench: load_1m={load:.2f} > {LOAD_MAX} — waiting "
                  "for the competing process to finish", file=sys.stderr)
            time.sleep(min(15.0, max(deadline - time.monotonic(), 0.1)))
            load = _load_1m()
        if load <= LOAD_MAX:
            return load
    if LOAD_STRICT:
        print(f"bench: REFUSING to run: load_1m={load:.2f} > "
              f"{LOAD_MAX} (set GEOMESA_TPU_BENCH_LOAD_STRICT=0 to "
              "override)", file=sys.stderr)
        sys.exit(2)
    print("=" * 70, file=sys.stderr)
    print(f"bench: WARNING: load_1m={load:.2f} > {LOAD_MAX} — a "
          "competing process is running; timings below are NOT "
          "trustworthy (load_ok=false in the JSON)", file=sys.stderr)
    print("=" * 70, file=sys.stderr)
    return load


def big_points(rng, n=N_BIG):
    """The north-star point set (AIS-like: 40 clustered lanes + uniform
    noise), n rows (100M by default); chip_smoke.py builds its store
    from it too."""
    n_lane = n // 2
    lane = rng.integers(0, 40, n_lane)
    lx0 = rng.uniform(-170, 170, 40)
    ly0 = rng.uniform(-80, 80, 40)
    ang = rng.uniform(0, np.pi, 40)
    t = rng.uniform(-20, 20, n_lane)
    x = np.empty(n)
    y = np.empty(n)
    x[:n_lane] = np.clip(lx0[lane] + t * np.cos(ang[lane])
                         + rng.normal(0, 0.5, n_lane), -180, 180)
    y[:n_lane] = np.clip(ly0[lane] + t * np.sin(ang[lane])
                         + rng.normal(0, 0.5, n_lane), -90, 90)
    x[n_lane:] = rng.uniform(-180, 180, n - n_lane)
    y[n_lane:] = rng.uniform(-90, 90, n - n_lane)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n)
    return x, y, ms.astype(np.int64)


# -- config 2: fused kernel rate (primary metric) -------------------------

def bench_config2(jax, jnp, lax, zscan, x, y, ms):
    box = (-80.0, 30.0, -60.0, 45.0)
    t_lo, t_hi = 17_020 * MS_DAY, 17_050 * MS_DAY

    def cpu_pass():
        return ((x >= box[0]) & (x <= box[2])
                & (y >= box[1]) & (y <= box[3])
                & (ms >= t_lo) & (ms <= t_hi))

    cpu_s = _pinned_median(cpu_pass)
    base_mask = cpu_pass()
    cpu_rate = len(x) / cpu_s

    data = zscan.build_scan_data(x, y, ms)
    q = zscan.make_query([box], [(t_lo, t_hi - 1)])  # inclusive hi

    @functools.partial(jax.jit, static_argnames=("reps", "time_any"))
    def chained(xhi, xlo, yhi, ylo, tday, tms,
                boxes, bvalid, times, tvalid, reps, time_any):
        def body(i, acc):
            # tiny per-iteration bound perturbation (orders below any
            # coordinate ulp) defeats CSE across iterations
            b = boxes.at[0, 1].add(jnp.float32(i) * jnp.float32(1e-30))
            m = zscan._scan_mask(xhi, xlo, yhi, ylo, tday, tms,
                                 b, bvalid, times, tvalid, time_any)
            return acc + jnp.sum(m, dtype=jnp.int32)
        return lax.fori_loop(0, reps, body, jnp.int32(0))

    args = (data.xhi, data.xlo, data.yhi, data.ylo, data.tday, data.tms,
            q.boxes, q.box_valid, q.times, q.time_valid)
    int(chained(*args, REPS, q.time_any))  # compile + execute once

    # the host fetch of the scalar waits for the chain; the 1-rep
    # chain's time (dispatch + fetch) is subtracted below
    one = float("inf")
    for _ in range(TRIALS + 2):
        t0 = time.perf_counter()
        int(chained(*args, 1, q.time_any))
        one = min(one, time.perf_counter() - t0)
    best = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        int(chained(*args, REPS, q.time_any))
        best = min(best, time.perf_counter() - t0)
    per_scan = max(best - one, 1e-9) / (REPS - 1)
    rate = len(x) / per_scan

    # correctness: identical feature indices (boundary-exact contract)
    host_mask = np.asarray(zscan.scan_mask(data, q))[:data.n]
    cand = zscan.boundary_candidates(np.asarray(data.xhi)[:data.n],
                                     np.asarray(data.yhi)[:data.n], q)
    host_mask = zscan.exact_patch(host_mask, cand, x, y, ms, q)
    align = base_mask & (ms <= t_hi - 1)
    ok = np.array_equal(np.flatnonzero(host_mask), np.flatnonzero(align))
    del data
    return {
        "rate": round(rate, 1), "best_scan_ms": round(per_scan * 1e3, 3),
        "cpu_baseline_rate": round(cpu_rate, 1),
        "vs_baseline": round(rate / cpu_rate, 2), "n": len(x),
        "hits": int(host_mask.sum()), "ids_exact": bool(ok),
    }


# -- config 1: store-level BBOX query at 1M (CQEngine analog) -------------

def bench_config1(rng):
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    n = 1_000_000
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("gdelt", "dtg:Date,*geom:Point:srid=4326"))
    ids = np.arange(n).astype(str).astype(object)
    ds.write_dict("gdelt", ids, {"dtg": ms, "geom": (x, y)})
    ecql = "BBOX(geom, -80, 30, -60, 45)"
    ds.query(ecql, "gdelt")  # build index + compile
    times = []
    for _ in range(15):
        t0 = time.perf_counter()
        res = ds.query(ecql, "gdelt")
        times.append(time.perf_counter() - t0)
    def cpu_pass():
        bmask = (x >= -80) & (x <= -60) & (y >= 30) & (y <= 45)
        return np.flatnonzero(bmask)

    bp50 = _pinned_median(cpu_pass)
    bidx = cpu_pass()
    ok = np.array_equal(np.sort(res.ids.astype(int)), bidx)
    pc = _pcts(times)
    p50 = pc["p50"]
    return {"p50_ms": round(p50 * 1e3, 2),
            "p95_ms": round(pc["p95"] * 1e3, 2),
            "p99_ms": round(pc["p99"] * 1e3, 2),
            "cpu_p50_ms": round(bp50 * 1e3, 2),
            "vs_baseline": round(bp50 / p50, 2),
            "n": n, "hits": res.n, "ids_exact": bool(ok)}


# -- pinned CPU baselines --------------------------------------------------

def _pinned_median(fn, trials=5):
    """One warm-up + median of `trials` — CPU baselines must be
    comparable run to run (fixed seeds handle the data side)."""
    fn()
    return _p50([_timed(fn) for _ in range(trials)])


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# -- config 3: DWithin join 10M x 1k, through the SQL surface -------------

def bench_config3(rng, x, y):
    """`SELECT count(*) FROM pts JOIN q ON ST_DWithin(...)` through
    SqlEngine over the in-memory store — the product path BASELINE.md
    names (geomesa-spark-sql SQLSpatialFunctions), not a raw kernel
    call. The engine feeds the join the store's RESIDENT device
    columns, so the timed region is plan + device count-reduce + band
    resolution, with no 10M-point re-upload."""
    from geomesa_tpu.analytics.join import dwithin_join
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.sql import SqlEngine
    from geomesa_tpu.store import InMemoryDataStore

    n, k, r = len(x), 1_000, 0.25
    qx = rng.uniform(-170, 170, k)
    qy = rng.uniform(-80, 80, k)
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("pts", "*geom:Point:srid=4326"))
    ds.write_dict("pts", np.arange(n).astype(str).astype(object),
                  {"geom": (x, y)})
    ds.create_schema(parse_spec("qpts", "*geom:Point:srid=4326"))
    ds.write_dict("qpts", np.arange(k).astype(str).astype(object),
                  {"geom": (qx, qy)})
    eng = SqlEngine(ds)
    sql = ("SELECT count(*) AS n FROM pts a JOIN qpts b "
           f"ON ST_DWithin(a.geom, b.geom, {r})")
    t0 = time.perf_counter()
    eng.query(sql)  # index build + device residency + compile
    first_s = time.perf_counter() - t0
    times = []
    total = 0
    for _ in range(5):
        t0 = time.perf_counter()
        total = int(eng.query(sql).column("n")[0])
        times.append(time.perf_counter() - t0)
    dev_s = _p50(times)

    # kernel-only reference (public API, same residency terms): the
    # SQL number must stay within ~20% of this or the product path has
    # regressed
    import jax.numpy as jnp
    dev = (jnp.asarray(x.astype(np.float32)),
           jnp.asarray(y.astype(np.float32)))
    counts, _ = dwithin_join(x, y, qx, qy, r, counts_only=True,
                             device_xy=dev)
    t0 = time.perf_counter()
    counts, _ = dwithin_join(x, y, qx, qy, r, counts_only=True,
                             device_xy=dev)
    kernel_s = time.perf_counter() - t0

    # pinned baseline: vectorized numpy over a query subsample,
    # extrapolated; warm-up + median of 5
    kb = 20

    def cpu_pass():
        for i in range(kb):
            (((x - qx[i]) ** 2 + (y - qy[i]) ** 2) <= r * r).sum()

    cpu_s = _pinned_median(cpu_pass) * (k / kb)
    base_counts = np.array(
        [int((((x - qx[i]) ** 2 + (y - qy[i]) ** 2) <= r * r).sum())
         for i in range(kb)])
    ok = (np.array_equal(counts[:kb], base_counts)
          and total == int(counts.sum()))
    _pc = _pcts(times)
    return {"p50_s": round(dev_s, 3),
            "p95_s": round(_pc["p95"], 3),
            "p99_s": round(_pc["p99"], 3),
            "first_s": round(first_s, 2),
            "kernel_s": round(kernel_s, 3),
            "pairs_per_s": round(n * k / dev_s, 1),
            "cpu_elapsed_s_extrapolated": round(cpu_s, 3),
            "vs_baseline": round(cpu_s / dev_s, 2),
            "n": n, "queries": k, "total_matches": total,
            "counts_exact": bool(ok)}


# -- config 4: KNN at 50M, k=100, through the process surface -------------

def bench_config4(rng, x, y):
    """KNNearestNeighborSearchProcess over a 50M-row store, BATCHED:
    all 8 query points ride ONE fused multi-query top-k dispatch
    (analytics/join.knn_batched via the knn_process array path) against
    the resident device columns — the batch pays one kernel launch and
    one host fetch instead of 8. p50_ms stays per-query (batch / nq);
    ids verify exact for EVERY query against an id-stable numpy
    oracle."""
    from geomesa_tpu.analytics.processes import knn_process
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    n, k, nq = min(50_000_000, len(x)), 100, 8
    x, y = x[:n], y[:n]
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("pts50", "*geom:Point:srid=4326"))
    ds.write_dict("pts50", np.arange(n).astype(str).astype(object),
                  {"geom": (x, y)})
    qs = [(10.0, 10.0), (-120.0, 40.0), (0.0, 0.0), (150.0, -30.0),
          (-60.0, -60.0), (80.0, 20.0), (-10.0, 55.0), (100.0, 5.0)]
    qxs = np.array([q[0] for q in qs[:nq]])
    qys = np.array([q[1] for q in qs[:nq]])
    # warm: index + residency + compile (or persistent-cache load —
    # the ingest prewarm already keyed this shape family)
    knn_process(ds, "pts50", qxs, qys, min(k, n))
    trials = []
    results = None
    for _ in range(5):
        t0 = time.perf_counter()
        results = knn_process(ds, "pts50", qxs, qys, k)
        trials.append(time.perf_counter() - t0)
    batch_s = _p50(trials)
    p50 = batch_s / nq

    # the unbatched path, for the coalescing win factor
    t0 = time.perf_counter()
    knn_process(ds, "pts50", qs[0][0], qs[0][1], k)
    single_s = time.perf_counter() - t0

    # pinned baseline: numpy argpartition, warm-up + median of 5
    def cpu_pass():
        bd2 = (x - qs[nq - 1][0]) ** 2 + (y - qs[nq - 1][1]) ** 2
        np.argpartition(bd2, k)

    cpu_s = _pinned_median(cpu_pass)
    # per-query exactness: id-stable top-k oracle (argpartition with
    # slack, then (distance, id) lexsort — matches the kernel contract)
    ok = True
    kk = min(k, n)
    for i in range(nq):
        d2 = (x - qxs[i]) ** 2 + (y - qys[i]) ** 2
        cand = np.argpartition(d2, min(kk + 64, n - 1))[:kk + 64]
        oracle = cand[np.lexsort((cand, d2[cand]))][:kk]
        got = np.asarray(results[i][0], dtype=np.int64)
        ok = ok and np.array_equal(got, oracle)
    _pc = _pcts(trials)
    return {"p50_ms": round(p50 * 1e3, 2),
            "p95_ms": round(_pc["p95"] / nq * 1e3, 2),
            "p99_ms": round(_pc["p99"] / nq * 1e3, 2),
            "batch_ms": round(batch_s * 1e3, 2),
            "single_query_ms": round(single_s * 1e3, 2),
            "cpu_ms": round(cpu_s * 1e3, 2),
            "vs_baseline": round(cpu_s / p50, 2),
            "batched": True,
            "n": n, "k": k, "queries": nq, "ids_exact": bool(ok)}


# -- config 5: ST_Contains 100M points vs 10k polygons --------------------

def bench_config5(rng, ds, x, y, n_poly=10_000):
    """10k polygon-containment counts as ONE batched join: all polygons
    ride a single fused x-slab + point-in-polygon counts kernel
    (analytics/processes.contains_process -> join.contains_join), with
    boundary-band rows patched exactly on host in f64. This replaces
    the r3-r5 per-polygon query_count loop whose dense prefilter
    transfers regressed elapsed_s from 2.9s to 16s. Reported warm/cold:
    `first_s` includes compile (or persistent-cache load) + x-sort,
    `p50_s`/`elapsed_s` is the warm median of 3."""
    from geomesa_tpu.analytics.processes import contains_process
    from geomesa_tpu.filters import ast as fast
    from geomesa_tpu.geometry import parse_wkt
    from geomesa_tpu.index.api import Query

    cx = rng.uniform(-175, 175, n_poly)
    cy = rng.uniform(-85, 85, n_poly)
    w = rng.uniform(0.05, 0.5, n_poly)
    h = rng.uniform(0.05, 0.5, n_poly)
    polys = [parse_wkt(
        f"POLYGON (({cx[i]-w[i]} {cy[i]-h[i]}, {cx[i]+w[i]} {cy[i]-h[i]}, "
        f"{cx[i]+w[i]} {cy[i]+h[i]}, {cx[i]-w[i]} {cy[i]+h[i]}, "
        f"{cx[i]-w[i]} {cy[i]-h[i]}))") for i in range(n_poly)]

    # cold: compile (or persistent-cache hit) + device x-sort + scan
    t0 = time.perf_counter()
    counts, _ = contains_process(ds, "ais", polys)
    first_s = time.perf_counter() - t0

    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        counts, _ = contains_process(ds, "ais", polys)
        warm.append(time.perf_counter() - t0)
    scan_s = _p50(warm)
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())

    # pinned baseline: numpy bbox mask + exact PIP per polygon over all
    # 100M, subsampled + extrapolated; warm-up + median of 5
    nb = 8

    def cpu_pass():
        for i in range(nb):
            p = polys[i]
            env = p.envelope
            m = ((x >= env.xmin) & (x <= env.xmax)
                 & (y >= env.ymin) & (y <= env.ymax))
            ridx = np.flatnonzero(m)
            p.contains_points(x[ridx], y[ridx]).sum()

    cpu_s = _pinned_median(cpu_pass) * (n_poly / nb)
    base_counts = np.zeros(nb, dtype=np.int64)
    for i in range(nb):
        p = polys[i]
        env = p.envelope
        m = ((x >= env.xmin) & (x <= env.xmax)
             & (y >= env.ymin) & (y <= env.ymax))
        ridx = np.flatnonzero(m)
        base_counts[i] = int(p.contains_points(x[ridx], y[ridx]).sum())
    ok = np.array_equal(counts[:nb], base_counts)
    # spot-check the store surface still agrees with the join path
    store_agrees = all(
        ds.query_count(Query("ais", fast.Intersects("geom", polys[i])))
        == int(counts[i]) for i in range(min(4, n_poly)))
    _pc = _pcts(warm)
    return {"elapsed_s": round(scan_s, 2),
            "first_s": round(first_s, 2),
            "p50_s": round(scan_s, 2),
            "p95_s": round(_pc["p95"], 2),
            "p99_s": round(_pc["p99"], 2),
            "polygons_per_s": round(n_poly / scan_s, 1),
            "cpu_elapsed_s_extrapolated": round(cpu_s, 2),
            "vs_baseline": round(cpu_s / scan_s, 2),
            "n": len(x), "polygons": n_poly,
            "total_matches": total,
            "store_agrees": bool(store_agrees),
            "counts_exact": bool(ok and store_agrees)}


# -- config 6: concurrent BBOX micro-batching at 10M ----------------------

def bench_config6(rng, x, y, ms):
    """Aggregate throughput of coalesced multi-query execution. Wide
    BBOX windows land in the dense device tier, where the sequential
    path pays per-query launch + O(n) mask transfer + host boundary
    scan; `query_batched` evaluates the whole admission batch in ONE
    vmapped kernel (device-side candidate detection, O(hits) transfer),
    so throughput scales with batch size instead of request count."""
    import threading

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.scan.batcher import QueryBatcher
    from geomesa_tpu.store import InMemoryDataStore

    n = len(x)
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("ais6", "dtg:Date,*geom:Point:srid=4326"))
    ds.write_dict("ais6", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})

    def mk_queries(m, seed):
        q_rng = np.random.default_rng(seed)
        out = []
        for _ in range(m):
            x0 = float(q_rng.uniform(-150, 110))
            y0 = float(q_rng.uniform(-70, 45))
            out.append(Query("ais6",
                             f"BBOX(geom, {x0:.4f}, {y0:.4f}, "
                             f"{x0 + 40:.4f}, {y0 + 25:.4f})"))
        return out

    # exactness gate: coalesced ids equal per-query ids, query for query
    probe = mk_queries(8, seed=7)
    seq_ids = [set(ds.query(q).ids.astype(str)) for q in probe]
    bat_ids = [set(r.ids.astype(str)) for r in ds.query_batched(probe)]
    ok = seq_ids == bat_ids

    levels = {}
    for c in (1, 8, 32, 128):
        rounds = 12 if c == 1 else 3
        qs = mk_queries(c * rounds, seed=100 + c)
        # sequential per-query dispatch (today's path)
        for q in qs[:min(2, len(qs))]:
            ds.query(q)  # warm the scalar shape class
        t0 = time.perf_counter()
        for q in qs:
            ds.query(q)
        seq_s = time.perf_counter() - t0
        # coalesced: one fused scan per c-sized admission batch. Warm
        # with an un-timed pass over the SAME chunks so every hit-count
        # compaction size class is compiled — the timed pass measures
        # steady-state serving, matching the other configs' convention
        for j in range(rounds):
            ds.query_batched(qs[j * c:(j + 1) * c])
        t0 = time.perf_counter()
        for j in range(rounds):
            ds.query_batched(qs[j * c:(j + 1) * c])
        bat_s = time.perf_counter() - t0
        levels[str(c)] = {
            "queries": len(qs),
            "seq_qps": round(len(qs) / seq_s, 1),
            "batched_qps": round(len(qs) / bat_s, 1),
            "speedup": round(seq_s / bat_s, 2),
        }

    # single-query latency through the batcher passthrough (the <= 10%
    # regression budget) vs direct store.query
    q1 = mk_queries(1, seed=999)[0]
    solo = QueryBatcher(ds)
    solo.query(q1)
    direct_samples = [_timed(lambda: ds.query(q1)) for _ in range(15)]
    via_samples = [_timed(lambda: solo.query(q1)) for _ in range(15)]
    direct_pc, via_pc = _pcts(direct_samples), _pcts(via_samples)
    direct_p50, via_p50 = direct_pc["p50"], via_pc["p50"]

    # a threaded burst through the real admission queue: occupancy,
    # coalesce ratio and plan-cache behavior as a server would see them
    burst = QueryBatcher(ds, max_batch=32, linger_us=20_000)
    bqs = mk_queries(32, seed=13)
    threads = [threading.Thread(target=burst.query, args=(q,))
               for q in bqs]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    burst_s = time.perf_counter() - t0
    st = burst.stats()
    return {
        "concurrency": levels,
        "speedup_at_32": levels["32"]["speedup"],
        "p50_direct_ms": round(direct_p50 * 1e3, 3),
        "p99_direct_ms": round(direct_pc["p99"] * 1e3, 3),
        "p50_via_batcher_ms": round(via_p50 * 1e3, 3),
        "p99_via_batcher_ms": round(via_pc["p99"] * 1e3, 3),
        "single_query_overhead_pct": round(
            (via_p50 / direct_p50 - 1.0) * 100, 1),
        "threaded_burst_qps": round(len(bqs) / burst_s, 1),
        "coalesce_ratio": round(st["coalesce_ratio"], 3),
        "plan_cache_hit_rate": round(st["plan_cache_hit_rate"], 3),
        "n": n, "ids_exact": bool(ok),
    }


# -- config 7: durable ingest overhead + crash recovery -------------------

def bench_config7(rng):
    """What durability costs at ingest and buys at reopen. The same
    chunked ingest runs non-durable, then with the WAL at each fsync
    policy; each durable run then measures a full cold recovery (reopen
    replays the whole log), and the `never` run also measures the
    checkpoint-bounded reopen (snapshot load + empty tail) — the two
    ends of the recovery-time spectrum."""
    import shutil
    import tempfile

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.metrics import metrics
    from geomesa_tpu.store import InMemoryDataStore

    def fsync_count():
        return metrics.snapshot()["counters"].get("wal.fsyncs", 0)

    rows = int(os.environ.get("GEOMESA_TPU_BENCH_WAL_ROWS", 1_000_000))
    chunk = max(rows // 100, 1)
    spec = "dtg:Date,*geom:Point:srid=4326"
    x = rng.uniform(-180, 180, rows)
    y = rng.uniform(-90, 90, rows)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY,
                      rows).astype(np.int64)
    ids = np.arange(rows).astype(str).astype(object)

    def ingest(ds):
        t0 = time.perf_counter()
        for lo in range(0, rows, chunk):
            hi = min(lo + chunk, rows)
            ds.write_dict("ais7", ids[lo:hi],
                          {"dtg": ms[lo:hi],
                           "geom": (x[lo:hi], y[lo:hi])})
        return time.perf_counter() - t0

    # warm the WAL encode path (pyarrow IPC import + first-stream cost)
    # outside any timed region so the first policy isn't penalized
    wd = tempfile.mkdtemp(prefix="geomesa-wal-bench-warm-")
    try:
        warm = InMemoryDataStore(durable_dir=wd, wal_fsync="never")
        warm.create_schema(parse_spec("ais7", spec))
        warm.write_dict("ais7", ids[:chunk],
                        {"dtg": ms[:chunk], "geom": (x[:chunk], y[:chunk])})
        warm.close()
    finally:
        shutil.rmtree(wd, ignore_errors=True)

    base_ds = InMemoryDataStore()
    base_ds.create_schema(parse_spec("ais7", spec))
    base_s = ingest(base_ds)
    out = {"rows": rows, "chunk_rows": chunk,
           "non_durable_ingest_s": round(base_s, 3),
           "non_durable_rows_per_s": round(rows / base_s, 1),
           "policies": {}}

    for policy in ("never", "interval", "always"):
        d = tempfile.mkdtemp(prefix=f"geomesa-wal-bench-{policy}-")
        try:
            ds = InMemoryDataStore(durable_dir=d, wal_fsync=policy)
            ds.create_schema(parse_spec("ais7", spec))
            fs0 = fsync_count()
            el = ingest(ds)
            fsyncs = fsync_count() - fs0
            wal_bytes = sum(os.path.getsize(p)
                            for _, p in ds.journal.wal._segments())
            ds.close()
            # cold recovery: reopen replays the whole log
            t0 = time.perf_counter()
            ds2 = InMemoryDataStore(durable_dir=d, wal_fsync=policy)
            reopen_s = time.perf_counter() - t0
            rep = ds2.journal.last_report
            exact = ds2.count("ais7") == rows
            entry = {
                "ingest_s": round(el, 3),
                "rows_per_s": round(rows / el, 1),
                "overhead_pct": round((el / base_s - 1.0) * 100, 1),
                "wal_mb": round(wal_bytes / 1e6, 1),
                "ingest_fsyncs": fsyncs,
                "recovery_s": round(rep.wall_time_s, 3),
                "recovery_rows_per_s": round(
                    rows / rep.wall_time_s, 1) if rep.wall_time_s else 0,
                "reopen_s": round(reopen_s, 3),
                "rows_exact": bool(exact),
            }
            if policy == "never":
                # checkpoint bounds recovery: snapshot + compacted log
                ds2.checkpoint()
                ds2.close()
                t0 = time.perf_counter()
                ds3 = InMemoryDataStore(durable_dir=d, wal_fsync=policy)
                entry["reopen_after_checkpoint_s"] = round(
                    time.perf_counter() - t0, 3)
                entry["rows_exact"] = bool(entry["rows_exact"]
                                           and ds3.count("ais7") == rows)
                ds3.close()
            else:
                ds2.close()
            out["policies"][policy] = entry
        finally:
            shutil.rmtree(d, ignore_errors=True)
    return out


# -- config 8: remote tier on a faulty network ----------------------------

def bench_config8(rng):
    """What the resilience layer costs and buys. A web-served store
    answers the same BBOX query stream twice from a RemoteDataStore —
    direct, then through a ChaosProxy injecting 1% connection resets +
    ~10ms jitter — and the faulty run must finish with ZERO
    client-visible errors and id-identical results (the retry/breaker
    stack absorbs the faults). Also measured: the breaker's fast-fail
    latency against a black-holed endpoint (vs burning timeout_s per
    call) and broker kill->restart recovery for a long-polling
    SocketBus consumer (server-committed offsets resume exactly-once)."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.metrics import metrics
    from geomesa_tpu.resilience import (BreakerBoard, ChaosProxy,
                                        CircuitOpenError, RetryPolicy)
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.live import GeoMessage
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.store.socketbus import SocketBroker, SocketBus
    from geomesa_tpu.web import GeoMesaWebServer

    nq = int(os.environ.get("GEOMESA_TPU_BENCH_CHAOS_QUERIES", 300))
    n = 200_000
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("pts8", "*geom:Point:srid=4326"))
    ds.write_dict("pts8", np.arange(n).astype(str).astype(object),
                  {"geom": (x, y)})
    srv = GeoMesaWebServer(ds).start()

    def boxes(seed):
        q_rng = np.random.default_rng(seed)
        for _ in range(nq):
            x0 = float(q_rng.uniform(-170, 130))
            y0 = float(q_rng.uniform(-80, 55))
            yield f"BBOX(geom, {x0:.4f}, {y0:.4f}, {x0+5:.4f}, {y0+5:.4f})"

    def run(client):
        ids, times, errors = [], [], 0
        for ecql in boxes(seed=77):
            t0 = time.perf_counter()
            try:
                res = client.query(ecql, "pts8")
                ids.append(tuple(sorted(res.ids.astype(str))))
            except Exception:
                errors += 1
                ids.append(None)
            times.append(time.perf_counter() - t0)
        arr = np.asarray(times)
        return ids, {"qps": round(nq / arr.sum(), 1),
                     "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 2),
                     "p95_ms": round(float(np.percentile(arr, 95)) * 1e3, 2),
                     "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 2),
                     "client_errors": errors}

    out = {"queries": nq, "n": n}
    try:
        direct = RemoteDataStore("127.0.0.1", srv.port)
        direct.query("BBOX(geom, 0, 0, 5, 5)", "pts8")  # warm
        clean_ids, out["clean"] = run(direct)

        proxy = ChaosProxy("127.0.0.1", srv.port, reset_rate=0.01,
                           jitter_s=0.010, seed=42).start()
        try:
            faulty = RemoteDataStore("127.0.0.1", proxy.port,
                                     timeout_s=10.0)
            r0 = metrics.snapshot()["counters"].get("resilience.retries", 0)
            chaos_ids, chaos = run(faulty)
            chaos["resets_injected"] = proxy.stats["resets"]
            chaos["retries"] = (metrics.snapshot()["counters"]
                                .get("resilience.retries", 0) - r0)
            chaos["ids_exact"] = bool(chaos_ids == clean_ids)
            out["chaos_1pct_resets"] = chaos
        finally:
            proxy.stop()

        # breaker fast-fail: a black-holed endpoint costs timeout_s per
        # attempt until the breaker opens, then microseconds
        hole = ChaosProxy("127.0.0.1", srv.port, blackhole=True).start()
        try:
            dead = RemoteDataStore(
                "127.0.0.1", hole.port, timeout_s=0.3,
                retry_policy=RetryPolicy(max_attempts=1),
                breakers=BreakerBoard(failure_threshold=2,
                                      reset_timeout_s=60.0))
            for _ in range(2):  # trip the breaker
                try:
                    dead.count("pts8")
                except Exception:
                    pass
            ff = []
            for _ in range(20):
                t0 = time.perf_counter()
                try:
                    dead.count("pts8")
                except CircuitOpenError:
                    pass
                ff.append(time.perf_counter() - t0)
            out["breaker_fast_fail_us"] = round(_p50(ff) * 1e6, 1)
        finally:
            hole.stop()
    finally:
        srv.stop()

    # broker kill -> restart while a consumer is parked in a long poll:
    # wall time from the kill to the reconnected consumer delivering
    # the first post-restart message
    root = tempfile.mkdtemp(prefix="geomesa-bench8-")
    try:
        fast = dict(max_attempts=60, base_s=0.02, cap_s=0.25)
        b1 = SocketBroker(root=root).start()
        port = b1.port
        prod = SocketBus(b1.host, port, group="prod",
                         retry_policy=RetryPolicy(**fast))
        got = []
        cons = SocketBus(b1.host, port, group="cons",
                         retry_policy=RetryPolicy(**fast))
        cons.subscribe("t", lambda m: got.append(time.perf_counter()))
        for i in range(3):
            prod.publish("t", GeoMessage("delete", "t", ids=(f"m{i}",)))
        cons.poll()
        th = threading.Thread(target=lambda: cons.poll(wait_s=20.0))
        th.start()
        time.sleep(0.3)          # consumer parked broker-side
        b1.stop()
        t_kill = time.perf_counter()
        b2 = SocketBroker(port=port, root=root).start()
        prod.publish("t", GeoMessage("delete", "t", ids=("m3",)))
        th.join(timeout=25)
        out["broker_restart_recovery_ms"] = (
            round((got[-1] - t_kill) * 1e3, 1) if got and not th.is_alive()
            else None)
        prod.close()
        cons.close()
        b2.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_config9(rng):
    """What replication buys: read scaling and survivable failover.

    Phase 1 — read qps through a ReplicatedDataStore at 1/2/4 replicas
    (same BBOX count stream; all replicas caught up, so every read is
    staleness-eligible) plus the staleness-bound hit rate (fraction of
    reads served by a replica rather than falling back to the primary).

    Phase 2 — failover: writes flow through the router into a primary
    fronted by a ChaosProxy; mid-ingest the primary dies (web server +
    shipper down, proxy partitioned). Reported: wall time from first
    failed probe to completed auto-promotion, and whether every
    replication-ACKed write survived (the zero-loss contract)."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.metrics import metrics
    from geomesa_tpu.replication import (Replica, ReplicatedDataStore,
                                         WalShipper)
    from geomesa_tpu.resilience import ChaosProxy, RetryPolicy
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web import GeoMesaWebServer

    nq = int(os.environ.get("GEOMESA_TPU_BENCH_REPL_QUERIES", 400))
    n = 200_000
    spec = "*geom:Point:srid=4326"
    out = {"queries": nq, "n": n}

    def boxes(seed):
        q_rng = np.random.default_rng(seed)
        for _ in range(nq):
            x0 = float(q_rng.uniform(-170, 130))
            y0 = float(q_rng.uniform(-80, 55))
            yield f"BBOX(geom, {x0:.4f}, {y0:.4f}, {x0+5:.4f}, {y0+5:.4f})"

    def wait_for(cond, timeout_s=30.0):
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return False

    # -- phase 1: read scaling over replica count -------------------------
    root = tempfile.mkdtemp(prefix="geomesa-bench9-")
    try:
        ds = InMemoryDataStore(durable_dir=os.path.join(root, "p"),
                               wal_fsync="never")
        ds.create_schema(parse_spec("pts9", spec))
        ds.write_dict("pts9", np.arange(n).astype(str).astype(object),
                      {"geom": (rng.uniform(-180, 180, n),
                                rng.uniform(-90, 90, n))})
        ship = WalShipper(ds.journal)
        try:
            for k in (1, 2, 4):
                replicas = [Replica(ship.host, ship.port, name=f"r{i}")
                            for i in range(k)]
                router = ReplicatedDataStore(ds, replicas, ack_replicas=0,
                                             max_lag_lsn=10_000,
                                             max_lag_s=600)
                try:
                    tail = ds.journal.wal.last_lsn
                    wait_for(lambda: all(r.applied_lsn >= tail
                                         for r in replicas))
                    for r in replicas:  # warm every replica's index
                        r.query_count("BBOX(geom, 0, 0, 5, 5)", "pts9")
                    c0 = metrics.snapshot()["counters"]
                    lat = []
                    t0 = time.perf_counter()
                    for ecql in boxes(seed=90 + k):
                        tq = time.perf_counter()
                        router.query_count(ecql, "pts9")
                        lat.append(time.perf_counter() - tq)
                    wall = time.perf_counter() - t0
                    c1 = metrics.snapshot()["counters"]
                    on_replica = (c1.get("replication.reads.replica", 0)
                                  - c0.get("replication.reads.replica", 0))
                    _pc = _pcts(lat)
                    out[f"replicas_{k}"] = {
                        "read_qps": round(nq / wall, 1),
                        "p50_ms": round(_pc["p50"] * 1e3, 2),
                        "p95_ms": round(_pc["p95"] * 1e3, 2),
                        "p99_ms": round(_pc["p99"] * 1e3, 2),
                        "staleness_hit_rate": round(on_replica / nq, 3)}
                finally:
                    # keep the primary: detach replicas only
                    for r in replicas:
                        r.stop()
                    router._probe_stop.set()
        finally:
            ship.stop()

        # -- phase 2: chaos failover ----------------------------------
        primary = InMemoryDataStore(durable_dir=os.path.join(root, "f"),
                                    wal_fsync="never")
        primary.create_schema(parse_spec("pts9", spec))
        srv = GeoMesaWebServer(primary).start()
        proxy = ChaosProxy("127.0.0.1", srv.port).start()
        remote = RemoteDataStore(
            "127.0.0.1", proxy.port, timeout_s=2.0,
            retry_policy=RetryPolicy(max_attempts=2, base_s=0.02,
                                     cap_s=0.05, total_deadline_s=1.0))
        ship2 = WalShipper(primary.journal)
        replicas = [Replica(ship2.host, ship2.port, name=f"f{i}")
                    for i in range(2)]
        router = ReplicatedDataStore(primary=remote, replicas=replicas,
                                     ack_replicas=1, auto_promote=True,
                                     probe_ms=50, probe_failures=2,
                                     max_lag_lsn=10_000, max_lag_s=600)
        acked, failed_writes = [], [0]
        sft9 = parse_spec("pts9", spec)
        stop_ingest = threading.Event()

        def ingest():
            batch_no = 0
            while not stop_ingest.is_set():
                ids = [f"w{batch_no}_{i}" for i in range(50)]
                from geomesa_tpu.features import FeatureBatch
                b = FeatureBatch.from_dict(
                    sft9, ids, {"geom": (np.random.uniform(-10, 10, 50),
                                         np.random.uniform(-10, 10, 50))})
                try:
                    router.write("pts9", b)
                    acked.extend(ids)
                except Exception:
                    failed_writes[0] += 1
                batch_no += 1

        th = threading.Thread(target=ingest, daemon=True)
        th.start()
        try:
            time.sleep(1.0)          # ingest under healthy conditions
            srv.stop()               # primary dies mid-ingest
            ship2.stop()
            proxy.stop()
            promoted = wait_for(
                lambda: isinstance(router.primary, Replica), 15.0)
            stop_ingest.set()
            th.join(timeout=10)
            st = router.replication_status()
            survived = set()
            if promoted:
                res = router.query("INCLUDE", "pts9")
                survived = set(res.ids.astype(str))
            lost = [i for i in acked if i not in survived]
            out["failover"] = {
                "auto_promoted": bool(promoted),
                "failover_s": st.get("failover_seconds"),
                "acked_writes": len(acked),
                "acked_lost": len(lost),
                "zero_acked_loss": promoted and not lost,
                "unacked_write_errors": failed_writes[0]}
        finally:
            stop_ingest.set()
            router.close()
            proxy.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_config11(rng, n=None, nq=None):
    """Cluster serving: scatter-gather scaling + partition tolerance.

    Phase 1 — scatter count qps through a ClusterDataStore at 1/2/4
    in-process shard groups vs the single-store baseline, every box
    checked count-exact against the oracle.

    Phase 2 — failover: two shard groups; group 0 is replicated with
    its primary behind a ChaosProxy-fronted web server. Mid-ingest the
    primary dies; the group auto-promotes INSIDE the cluster while a
    concurrent query stream keeps running. Reported: failover_s, zero
    acked-write loss, and the query accounting — every concurrent
    query must be exact-or-typed-error, never silently wrong (reads
    ride replica legs through the outage, so most stay exact).

    Phase 3 — degraded completeness accounting with one group hard
    down: typed failures with `geomesa.cluster.allow.partial` off,
    flagged partials (completeness fraction + missing z-ranges) on."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.cluster import ClusterDataStore, ShardUnavailableError
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.replication import (Replica, ReplicatedDataStore,
                                         WalShipper)
    from geomesa_tpu.resilience import ChaosProxy, RetryPolicy
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web import GeoMesaWebServer

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_CLUSTER_N", 200_000))
    nq = nq if nq is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_CLUSTER_QUERIES", 400))
    spec = "*geom:Point:srid=4326"
    sft = parse_spec("pts11", spec)
    out = {"queries": nq, "n": n}

    def boxes(seed, count=nq):
        q_rng = np.random.default_rng(seed)
        for _ in range(count):
            x0 = float(q_rng.uniform(-170, 130))
            y0 = float(q_rng.uniform(-80, 55))
            yield f"BBOX(geom, {x0:.4f}, {y0:.4f}, {x0+5:.4f}, {y0+5:.4f})"

    def wait_for(cond, timeout_s=30.0):
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return False

    ids = np.arange(n).astype(str).astype(object)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)

    # -- phase 1: scatter scaling over group count ------------------------
    oracle = InMemoryDataStore()
    oracle.create_schema(sft)
    oracle.write_dict("pts11", ids, {"geom": (x, y)})
    oracle.query_count("BBOX(geom, 0, 0, 5, 5)", "pts11")  # warm
    t0 = time.perf_counter()
    for ecql in boxes(seed=110):
        oracle.query_count(ecql, "pts11")
    out["single_qps"] = round(nq / (time.perf_counter() - t0), 1)

    exact = True
    for k in (1, 2, 4):
        groups = [InMemoryDataStore() for _ in range(k)]
        cluster = ClusterDataStore(groups, leg_deadline_s=60)
        cluster.create_schema(sft)
        cluster.write("pts11", FeatureBatch.from_dict(sft, ids,
                                                      {"geom": (x, y)}))
        cluster.query_count("BBOX(geom, 0, 0, 5, 5)", "pts11")  # warm
        lat = []
        t0 = time.perf_counter()
        for ecql in boxes(seed=110):
            tq = time.perf_counter()
            cluster.query_count(ecql, "pts11")
            lat.append(time.perf_counter() - tq)
        wall = time.perf_counter() - t0
        for ecql in boxes(seed=111, count=max(nq // 10, 5)):
            if cluster.query_count(ecql, "pts11") != \
                    oracle.query_count(ecql, "pts11"):
                exact = False
        _pc = _pcts(lat)
        out[f"groups_{k}"] = {"scatter_qps": round(nq / wall, 1),
                              "p50_ms": round(_pc["p50"] * 1e3, 2),
                              "p95_ms": round(_pc["p95"] * 1e3, 2),
                              "p99_ms": round(_pc["p99"] * 1e3, 2)}
    out["counts_exact"] = exact

    # -- phase 2: chaos failover inside one shard group -------------------
    root = tempfile.mkdtemp(prefix="geomesa-bench11-")
    try:
        primary = InMemoryDataStore(durable_dir=os.path.join(root, "g0"),
                                    wal_fsync="never")
        primary.create_schema(sft)
        srv = GeoMesaWebServer(primary).start()
        proxy = ChaosProxy("127.0.0.1", srv.port).start()
        remote = RemoteDataStore(
            "127.0.0.1", proxy.port, timeout_s=2.0,
            retry_policy=RetryPolicy(max_attempts=2, base_s=0.02,
                                     cap_s=0.05, total_deadline_s=1.0))
        ship = WalShipper(primary.journal)
        replicas = [Replica(ship.host, ship.port, name=f"g0r{i}")
                    for i in range(2)]
        group0 = ReplicatedDataStore(primary=remote, replicas=replicas,
                                     ack_replicas=1, auto_promote=True,
                                     probe_ms=50, probe_failures=2,
                                     max_lag_lsn=100_000, max_lag_s=600)
        group1 = InMemoryDataStore()
        group1.create_schema(sft)
        cluster = ClusterDataStore([group0, group1],
                                   names=["g0", "g1"],
                                   leg_deadline_s=5, hedge_ms=50)
        cluster._sfts["pts11"] = sft  # schemas pre-created per group
        # static rows the concurrent queries assert against
        n_static = min(n, 20_000)
        cluster.write("pts11", FeatureBatch.from_dict(
            sft, np.array([f"s{i}" for i in range(n_static)], object),
            {"geom": (x[:n_static], y[:n_static])}))
        acked, failed_writes = [], [0]
        stop = threading.Event()

        def ingest():
            batch_no = 0
            w_rng = np.random.default_rng(112)
            while not stop.is_set():
                wids = [f"w{batch_no}_{i}" for i in range(50)]
                b = FeatureBatch.from_dict(
                    sft, np.array(wids, dtype=object),
                    {"geom": (w_rng.uniform(-180, 180, 50),
                              w_rng.uniform(-90, 90, 50))})
                try:
                    cluster.write("pts11", b)
                    acked.extend(wids)
                except Exception:
                    failed_writes[0] += 1
                batch_no += 1

        q_ok, q_err, q_wrong = [0], [0], [0]

        def query_loop():
            sq_rng = np.random.default_rng(113)
            while not stop.is_set():
                x0 = float(sq_rng.uniform(-170, 130))
                y0 = float(sq_rng.uniform(-80, 55))
                ecql = (f"BBOX(geom, {x0:.4f}, {y0:.4f}, "
                        f"{x0+20:.4f}, {y0+20:.4f})")
                try:
                    res = cluster.query(ecql, "pts11")
                except Exception:
                    # typed failure (ShardUnavailableError or a write
                    # race) — loud, never wrong
                    q_err[0] += 1
                    continue
                got = set(res.ids.astype(str))
                want = {f"s{i}" for i in range(n_static)
                        if x0 <= x[i] <= x0 + 20 and y0 <= y[i] <= y0 + 20}
                # static rows exact; extras must be concurrent ingest
                if want - got or any(not g.startswith(("s", "w"))
                                     for g in got - want):
                    q_wrong[0] += 1
                else:
                    q_ok[0] += 1

        t_ing = threading.Thread(target=ingest, daemon=True)
        t_qry = threading.Thread(target=query_loop, daemon=True)
        t_ing.start()
        t_qry.start()
        try:
            time.sleep(1.0)           # healthy ingest + queries
            srv.stop()                # group 0's primary dies
            ship.stop()
            proxy.stop()
            promoted = wait_for(
                lambda: isinstance(group0.primary, Replica), 15.0)
            time.sleep(0.5)           # queries against promoted group
            stop.set()
            t_ing.join(timeout=10)
            t_qry.join(timeout=10)
            st = group0.replication_status()
            survived = set()
            if promoted:
                res = cluster.query("INCLUDE", "pts11")
                survived = set(res.ids.astype(str))
            lost = [i for i in acked if i not in survived]
            out["failover"] = {
                "auto_promoted": bool(promoted),
                "failover_s": st.get("failover_seconds"),
                "acked_writes": len(acked),
                "acked_lost": len(lost),
                "zero_acked_loss": promoted and not lost,
                "unacked_write_errors": failed_writes[0],
                "queries_ok": q_ok[0],
                "queries_typed_error": q_err[0],
                "queries_silently_wrong": q_wrong[0]}
        finally:
            stop.set()
            cluster.close()
            proxy.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # -- phase 3: degraded completeness accounting ------------------------
    class _Down:
        """A shard group that lost every node: reads/writes all fail."""

        def __getattr__(self, name):
            def boom(*a, **kw):
                raise ConnectionError("shard group down")
            return boom

    live = InMemoryDataStore()
    live.create_schema(sft)
    half = ClusterDataStore([live, _Down()], names=["up", "down"],
                            leg_deadline_s=2, hedge_ms=20)
    half._sfts["pts11"] = sft
    live.write("pts11", FeatureBatch.from_dict(sft, ids,
                                               {"geom": (x, y)}))
    typed = partial = 0
    nq3 = max(nq // 10, 5)
    # this phase measures the ALL-legs degraded contract: pin the Z-range
    # planner off so every query contacts the dead group (with it on, a
    # selective box legitimately skips "down" and returns the exact
    # answer — config 20 covers that path)
    from geomesa_tpu.cluster.coordinator import CLUSTER_PRUNE
    CLUSTER_PRUNE.set("false")
    try:
        for ecql in boxes(seed=114, count=nq3):
            try:
                half.query_count(ecql, "pts11")
            except ShardUnavailableError:
                typed += 1
        half_p = ClusterDataStore([live, _Down()], names=["up", "down"],
                                  leg_deadline_s=2, hedge_ms=20,
                                  allow_partial=True)
        half_p._sfts["pts11"] = sft
        got_rows = want_rows = 0
        missing_ranges = []
        for ecql in boxes(seed=114, count=nq3):
            c = half_p.query_count(ecql, "pts11")
            if getattr(c, "complete", True) is False:
                partial += 1
                missing_ranges = c.missing_z_ranges
            got_rows += int(c)
            want_rows += oracle.query_count(ecql, "pts11")
    finally:
        CLUSTER_PRUNE.set(None)
    out["degraded"] = {
        "queries": nq3,
        "typed_errors_knob_off": typed,
        "partial_flagged_knob_on": partial,
        "completeness_fraction": round(got_rows / max(want_rows, 1), 3),
        "missing_z_ranges": missing_ranges}
    return out


# -- config 12: hot-tile serving via the materialized result cache --------

def bench_config12(rng, n=None, concurrency=None, nq=None,
                   repl_writes=None):
    """What LSN-keyed memoization buys on a hot-tile workload.

    Mixed hot/cold density-tile traffic at c=32 against one store —
    a p99 story, not a p50 one (a dashboard feels the slowest tile).
    Phases: (A) uncached (kill switch off: every request recomputes),
    (B) cached warm, (C) single-flight — c identical cold requests must
    collapse into ONE device compute, (D) cached under sustained writes
    with the background refresher re-materializing hot tiles, (E) the
    exactness gate — a cached tile must be byte-identical to a fresh
    recompute at the same version, and (F) a replicated probe: cached
    reads through the staleness-bounded router never observe state
    older than ``geomesa.repl.max.lag.lsn``."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.cache import CACHE_ENABLED, CacheRefresher
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_CACHE_N", N_BIG))
    c = int(concurrency if concurrency is not None else 32)
    nq = int(nq if nq is not None else 12)   # requests per worker/phase
    out = {"n": n, "concurrency": c}

    sft = parse_spec("tiles12", "dtg:Date,*geom:Point:srid=4326")
    ds = InMemoryDataStore()
    ds.create_schema(sft)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("tiles12", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})
    del x, y, ms

    # the tile universe: 32 tiles of a 45-degree world grid; the first
    # 4 are "hot" (~80% of traffic), the rest long-tail cold
    W = H = 256
    n_tiles, n_hot = 32, 4

    def tile_bbox(i):
        x0 = -180.0 + (i % 8) * 45.0
        y0 = -90.0 + ((i // 8) % 4) * 45.0
        return (x0, y0, x0 + 45.0, y0 + 45.0)

    def serve(i):
        return ds.density("tiles12", "INCLUDE", tile_bbox(int(i)), W, H)

    def run_phase(seed):
        """c workers x nq requests each, ~80% hot / 20% cold; every
        worker's schedule is fixed up front so phases are comparable."""
        prng = np.random.default_rng(seed)
        sched = [np.where(prng.random(nq) < 0.8,
                          prng.integers(0, n_hot, nq),
                          prng.integers(n_hot, n_tiles, nq))
                 for _ in range(c)]
        lat = [[] for _ in range(c)]
        hot = [[] for _ in range(c)]
        barrier = threading.Barrier(c)

        def worker(w):
            barrier.wait()
            for i in sched[w]:
                t0 = time.perf_counter()
                serve(i)
                dt = time.perf_counter() - t0
                lat[w].append(dt)
                if i < n_hot:
                    hot[w].append(dt)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(c)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        alls = [v for ws in lat for v in ws]
        hots = [v for ws in hot for v in ws] or alls
        pc, hpc = _pcts(alls), _pcts(hots)
        return {"requests": len(alls), "qps": round(len(alls) / wall, 1),
                "p50_ms": round(pc["p50"] * 1e3, 2),
                "p95_ms": round(pc["p95"] * 1e3, 2),
                "p99_ms": round(pc["p99"] * 1e3, 2),
                "hot_p99_ms": round(hpc["p99"] * 1e3, 2)}

    # -- phase A: uncached (process-wide kill switch, all threads) --------
    serve(0)  # index build + compile outside the timed window
    CACHE_ENABLED.set("false")
    try:
        out["uncached"] = run_phase(7)
    finally:
        CACHE_ENABLED.set(None)

    # -- phase B: cached warm ---------------------------------------------
    for i in range(n_tiles):
        serve(i)  # prewarm every tile at the current version
    h0, m0 = ds.result_cache.hits, ds.result_cache.misses
    out["cached"] = run_phase(8)
    served = ds.result_cache.hits - h0
    out["cached"]["hit_rate"] = round(
        served / max(served + ds.result_cache.misses - m0, 1), 4)
    out["hot_p99_speedup"] = round(
        out["uncached"]["hot_p99_ms"]
        / max(out["cached"]["hot_p99_ms"], 1e-6), 1)

    # -- phase C: single-flight collapse ----------------------------------
    cache = ds.result_cache
    cache.invalidate()
    m0, sf0 = cache.misses, cache.singleflight_waits
    barrier = threading.Barrier(c)

    def cold(_w):
        barrier.wait()
        serve(0)

    threads = [threading.Thread(target=cold, args=(w,)) for w in range(c)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    computes = cache.misses - m0
    out["singleflight"] = {
        "concurrent_identical_requests": c,
        "device_computes": int(computes),
        "waits": int(cache.singleflight_waits - sf0),
        "collapsed": bool(computes == 1)}

    # -- phase D: cached under sustained writes + hot refresher -----------
    stop_w = threading.Event()
    wrote = [0]

    def writer():
        w_rng = np.random.default_rng(999)
        while not stop_w.is_set():
            k = 100
            ids = np.array([f"w{wrote[0] + j}" for j in range(k)],
                           dtype=object)
            ds.write_dict("tiles12", ids, {
                "dtg": w_rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY,
                                      k).astype(np.int64),
                "geom": (w_rng.uniform(-180, 180, k),
                         w_rng.uniform(-90, 90, k))})
            wrote[0] += k
            stop_w.wait(0.02)

    refresher = CacheRefresher(ds, interval_s=0.05, top_k=n_hot)
    refresher.start()
    wt = threading.Thread(target=writer)
    wt.start()
    try:
        out["cached_under_writes"] = run_phase(9)
    finally:
        stop_w.set()
        wt.join()
        refresher.stop()
    out["cached_under_writes"]["rows_written_during"] = wrote[0]
    out["cached_under_writes"]["refresh_passes"] = refresher.runs

    # -- phase E: exactness gate (cached == fresh recompute, same LSN) ----
    exact = True
    for i in range(n_hot + 2):
        g_cached = np.asarray(serve(i), np.float32)
        CACHE_ENABLED.thread_local_set("false")
        try:
            g_fresh = np.asarray(serve(i), np.float32)
        finally:
            CACHE_ENABLED.thread_local_set(None)
        exact = exact and g_cached.tobytes() == g_fresh.tobytes()
    out["exact_at_lsn"] = bool(exact)
    del ds

    # -- phase F: replicated bounded-staleness probe ----------------------
    # One feature per write => the primary's WAL LSN maps 1:1 onto the
    # density grid's mass: a tile whose sum implies fewer rows than
    # (primary LSN at request time - max_lag_lsn) is a staleness
    # violation. Cached replica tiles are stamped with the replica's
    # own applied version, so they can never be staler than the
    # replica itself — the router's eligibility bound is the contract.
    from geomesa_tpu.replication import (Replica, ReplicatedDataStore,
                                         WalShipper)
    lag_bound = 50
    writes = int(repl_writes if repl_writes is not None else 150)
    root = tempfile.mkdtemp(prefix="geomesa-bench12-")
    violations = reads = 0
    try:
        prim = InMemoryDataStore(durable_dir=os.path.join(root, "p"),
                                 wal_fsync="never")
        prim.create_schema(parse_spec("pts12", "*geom:Point:srid=4326"))
        base = 64
        prim.write_dict("pts12",
                        np.arange(base).astype(str).astype(object),
                        {"geom": (np.full(base, 0.5),
                                  np.full(base, 0.5))})
        base_lsn = prim.journal.wal.last_lsn
        ship = WalShipper(prim.journal)
        replica = Replica(ship.host, ship.port, name="r0")
        router = ReplicatedDataStore(prim, [replica], ack_replicas=0,
                                     max_lag_lsn=lag_bound,
                                     max_lag_s=600)
        try:
            deadline = time.perf_counter() + 30
            while (replica.applied_lsn < base_lsn
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
            bb = (0.0, 0.0, 1.0, 1.0)
            stop = threading.Event()

            def repl_writer():
                j = 0
                while not stop.is_set() and j < writes:
                    prim.write_dict("pts12", np.array([f"x{j}"],
                                                      dtype=object),
                                    {"geom": (np.full(1, 0.5),
                                              np.full(1, 0.5))})
                    j += 1
                    stop.wait(0.002)

            rw = threading.Thread(target=repl_writer)
            rw.start()
            try:
                while rw.is_alive() or reads < 20:
                    lsn_pre = prim.journal.wal.last_lsn
                    grid = router.density("pts12", "INCLUDE", bb, 8, 8)
                    implied_lsn = (base_lsn
                                   + int(round(float(np.sum(grid))))
                                   - base)
                    reads += 1
                    if implied_lsn < lsn_pre - lag_bound:
                        violations += 1
                    if reads > writes * 4:
                        break
            finally:
                stop.set()
                rw.join()
        finally:
            router.close()
            ship.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    out["replicated"] = {"reads": reads,
                         "staleness_bound_lsn": lag_bound,
                         "violations": int(violations)}
    return out


# -- config 13: tail-latency serving tier ---------------------------------

def bench_config13(rng, n=None, c_web=None, c_emb=None, nq=None,
                   slow_s=None):
    """What the tail-latency serving tier buys, in three phases.

    (A) Coalesce proof: web-tier HTTP requests and embedded callers
        ask the process-wide ``BatcherRegistry`` for the same store's
        batcher and must land in ONE fused device dispatch (counter
        assertion, id-exact vs direct ``store.query``). Driven
        deterministically: a gated sacrificial query holds a dispatch
        in flight so the burst's leader load-gates into a long static
        linger, and ``max_batch`` equals the caller count so the last
        arrival releases the batch without waiting out the window.
    (B) Hedged vs unhedged p99 through a ChaosProxy straggler profile
        (``slow_rate``/``slow_s``): most requests are fast, a random
        few stall a quarter second — the tail only a speculative
        second attempt rescues. Both clients warm the latency EWMA on
        a clean proxy first, then run the same stream with stragglers
        on; reports win/loss/cancelled/suppressed counters, the
        budget invariant, and an id-exactness probe under chaos.
    (C) Latency-derived batch caps: with the per-shape-class cost
        EWMA seeded by phase A's fused dispatch, setting
        ``geomesa.batch.latency.budget.ms`` must shrink the effective
        cap below the static ceiling (and leaving it unset must not).
    """
    import threading

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.metrics import metrics
    from geomesa_tpu.resilience import ChaosProxy
    from geomesa_tpu.scan.batcher import (BATCH_LATENCY_BUDGET_MS,
                                          BATCH_LINGER_ADAPTIVE,
                                          BATCH_LINGER_MICROS,
                                          BATCH_MAX_SIZE)
    from geomesa_tpu.scan.registry import batcher_registry, shared_batcher
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web.server import GeoMesaWebServer

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_TAIL_N", 200_000))
    cw = int(c_web if c_web is not None else 16)
    ce = int(c_emb if c_emb is not None else 16)
    nq = int(nq if nq is not None else 150)
    slow = float(slow_s if slow_s is not None else 0.25)
    total = cw + ce
    out = {"n": n, "web_callers": cw, "embedded_callers": ce}

    class GateStore(InMemoryDataStore):
        """Holds a marked scalar query in flight so the coalesce
        phase's leader load-gates into its linger window."""

        def __init__(self):
            super().__init__()
            self.hold = threading.Event()

        def query(self, q, *args, **kwargs):
            if getattr(q, "hints", {}).get("_gate13"):
                assert self.hold.wait(60.0), "gate never released"
            return super().query(q, *args, **kwargs)

    ds = GateStore()
    ds.create_schema(parse_spec("tail13",
                                "dtg:Date,*geom:Point:srid=4326"))
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("tail13", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})
    del x, y, ms

    def bbox_q(i, w=4.0, h=4.0):
        x0 = -170.0 + (i * 37) % 330
        y0 = -80.0 + (i * 23) % 150
        return Query("tail13",
                     f"BBOX(geom, {x0}, {y0}, {x0 + w}, {y0 + h})")

    def _wait(pred, timeout=15.0):
        deadline = time.perf_counter() + timeout
        while not pred():
            if time.perf_counter() > deadline:
                raise AssertionError("config 13 staging timed out")
            time.sleep(0.001)

    # -- phase A: shared-registry coalesce proof --------------------------
    batcher_registry.clear()
    BATCH_LINGER_ADAPTIVE.set("false")
    BATCH_LINGER_MICROS.set(str(int(5e6)))
    BATCH_MAX_SIZE.set(str(total))
    server = None
    try:
        server = GeoMesaWebServer(ds).start()
        b = shared_batcher(ds)
        # the tentpole contract: BOTH tiers hold the same instance
        shared = server.batcher is b
        client = RemoteDataStore("127.0.0.1", server.port, hedge=False)
        client.get_schema("tail13")   # prefetch off the burst path
        batches_pre = b.batches
        gate = bbox_q(0, w=0.01, h=0.01)
        gate.hints["_gate13"] = True
        warm = threading.Thread(target=b.query, args=(gate,), daemon=True)
        warm.start()
        _wait(lambda: b._in_flight >= 1 and b.batches == batches_pre + 1)
        batches0, co0 = b.batches, b.coalesced_queries
        queries = [bbox_q(i + 1) for i in range(total)]
        results: list = [None] * total
        barrier = threading.Barrier(total)

        def web_worker(i):
            barrier.wait()
            results[i] = client.query(queries[i])

        def emb_worker(i):
            barrier.wait()
            results[i] = b.query(queries[i])

        threads = [threading.Thread(
            target=web_worker if i < cw else emb_worker, args=(i,),
            daemon=True) for i in range(total)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        stuck = any(t.is_alive() for t in threads)
        ds.hold.set()
        warm.join(10.0)
        exact = not stuck
        for i, r in enumerate(results):
            if r is None:
                exact = False
                continue
            want = InMemoryDataStore.query(ds, queries[i])
            exact = exact and np.array_equal(np.sort(r.ids),
                                             np.sort(want.ids))
        fused = int(b.batches - batches0)
        out["coalesce"] = {
            "callers": total,
            "registry_shared_instance": bool(shared),
            "fused_dispatches": fused,
            "coalesced_queries": int(b.coalesced_queries - co0),
            "single_fused_dispatch": bool(
                fused == 1 and b.coalesced_queries - co0 == total),
            "ids_exact": bool(exact)}
        # the health surface must expose the registry's queue depths
        import http.client as _hc
        conn = _hc.HTTPConnection("127.0.0.1", server.port, timeout=10)
        try:
            conn.request("GET", "/rest/health")
            health = json.loads(conn.getresponse().read().decode())
        finally:
            conn.close()
        out["coalesce"]["health_has_batcher"] = "batcher" in health \
            and health["batcher"] is not None
    finally:
        BATCH_LINGER_ADAPTIVE.set(None)
        BATCH_LINGER_MICROS.set(None)
        BATCH_MAX_SIZE.set(None)
        if server is not None:
            server.stop()

    # -- phase C (uses phase A's seeded cost EWMA) ------------------------
    cost = max(b._cost_ewma.values()) if b._cost_ewma else 0.0
    eff_unset = b.effective_max_batch("tail13")
    want_cap = max(1, total // 2)
    BATCH_LATENCY_BUDGET_MS.set(str(cost * (want_cap + 0.5) * 1e3))
    try:
        eff = b.effective_max_batch("tail13")
    finally:
        BATCH_LATENCY_BUDGET_MS.set(None)
    out["batch_caps"] = {
        "static_max_batch": int(b.max_batch),
        "per_query_cost_ms": round(cost * 1e3, 3),
        "effective_max_batch": int(eff),
        "derived_below_static": bool(cost > 0 and eff < b.max_batch),
        "uncapped_without_budget": bool(eff_unset == b.max_batch)}
    batcher_registry.clear()

    # -- phase B: hedged vs unhedged p99 under a straggler profile --------
    server = GeoMesaWebServer(ds).start()
    proxy = ChaosProxy("127.0.0.1", server.port, seed=7,
                       slow_rate=0.0, slow_s=slow).start()
    try:
        unhedged = RemoteDataStore(proxy.host, proxy.port, hedge=False)
        hedged = RemoteDataStore(proxy.host, proxy.port)

        def stream(ds_client, count):
            lat = []
            for i in range(count):
                t0 = time.perf_counter()
                ds_client.query(bbox_q(i))
                lat.append(time.perf_counter() - t0)
            return lat

        # clean-proxy warmup: both clients build their latency EWMA on
        # healthy calls (the p99 estimate that picks the hedge delay)
        stream(unhedged, max(nq // 5, 10))
        stream(hedged, max(nq // 5, 10))

        proxy.slow_rate = 0.1
        c0 = metrics.snapshot()["counters"]
        lat_u = stream(unhedged, nq)
        lat_h = stream(hedged, nq)
        c1 = metrics.snapshot()["counters"]

        def delta(key):
            return int(c1.get(key, 0) - c0.get(key, 0))

        # id-exactness probe while stragglers are live
        probe_ok = True
        for i in range(5):
            got = hedged.query(bbox_q(i))
            want = InMemoryDataStore.query(ds, bbox_q(i))
            probe_ok = probe_ok and np.array_equal(
                np.sort(got.ids), np.sort(want.ids))
        proxy.slow_rate = 0.0

        pu, ph = _pcts(lat_u), _pcts(lat_h)
        attempts = delta("resilience.hedge.attempts")
        # budget invariant: hedges are charged to the shared retry
        # budget (capacity 10, ratio 0.2 per first attempt)
        budget_cap = (nq + max(nq // 5, 10) + 5) * 0.2 + 10.0
        out["unhedged"] = {"requests": nq,
                           "p50_ms": round(pu["p50"] * 1e3, 2),
                           "p95_ms": round(pu["p95"] * 1e3, 2),
                           "p99_ms": round(pu["p99"] * 1e3, 2)}
        out["hedged"] = {"requests": nq,
                         "p50_ms": round(ph["p50"] * 1e3, 2),
                         "p95_ms": round(ph["p95"] * 1e3, 2),
                         "p99_ms": round(ph["p99"] * 1e3, 2),
                         "attempts": attempts,
                         "wins": delta("resilience.hedge.wins"),
                         "losses": delta("resilience.hedge.losses"),
                         "cancelled": delta("resilience.hedge.cancelled"),
                         "suppressed_budget": delta(
                             "resilience.hedge.suppressed.budget"),
                         "budget_ok": bool(attempts <= budget_cap),
                         "ids_exact": bool(probe_ok)}
        out["slow_profile"] = {"slow_rate": 0.1, "slow_s": slow,
                               "slowed_connections": proxy.stats["slowed"]}
        out["hedge_p99_speedup"] = round(
            pu["p99"] / max(ph["p99"], 1e-9), 2)
        out["hedged_beats_unhedged_p99"] = bool(ph["p99"] < pu["p99"])
    finally:
        proxy.stop()
        server.stop()
        batcher_registry.clear()
    return out


# -- config 14: streaming result plane ------------------------------------

def bench_config14(rng, n=None, batch_rows=None):
    """What the streaming result plane buys, in three gates.

    (A) Time-to-first-batch: a remote ``query_stream`` must hand the
        client its first record batch while the server is still
        encoding the rest — gate: TTFB < 10% of the materialized
        ``arrow_ipc`` fetch of the same hits.
    (B) Constant client memory: tracemalloc peak while draining the
        stream (batches discarded as consumed) must stay under two
        wire batches' worth — the client never holds the result.
    (C) Byte-exact reconstruction: reassembling the streamed batches
        (arrow/delta.reassemble_ipc) must reproduce the materialized
        IPC payload byte-for-byte on the quiesced store.
    """
    import tracemalloc

    from geomesa_tpu.arrow.delta import iter_ipc, reassemble_ipc
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.obs.prof import PROF_HZ
    from geomesa_tpu.obs.runtime import RUNTIME_ENABLED
    from geomesa_tpu.obs.slo import SLO_ENABLED
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web.server import GeoMesaWebServer

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_STREAM_N", 1_000_000))
    rows = int(batch_rows if batch_rows is not None else 8192)
    out = {"n": n, "batch_rows": rows}

    # server and client share this process, so the tracemalloc windows
    # below would otherwise count the health plane's background
    # allocations (profiler trie, SLO ring buckets, runtime samples)
    # against the CLIENT-memory contract. The health-plane tax has its
    # own config (18_health); keep it out of this measurement.
    _health_saved = {p: p.get_override()
                     for p in (PROF_HZ, SLO_ENABLED, RUNTIME_ENABLED)}
    PROF_HZ.set("0")
    SLO_ENABLED.set("false")
    RUNTIME_ENABLED.set("false")

    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("s14", "dtg:Date,*geom:Point:srid=4326"))
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("s14", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})
    del x, y, ms

    server = GeoMesaWebServer(ds).start()
    try:
        client = RemoteDataStore("127.0.0.1", server.port, hedge=False)
        client.get_schema("s14")
        q = Query("s14")

        # -- (A) TTFB vs the materialized fetch ---------------------------
        t0 = time.perf_counter()
        payload = client.arrow_ipc("s14")
        full_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        stream = iter(client.query_stream(q, batch_rows=rows))
        first = next(stream)
        ttfb_s = time.perf_counter() - t0
        streamed = first.n + sum(b.n for b in stream)
        total_s = time.perf_counter() - t0
        out["ttfb"] = {
            "rows_streamed": int(streamed),
            "materialized_fetch_s": round(full_s, 4),
            "ttfb_s": round(ttfb_s, 4),
            "stream_total_s": round(total_s, 4),
            "ttfb_fraction": round(ttfb_s / max(full_s, 1e-9), 4),
            "ttfb_under_10pct": bool(ttfb_s < 0.10 * full_s)}

        # -- (B) constant-memory drain ------------------------------------
        # "one batch's worth" is measured, not assumed: the tracemalloc
        # peak of pulling a single warm batch (decode + python-side id
        # strings). Phase A already warmed the server-side caches, so
        # neither measurement below sees the server thread's one-time
        # result materialization (server and client share this process).
        wire_bytes = int(first.to_arrow().nbytes)
        tracemalloc.start()
        tracemalloc.reset_peak()
        probe = iter(client.query_stream(q, batch_rows=rows))
        next(probe)
        _, batch_peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        for _ in probe:
            pass
        tracemalloc.start()
        tracemalloc.reset_peak()
        drained = 0
        for b in client.query_stream(q, batch_rows=rows):
            drained += b.n
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        out["client_memory"] = {
            "rows_drained": int(drained),
            "wire_batch_bytes": wire_bytes,
            "one_batch_peak_bytes": int(batch_peak),
            "drain_peak_bytes": int(peak),
            "peak_batches": round(peak / max(batch_peak, 1), 2),
            "under_two_batches": bool(peak < 2 * batch_peak)}

        # -- (C) byte-exact reconstruction --------------------------------
        rebuilt = reassemble_ipc(client.get_schema("s14"),
                                 client.query_stream(q, batch_rows=rows))
        out["reconstruction"] = {
            "materialized_bytes": len(payload),
            "rebuilt_bytes": len(rebuilt),
            "byte_exact": bool(rebuilt == payload)}
        out["gates_pass"] = bool(
            out["ttfb"]["ttfb_under_10pct"]
            and out["client_memory"]["under_two_batches"]
            and out["reconstruction"]["byte_exact"]
            and streamed == n and drained == n)
    finally:
        server.stop()
        for p, v in _health_saved.items():
            p.set(v)
    return out


# -- config 15: device-resident geofencing ---------------------------------

def _geofence_ecql(rng, i: int) -> str:
    """One standing filter: mostly plain geofence boxes, with time /
    numeric-range / residual-LIKE variants mixed in (the residual tenth
    exercises the evaluate-on-survivors patch path)."""
    cx = float(rng.uniform(-178, 178))
    cy = float(rng.uniform(-88, 88))
    w = float(rng.uniform(0.5, 2.5))
    box = (f"bbox(geom,{cx - w:.4f},{cy - w:.4f},"
           f"{cx + w:.4f},{cy + w:.4f})")
    m = i % 10
    if m == 3:
        return (f"{box} AND dtg DURING "
                f"2016-07-01T00:00:00Z/2016-09-01T00:00:00Z")
    if m == 5:
        lo = float(rng.uniform(0, 200))
        return f"{box} AND speed BETWEEN {lo:.2f} AND {lo + 40:.2f}"
    if m == 7:
        return f"{box} AND name LIKE 'u{i % 100}%'"
    return box


def _geofence_batch(rng, sft, n, tag):
    from geomesa_tpu.features.batch import FeatureBatch
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    return FeatureBatch.from_dict(
        sft, [f"{tag}_{i}" for i in range(n)],
        {"name": [f"u{i % 500}" for i in range(n)],
         "speed": rng.uniform(0, 300, n),
         "dtg": ms,
         "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))})


def bench_config15(rng, n_filters=None, n_filters_big=None,
                   ingest_rows=None, n_batches=None, big_rows=None):
    """Standing-query matching at geofence scale, in three gates.

    (A) Throughput, 10k filters x sustained ingest through the real
        ``ContinuousQueryPublisher``: the fused device kernel
        (``geomesa.cq.device``) vs the per-filter host ``evaluate``
        loop (kill switch off) on identical batches — gate: device
        >= 20x host rows/s. The matched-row ids published per topic
        must be identical between the two runs (the kill switch is
        bit-identical, not merely equivalent).
    (B) Exactness, 100k filters x one bulk batch straight through
        ``StandingFilterSet.dispatch``: per-filter hit rows id-exact
        vs the per-filter ``filters.evaluate`` oracle, residual
        filters included (GEOMESA_TPU_BENCH_GEOFENCE_ORACLE=0 checks
        every filter; the default samples 2048, residual-stratified).
    (C) Incrementality: register/unregister churn within the padded
        cap triggers zero kernel recompiles (plan-cache counters).
    """
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.filters import evaluate, parse_ecql
    from geomesa_tpu.scan.standing import StandingFilterSet
    from geomesa_tpu.store import LiveDataStore
    from geomesa_tpu.store.continuous import (CQ_DEVICE,
                                              ContinuousQueryPublisher)

    env = os.environ.get
    nf = int(n_filters if n_filters is not None
             else env("GEOMESA_TPU_BENCH_GEOFENCE_FILTERS", 10_000))
    nf_big = int(n_filters_big if n_filters_big is not None
                 else env("GEOMESA_TPU_BENCH_GEOFENCE_FILTERS_BIG",
                          100_000))
    rows = int(ingest_rows if ingest_rows is not None
               else env("GEOMESA_TPU_BENCH_GEOFENCE_INGEST_ROWS", 8192))
    batches = int(n_batches if n_batches is not None
                  else env("GEOMESA_TPU_BENCH_GEOFENCE_BATCHES", 4))
    nbig = int(big_rows if big_rows is not None
               else env("GEOMESA_TPU_BENCH_GEOFENCE_ROWS", 1_000_000))
    oracle_sample = int(env("GEOMESA_TPU_BENCH_GEOFENCE_ORACLE", 2048))
    spec = "name:String,speed:Double,dtg:Date,*geom:Point:srid=4326"
    out = {"filters": nf, "filters_big": nf_big, "ingest_rows": rows,
           "batches": batches, "bulk_rows": nbig}

    ecqls = [_geofence_ecql(rng, i) for i in range(max(nf, nf_big))]
    feed = [_geofence_batch(rng, parse_spec("g15", spec), rows, f"b{b}")
            for b in range(batches)]
    warm = _geofence_batch(rng, parse_spec("g15", spec), rows, "warm")

    # -- (A) publisher throughput: device kernel vs host loop -------------
    def run(device: bool):
        sft = parse_spec("g15", spec)
        store = LiveDataStore()
        store.create_schema(sft)
        pub = ContinuousQueryPublisher(store)
        t0 = time.perf_counter()
        for i in range(nf):
            pub.register(f"q{i}", "g15", ecqls[i])
        reg_s = time.perf_counter() - t0
        CQ_DEVICE.set("true" if device else "false")
        try:
            # one unprobed warmup write: the device run's jit compile
            # happens here, so the timed window is steady-state
            store.write("g15", warm)
            probe = {}
            sample = list(range(0, nf, max(nf // 64, 1)))
            for i in sample:
                got: list = []
                store.bus.subscribe(
                    f"cq.q{i}",
                    (lambda g: lambda m: g.extend(
                        list(m.batch.ids)))(got))
                probe[f"q{i}"] = got
            t0 = time.perf_counter()
            for b in feed:
                store.write("g15", b)
            elapsed = time.perf_counter() - t0
        finally:
            CQ_DEVICE.set(None)
        pub.close()
        return elapsed, reg_s, probe

    host_s, host_reg_s, host_probe = run(device=False)
    dev_s, dev_reg_s, dev_probe = run(device=True)
    total = rows * batches
    identical = all(host_probe[k] == dev_probe[k] for k in host_probe)
    out["publisher"] = {
        "register_per_s": round(nf / max(dev_reg_s, 1e-9)),
        "host_s": round(host_s, 3),
        "device_s": round(dev_s, 3),
        "host_rows_per_s": round(total / max(host_s, 1e-9)),
        "device_rows_per_s": round(total / max(dev_s, 1e-9)),
        "device_speedup": round(host_s / max(dev_s, 1e-9), 2),
        "topics_probed": len(host_probe),
        "kill_switch_bit_identical": bool(identical)}

    # -- (B) 100k-filter bulk exactness vs the evaluate oracle ------------
    sft = parse_spec("g15b", spec)
    fset = StandingFilterSet(sft)
    parsed = [parse_ecql(e) for e in ecqls[:nf_big]]
    t0 = time.perf_counter()
    for i, f in enumerate(parsed):
        fset.register(f"q{i}", f)
    big_reg_s = time.perf_counter() - t0
    bulk = _geofence_batch(rng, sft, nbig, "bulk")
    t0 = time.perf_counter()
    hits = fset.dispatch(bulk)
    bulk_s = time.perf_counter() - t0
    st = fset.stats()
    if oracle_sample and oracle_sample < nf_big:
        # residual-stratified sample: every 10th index is the LIKE
        # variant, so a stride over the population keeps them in
        check = list(range(0, nf_big,
                           max(nf_big // oracle_sample, 1)))
    else:
        check = list(range(nf_big))
    t0 = time.perf_counter()
    mism = sum(
        not np.array_equal(np.asarray(hits[f"q{i}"], dtype=np.int64),
                           np.flatnonzero(evaluate(parsed[i], bulk)))
        for i in check)
    oracle_s = time.perf_counter() - t0
    out["bulk"] = {
        "register_per_s": round(nf_big / max(big_reg_s, 1e-9)),
        "dispatch_s": round(bulk_s, 3),
        "rows_per_s": round(nbig / max(bulk_s, 1e-9)),
        "padded_cap": st["padded_cap"],
        "residual_fraction": st["residual_fraction"],
        "oracle_filters_checked": len(check),
        "oracle_s": round(oracle_s, 2),
        "id_exact": bool(mism == 0)}

    # -- (C) churn within the padded cap never recompiles -----------------
    miss0 = fset.cache_misses
    for i in range(0, min(nf_big, 256)):
        fset.unregister(f"q{i}")
        fset.register(f"q{i}r", parsed[i])
    # same row count as the bulk batch -> same jit shape class
    fset.dispatch(_geofence_batch(rng, sft, nbig, "churn"))
    out["churn"] = {"replaced": min(nf_big, 256),
                    "recompiles": fset.cache_misses - miss0,
                    "zero_recompile": bool(fset.cache_misses == miss0)}

    out["gates_pass"] = bool(
        out["publisher"]["device_speedup"] >= 20.0
        and out["publisher"]["kill_switch_bit_identical"]
        and out["bulk"]["id_exact"]
        and out["churn"]["zero_recompile"])
    return out


# -- config 16: ingest firehose — vectorized convert + group commit -------

def bench_config16(rng, n=None, c_read=None, read_rounds=None,
                   kill_rows=None):
    """The ingest firehose, end to end. (A) the same AIS-shaped CSV
    stream is converted and committed to a durable store two ways —
    the scalar per-write baseline (record-at-a-time transforms, one
    store.write per chunk) and the firehose path (columnar converter +
    group-commit pipeline) — gated at >= 5x sustained rows/s. (B) a
    c=32 BBOX read battery runs idle and again against a live ingest,
    so admission control's promise (bulk writes don't starve reads)
    shows up as a bounded p99 ratio. (C) a mid-ingest copy of the
    durable dir (the kill image, taken while the writer thread is
    live) must recover every row acked before the copy began — the
    zero-acked-loss contract."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.convert.converter import converter_for
    from geomesa_tpu.convert.dsl import EvaluationContext
    from geomesa_tpu.convert.vectorized import INGEST_VECTORIZED
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.ingest import IngestPipeline
    from geomesa_tpu.metrics import metrics
    from geomesa_tpu.store import InMemoryDataStore

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_INGEST_ROWS", 1_000_000))
    c_read = c_read if c_read is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_INGEST_READERS", 32))
    read_rounds = read_rounds if read_rounds is not None else 4
    kill_rows = kill_rows if kill_rows is not None else min(n, 100_000)
    baseline_chunk = 4096   # a client POST's worth per scalar write
    fast_chunk = 65536      # geomesa.ingest.batch.rows default

    spec = ("name:String,mmsi:Integer,dtg:Date,speed:Double,"
            "course:Double,heading:Double,*geom:Point:srid=4326")
    cfg = {"type": "delimited-text", "format": "CSV",
           "id-field": "concat('v', $2)",
           "fields": [
               {"name": "name", "transform": "$1"},
               {"name": "mmsi", "transform": "$2::int"},
               {"name": "dtg", "transform": "isoDate($3)"},
               {"name": "geom",
                "transform": "point($4::double, $5::double)"},
               {"name": "speed", "transform": "$6::double"},
               {"name": "course", "transform": "$7::double"},
               {"name": "heading", "transform": "$8::double"}]}

    def make_csv(rows, start=0):
        x = rng.uniform(-180, 180, rows)
        y = rng.uniform(-90, 90, rows)
        day = rng.integers(1, 28, rows)
        hh = rng.integers(0, 24, rows)
        spd = rng.uniform(0, 30, rows)
        crs = rng.uniform(0, 360, rows)
        return "".join(
            f"vessel{(start + i) % 5000},{start + i},"
            f"2017-03-{day[i]:02d}T{hh[i]:02d}:15:00Z,"
            f"{x[i]:.5f},{y[i]:.5f},{spd[i]:.2f},{crs[i]:.2f},"
            f"{crs[i]:.1f}\n"
            for i in range(rows))

    text = make_csv(n)
    sft = parse_spec("ais16", spec)
    conv = converter_for(sft, cfg)

    def fsyncs():
        return metrics.snapshot()["counters"].get("wal.fsyncs", 0)

    def groups():
        return metrics.snapshot()["counters"].get("ingest.groups", 0)

    # -- (A) sustained throughput: scalar per-write vs firehose -----------
    import gc

    d1 = tempfile.mkdtemp(prefix="geomesa-ingest16-scalar-")
    try:
        ds = InMemoryDataStore(durable_dir=d1, wal_fsync="interval")
        ds.create_schema(parse_spec("ais16", spec))
        # both timed legs run GC-quiesced: a threshold collection over
        # the other leg's surviving heap would bill one side for the
        # other's garbage (observed: a 2x swing on the second leg)
        gc.collect()
        gc.disable()
        INGEST_VECTORIZED.thread_local_set("false")
        try:
            ctx = EvaluationContext()
            fs0, t0 = fsyncs(), time.perf_counter()
            writes = 0
            for batch, _ in conv.iter_batches(text, ctx,
                                              batch_rows=baseline_chunk):
                ds.write("ais16", batch)
                writes += 1
            scalar_s = time.perf_counter() - t0
            scalar_fsyncs = fsyncs() - fs0
        finally:
            INGEST_VECTORIZED.thread_local_set(None)
            gc.enable()
        ok_scalar = ds.count("ais16") == ctx.success
        ds.close()
    finally:
        shutil.rmtree(d1, ignore_errors=True)

    d2 = tempfile.mkdtemp(prefix="geomesa-ingest16-vec-")
    read_ds = None
    try:
        ds = InMemoryDataStore(durable_dir=d2, wal_fsync="interval")
        ds.create_schema(parse_spec("ais16", spec))
        ctx = EvaluationContext()
        gc.collect()
        gc.disable()
        try:
            fs0, g0, t0 = fsyncs(), groups(), time.perf_counter()
            staged = 0
            with IngestPipeline(ds) as pipe:
                for batch, _ in conv.iter_batches(text, ctx,
                                                  batch_rows=fast_chunk):
                    pipe.write("ais16", batch)
                    staged += 1
                pipe.flush()
                vec_s = time.perf_counter() - t0
                vec_fsyncs, vec_groups = fsyncs() - fs0, groups() - g0
        finally:
            gc.enable()
        ok_vec = ds.count("ais16") == ctx.success
        read_ds = ds  # part B reads the freshly ingested store
    finally:
        pass  # d2 cleaned after part B

    speedup = scalar_s / vec_s
    out = {
        "rows": n,
        "scalar_per_write": {
            "chunk_rows": baseline_chunk, "ingest_s": round(scalar_s, 3),
            "rows_per_s": round(n / scalar_s, 1), "writes": writes,
            "fsyncs": scalar_fsyncs},
        "vectorized_group_commit": {
            "chunk_rows": fast_chunk, "ingest_s": round(vec_s, 3),
            "rows_per_s": round(n / vec_s, 1), "staged_batches": staged,
            "groups": vec_groups, "fsyncs": vec_fsyncs},
        "speedup": round(speedup, 2),
        "rows_exact": bool(ok_scalar and ok_vec),
    }

    # -- (B) c=32 reads, idle vs against a live ingest --------------------
    def mk_queries(m, seed):
        q_rng = np.random.default_rng(seed)
        qs = []
        for _ in range(m):
            x0 = float(q_rng.uniform(-150, 110))
            y0 = float(q_rng.uniform(-70, 45))
            qs.append(Query("ais16",
                            f"BBOX(geom, {x0:.4f}, {y0:.4f}, "
                            f"{x0 + 40:.4f}, {y0 + 25:.4f})"))
        return qs

    def read_battery(seed):
        lat: list[float] = []
        lock = threading.Lock()

        def worker(wid):
            qs = mk_queries(read_rounds, seed + wid)
            mine = []
            for q in qs:
                t0 = time.perf_counter()
                read_ds.query(q)
                mine.append(time.perf_counter() - t0)
            with lock:
                lat.extend(mine)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(c_read)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return _pcts(lat)

    read_ds.query(mk_queries(1, 5)[0])  # warm the plan path
    idle = read_battery(seed=1000)

    stop = threading.Event()
    ingest_text = make_csv(min(n, 200_000), start=n)

    def pump():
        with IngestPipeline(read_ds) as pipe:
            while not stop.is_set():
                c2 = EvaluationContext()
                for batch, _ in conv.iter_batches(ingest_text, c2,
                                                  batch_rows=fast_chunk):
                    if stop.is_set():
                        break
                    pipe.write("ais16", batch)
                pipe.flush()

    pumper = threading.Thread(target=pump, daemon=True)
    pumper.start()
    try:
        loaded = read_battery(seed=2000)
    finally:
        stop.set()
        pumper.join(timeout=30)
    read_ds.close()
    shutil.rmtree(d2, ignore_errors=True)

    ratio = loaded["p99"] / max(idle["p99"], 1e-9)
    out["reads_under_ingest"] = {
        "concurrency": c_read,
        "idle_p99_ms": round(idle["p99"] * 1e3, 3),
        "loaded_p99_ms": round(loaded["p99"] * 1e3, 3),
        "idle_p50_ms": round(idle["p50"] * 1e3, 3),
        "loaded_p50_ms": round(loaded["p50"] * 1e3, 3),
        "p99_ratio": round(ratio, 2),
        "bounded": bool(ratio < 10.0),
    }

    # -- (C) kill mid-ingest: the copy must hold every acked row ----------
    d3 = tempfile.mkdtemp(prefix="geomesa-ingest16-kill-")
    img = tempfile.mkdtemp(prefix="geomesa-ingest16-img-")
    try:
        ds = InMemoryDataStore(durable_dir=d3, wal_fsync="always")
        ds.create_schema(parse_spec("ais16", spec))
        kill_text = make_csv(kill_rows)
        acked_rows = 0
        acks = []
        with IngestPipeline(ds, group_rows=8192) as pipe:
            ctx = EvaluationContext()
            for batch, _ in conv.iter_batches(kill_text, ctx,
                                              batch_rows=1024):
                acks.append((pipe.write("ais16", batch), batch.n))
                if len(acks) >= (kill_rows // 1024) // 2:
                    break
            # the kill image: copy the live dir with the writer thread
            # still running; only rows acked BEFORE the copy may be
            # claimed (an acked row is journaled + fsynced)
            acked_rows = sum(b for a, b in acks if a is not None and a.done)
            shutil.copytree(d3, img, dirs_exist_ok=True)
        ds.close()
        ds2 = InMemoryDataStore(durable_dir=img, wal_fsync="always")
        recovered = ds2.count("ais16")
        ds2.close()
        out["kill_recovery"] = {
            "acked_rows_at_kill": int(acked_rows),
            "recovered_rows": int(recovered),
            "zero_acked_loss": bool(recovered >= acked_rows),
        }
    finally:
        shutil.rmtree(d3, ignore_errors=True)
        shutil.rmtree(img, ignore_errors=True)

    out["gates_pass"] = bool(
        out["speedup"] >= 5.0 and out["rows_exact"]
        and out["reads_under_ingest"]["bounded"]
        and out["kill_recovery"]["zero_acked_loss"])
    return out


# -- config 17: observability — tracing overhead + audit completeness -----

def bench_config17(rng, n=None, c=None, nq=None, slow_s=None):
    """What the observability plane costs and proves, in three gates.

    (A) Overhead: ``c`` concurrent web clients stream a mixed read
        workload (bbox query / count alternating) twice — tracing
        fully off (sample=0, slow=0) then fully on (sample=1.0, every
        trace kept, audit enriched) — p50/p99 must regress under 5%.
    (B) Slow-query always-capture: with sampling OFF and the slow
        threshold low, a deliberately stalled request must land in the
        ring anyway, its trace showing >= 4 distinct span kinds (web,
        batcher-wait, dispatch, store-scan).
    (C) Audit completeness: the store recorded exactly one enriched
        event per query across both phases; every traced-phase event's
        trace id resolves in the ring; the Prometheus exposition
        parses line-by-line.
    """
    import threading

    from geomesa_tpu.audit import AuditLogger
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.obs import tracer
    from geomesa_tpu.obs.trace import TRACE_SAMPLE, TRACE_SLOW_MS
    from geomesa_tpu.scan.registry import batcher_registry
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web.server import GeoMesaWebServer

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_OBS_N", 200_000))
    c = int(c if c is not None else 32)
    nq = int(nq if nq is not None else 25)
    slow = float(slow_s if slow_s is not None else 0.25)
    out = {"n": n, "clients": c, "queries_per_client": nq}

    # only whitelisted hints cross the REST wire, so the straggler is
    # marked by a sentinel bbox coordinate no workload rect ever uses
    stall_mark = "-179.25"

    class StallStore(InMemoryDataStore):
        """Sleeps on a marked query so the slow-capture phase has a
        deterministic straggler."""

        def query(self, q, *args, **kwargs):
            if stall_mark in str(getattr(q, "filter", "")):
                time.sleep(slow)
            return super().query(q, *args, **kwargs)

    audit = AuditLogger()
    ds = StallStore(audit=audit)
    ds.create_schema(parse_spec("obs17",
                                "dtg:Date,*geom:Point:srid=4326"))
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("obs17", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})
    del x, y, ms

    def bbox_q(i, w=4.0, h=4.0):
        x0 = -170.0 + (i * 37) % 330
        y0 = -80.0 + (i * 23) % 150
        return Query("obs17",
                     f"BBOX(geom, {x0}, {y0}, {x0 + w}, {y0 + h})")

    def run_phase(server):
        """c clients, nq mixed reads each; returns latency samples."""
        lat: list = [None] * (c * nq)
        barrier = threading.Barrier(c)

        def worker(ci):
            client = RemoteDataStore("127.0.0.1", server.port,
                                     hedge=False)
            barrier.wait()
            for j in range(nq):
                k = ci * nq + j
                t0 = time.perf_counter()
                if j % 2:
                    client.query_count(bbox_q(k))
                else:
                    client.query(bbox_q(k))
                lat[k] = time.perf_counter() - t0

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True) for i in range(c)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(v is None for v in lat), "config 17 phase stuck"
        return lat

    batcher_registry.clear()
    tracer.clear()
    server = GeoMesaWebServer(ds).start()
    try:
        # warmup compiles the scan kernels AND materializes every rect
        # both phases will ask for: off/on then compare like against
        # like (cache-hit serving, the tier's steady state) instead of
        # charging phase off the cold misses
        warm = RemoteDataStore("127.0.0.1", server.port, hedge=False)
        for k in range(c * nq):
            if k % 2:
                warm.query_count(bbox_q(k))
            else:
                warm.query(bbox_q(k))

        # -- phase A: instrumentation off vs fully on ---------------------
        TRACE_SAMPLE.set("0")
        TRACE_SLOW_MS.set("0")
        ev0 = len(audit.query())
        try:
            lat_off = run_phase(server)
        finally:
            TRACE_SAMPLE.set(None)
            TRACE_SLOW_MS.set(None)
        ev_off = len(audit.query()) - ev0

        TRACE_SAMPLE.set("1.0")
        ev1 = len(audit.query())
        try:
            lat_on = run_phase(server)
        finally:
            TRACE_SAMPLE.set(None)
        ev_on = len(audit.query()) - ev1
        traced_events = list(audit.query())[ev1:]

        po, pn = _pcts(lat_off), _pcts(lat_on)
        out["instrumentation_off"] = {
            "p50_ms": round(po["p50"] * 1e3, 2),
            "p99_ms": round(po["p99"] * 1e3, 2)}
        out["instrumentation_on"] = {
            "p50_ms": round(pn["p50"] * 1e3, 2),
            "p99_ms": round(pn["p99"] * 1e3, 2)}
        out["overhead"] = {
            "p50_pct": round((pn["p50"] / max(po["p50"], 1e-9) - 1)
                             * 100, 2),
            "p99_pct": round((pn["p99"] / max(po["p99"], 1e-9) - 1)
                             * 100, 2)}
        out["overhead_under_5pct"] = bool(
            pn["p50"] <= po["p50"] * 1.05
            and pn["p99"] <= po["p99"] * 1.05)

        # resolve traced-phase audit ids against the ring BEFORE phase
        # B clears it
        resolvable = 0
        for e in traced_events:
            if e.trace_id and tracer.get(e.trace_id) is not None:
                resolvable += 1

        # -- phase B: slow-query always-capture (sampling off) ------------
        tracer.clear()
        TRACE_SAMPLE.set("0")
        TRACE_SLOW_MS.set(str(int(slow * 1e3 / 2)))
        try:
            # a rect no phase-A client asked for: the stall must reach
            # the store, not the materialized result cache
            sq = Query("obs17", f"BBOX(geom, {stall_mark}, -80.25, "
                                "-175.25, -76.25)")
            client = RemoteDataStore("127.0.0.1", server.port,
                                     hedge=False)
            client.query(sq)
            # the server-side web trace is the one the ring must hold
            caught = [t for t in tracer.traces()
                      if t["root_kind"] in ("web", "batcher-wait")]
            kinds = set()
            for t in caught:
                kinds.update(t["kinds"])
            out["slow_capture"] = {
                "captured": bool(caught),
                "span_kinds": sorted(kinds),
                "four_kinds": bool(len(kinds) >= 4)}
        finally:
            TRACE_SAMPLE.set(None)
            TRACE_SLOW_MS.set(None)

        # -- phase C: audit completeness + prometheus parse ---------------
        prom = server.handle("GET", "/rest/metrics",
                             {"format": ["prometheus"]}, None)[2]
        prom_ok = all(
            ln.startswith("#") or (" " in ln and not ln[0].isspace())
            for ln in prom.splitlines() if ln)
        out["audit"] = {
            "queries": c * nq,
            "events_off": ev_off, "events_on": ev_on,
            "one_event_per_query": bool(
                ev_off == c * nq and ev_on == c * nq),
            "traced_ids_resolvable": resolvable,
            "all_resolvable": bool(resolvable == len(traced_events)),
            "prometheus_parses": prom_ok}
    finally:
        server.stop()
        batcher_registry.clear()
        tracer.clear()

    out["gates_pass"] = bool(
        out["overhead_under_5pct"]
        and out["slow_capture"]["four_kinds"]
        and out["audit"]["one_event_per_query"]
        and out["audit"]["all_resolvable"]
        and out["audit"]["prometheus_parses"])
    return out


# -- config 18: runtime health plane — overhead, stalls, burn reaction ----

def bench_config18(rng, n=None, c=None, nq=None, stall_s=None):
    """What the runtime health plane costs and proves, in three gates.

    (A) Overhead: ``c`` concurrent web clients stream a mixed read
        workload twice — health plane fully OFF (profiler hz 0, SLO
        engine disabled, runtime collector disabled, watchdog factor
        0) then fully ON (19Hz sampler, SLO recording + evaluation,
        runtime telemetry, watchdog armed) — p50/p99 must regress
        under 5%, and the ON phase must leave real data on all three
        surfaces (profiler samples, runtime dispatch rows, SLO routes).
    (B) Stall capture: a two-group cluster scatters to a healthy
        in-memory shard and a remote shard behind a ChaosProxy whose
        every connection stalls; the watchdog must capture the stuck
        scatter leg mid-flight with a non-empty live Python stack.
    (C) Burn reaction: a 503 storm against a ``max_inflight=1`` server
        trips the availability fast-burn on shortened windows; with
        ``geomesa.slo.react`` on the shared retry/hedge budget capacity
        measurably drops, and once the burn clears every touched knob
        override is restored EXACTLY (including not-set).
    """
    import threading

    from geomesa_tpu.cluster import ClusterDataStore
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.obs.prof import (PROF_HZ, WATCHDOG_FACTOR,
                                      WATCHDOG_MIN_MS, profiler, watchdog)
    from geomesa_tpu.obs.runtime import RUNTIME_ENABLED, runtime
    from geomesa_tpu.obs.slo import (SLO_ENABLED, SLO_REACT,
                                     SLO_WINDOWS_FAST, slo_engine)
    from geomesa_tpu.resilience import ChaosProxy
    from geomesa_tpu.resilience.policy import (RETRY_BUDGET_SCALE,
                                               RetryBudget)
    from geomesa_tpu.scan.batcher import BATCH_LINGER_MICROS
    from geomesa_tpu.scan.registry import batcher_registry
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.web.server import GeoMesaWebServer

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_HEALTH_N", 200_000))
    c = int(c if c is not None else 32)
    nq = int(nq if nq is not None else 25)
    stall = float(stall_s if stall_s is not None else 0.6)
    out = {"n": n, "clients": c, "queries_per_client": nq}

    hold_mark = "-178.125"   # sentinel bbox coord: phase C's held query

    class HoldStore(InMemoryDataStore):
        """Parks a marked query on an event so phase C can pin the
        server's single inflight slot for the storm's duration."""

        def __init__(self):
            super().__init__()
            self.hold = threading.Event()

        def query(self, q, *args, **kwargs):
            if hold_mark in str(getattr(q, "filter", "")):
                assert self.hold.wait(60.0), "config 18 hold leaked"
            return super().query(q, *args, **kwargs)

    sft = parse_spec("health18", "dtg:Date,*geom:Point:srid=4326")
    ds = HoldStore()
    ds.create_schema(sft)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("health18", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})

    def bbox_q(i, w=4.0, h=4.0):
        x0 = -170.0 + (i * 37) % 330
        y0 = -80.0 + (i * 23) % 150
        return Query("health18",
                     f"BBOX(geom, {x0}, {y0}, {x0 + w}, {y0 + h})")

    def run_phase(server):
        lat: list = [None] * (c * nq)
        barrier = threading.Barrier(c)

        def worker(ci):
            client = RemoteDataStore("127.0.0.1", server.port,
                                     hedge=False)
            barrier.wait()
            for j in range(nq):
                k = ci * nq + j
                t0 = time.perf_counter()
                if j % 2:
                    client.query_count(bbox_q(k))
                else:
                    client.query(bbox_q(k))
                lat[k] = time.perf_counter() - t0

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True) for i in range(c)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(v is None for v in lat), "config 18 phase stuck"
        return lat

    def plane(on: bool):
        """Flip the whole health plane: profiler, SLO, runtime
        telemetry, watchdog. ``None`` restores the (on) defaults."""
        PROF_HZ.set(None if on else "0")
        SLO_ENABLED.set(None if on else "false")
        RUNTIME_ENABLED.set(None if on else "false")
        WATCHDOG_FACTOR.set(None if on else "0")

    # -- phase A: health plane off vs fully on ----------------------------
    batcher_registry.clear()
    slo_engine.clear()
    watchdog.clear()
    runtime.clear()
    profiler.clear()
    server = GeoMesaWebServer(ds).start()
    try:
        # warmup compiles the scan kernels and materializes every rect
        # both phases ask for: compare steady state against steady state
        warm = RemoteDataStore("127.0.0.1", server.port, hedge=False)
        for k in range(c * nq):
            if k % 2:
                warm.query_count(bbox_q(k))
            else:
                warm.query(bbox_q(k))

        plane(on=False)
        try:
            lat_off = run_phase(server)
        finally:
            plane(on=True)
        lat_on = run_phase(server)

        po, pn = _pcts(lat_off), _pcts(lat_on)
        out["health_off"] = {"p50_ms": round(po["p50"] * 1e3, 2),
                             "p99_ms": round(po["p99"] * 1e3, 2)}
        out["health_on"] = {"p50_ms": round(pn["p50"] * 1e3, 2),
                            "p99_ms": round(pn["p99"] * 1e3, 2)}
        out["overhead"] = {
            "p50_pct": round((pn["p50"] / max(po["p50"], 1e-9) - 1)
                             * 100, 2),
            "p99_pct": round((pn["p99"] / max(po["p99"], 1e-9) - 1)
                             * 100, 2)}
        out["overhead_under_5pct"] = bool(
            pn["p50"] <= po["p50"] * 1.05
            and pn["p99"] <= po["p99"] * 1.05)

        snap = runtime.snapshot()
        slo_routes = slo_engine.status().get("routes", {})
        out["surfaces"] = {
            "profiler_samples": profiler.stats()["samples"],
            # fused-dispatch rows need real coalescing pressure; at
            # full c=32 they populate, at toy sizes they may not —
            # reported, not gated
            "runtime_dispatch_domains": sorted(snap["dispatch"]),
            "runtime_compile_domains": sorted(snap["compile"]),
            "slo_routes": sorted(slo_routes),
            "all_live": bool(profiler.stats()["samples"] > 0
                             and slo_routes)}
    finally:
        server.stop()
        batcher_registry.clear()

    # -- phase B: ChaosProxy-stalled scatter leg hits the watchdog --------
    slo_engine.clear()
    watchdog.clear()
    backend = InMemoryDataStore()
    srv2 = GeoMesaWebServer(backend).start()
    proxy = ChaosProxy("127.0.0.1", srv2.port, seed=18,
                       slow_rate=0.0, slow_s=stall).start()
    WATCHDOG_MIN_MS.set("50")
    # the stall probe must REACH the proxied leg: pin the Z-range
    # planner off so the selective probe box is not pruned away from it
    from geomesa_tpu.cluster.coordinator import CLUSTER_PRUNE
    CLUSTER_PRUNE.set("false")
    try:
        cluster = ClusterDataStore(
            [InMemoryDataStore(),
             RemoteDataStore(proxy.host, proxy.port, hedge=False)],
            names=["mem", "proxied"], leg_deadline_s=60)
        cluster.create_schema(sft)
        nb = min(n, 10_000)
        cluster.write("health18", FeatureBatch.from_dict(
            sft, np.arange(nb).astype(str).astype(object),
            {"dtg": ms[:nb], "geom": (x[:nb], y[:nb])}))
        # healthy warmup teaches the watchdog each leg's p99
        for i in range(8):
            cluster.query_count(bbox_q(i), "health18")

        proxy.slow_rate = 1.0
        hit: list = []
        done = threading.Event()

        def stalled_query():
            try:
                cluster.query_count(bbox_q(99), "health18")
            finally:
                done.set()

        t = threading.Thread(target=stalled_query, daemon=True)
        t.start()
        deadline = time.perf_counter() + max(stall * 10, 5.0)
        while time.perf_counter() < deadline:
            watchdog.check()
            hit = [s for s in watchdog.stalls()
                   if s["key"] == "scatter-leg.proxied"]
            if hit:
                break
            time.sleep(0.005)
        done.wait(max(stall * 10, 5.0))
        t.join(5.0)
        proxy.slow_rate = 0.0
        out["stall_capture"] = {
            "captured": bool(hit),
            "key": hit[0]["key"] if hit else None,
            "stack_depth": len(hit[0]["stack"]) if hit else 0,
            "threshold_ms": round(hit[0]["threshold_s"] * 1e3, 1)
            if hit else None,
            "non_empty_stack": bool(hit and hit[0]["stack"])}
    finally:
        CLUSTER_PRUNE.set(None)
        WATCHDOG_MIN_MS.set(None)
        proxy.stop()
        srv2.stop()
        watchdog.clear()

    # -- phase C: 503 storm -> fast-burn -> react tightens, then restores -
    slo_engine.clear()
    SLO_WINDOWS_FAST.set("1:10:14.4")   # real-time-friendly windows
    SLO_REACT.set("true")
    rb = RetryBudget(capacity=10.0)
    scale_before = RETRY_BUDGET_SCALE.get_override()
    linger_before = BATCH_LINGER_MICROS.get_override()
    cap_before = rb.effective_capacity()
    srv3 = GeoMesaWebServer(ds, max_inflight=1).start()
    try:
        holder = threading.Thread(
            target=lambda: RemoteDataStore(
                "127.0.0.1", srv3.port, hedge=False).query(
                    Query("health18", f"BBOX(geom, {hold_mark}, -80.125,"
                                      " -174.125, -76.125)")),
            daemon=True)
        holder.start()
        deadline = time.perf_counter() + 10.0
        while srv3._inflight < 1 and time.perf_counter() < deadline:
            time.sleep(0.002)

        import http.client as _hc
        sheds = 0
        for _ in range(24):
            conn = _hc.HTTPConnection("127.0.0.1", srv3.port, timeout=10)
            try:
                conn.request("GET", "/rest/schemas")
                sheds += int(conn.getresponse().status == 503)
            finally:
                conn.close()
        states = slo_engine.evaluate()
        fired = any(s["fast_firing"] for s in states.values())
        cap_during = rb.effective_capacity()
        scale_during = RETRY_BUDGET_SCALE.get_override()
        linger_during = BATCH_LINGER_MICROS.get_override()

        ds.hold.set()
        holder.join(10.0)
        time.sleep(1.3)   # the 1s short window drains
        states = slo_engine.evaluate()
        cleared = not any(s["fast_firing"] for s in states.values())
        cap_after = rb.effective_capacity()
        restored = (RETRY_BUDGET_SCALE.get_override() == scale_before
                    and BATCH_LINGER_MICROS.get_override() == linger_before)
        out["burn_react"] = {
            "sheds": sheds,
            "fast_burn_fired": bool(fired),
            "budget_capacity": {"before": cap_before,
                                "during": cap_during,
                                "after": cap_after},
            "scale_override_during": scale_during,
            "linger_override_during": linger_during,
            "budget_tightened": bool(cap_during < cap_before),
            "cleared": bool(cleared),
            "restored_exactly": bool(restored
                                     and cap_after == cap_before)}
    finally:
        ds.hold.set()
        SLO_WINDOWS_FAST.set(None)
        SLO_REACT.set(None)
        srv3.stop()
        slo_engine.clear()
        batcher_registry.clear()
        runtime.clear()

    out["gates_pass"] = bool(
        out["overhead_under_5pct"]
        and out["surfaces"]["all_live"]
        and out["stall_capture"]["non_empty_stack"]
        and out["burn_react"]["fast_burn_fired"]
        and out["burn_react"]["budget_tightened"]
        and out["burn_react"]["restored_exactly"])
    return out


# -- config 19: distributed SQL — partial-aggregate pushdown ---------------

def bench_config19(rng, n=None, reps=None):
    """What partial-aggregate pushdown buys over coordinator
    materialization through ONE SQL frontend.

    Phase 1 — grouped/ungrouped aggregates on a 4-group cluster, three
    ways: `single` (one store holding all rows — the reference),
    `cluster_pull` (kill switch off: every leg ships its ROWS and the
    coordinator concatenates + aggregates — the pre-pushdown path),
    and `distributed` (each leg reduces locally, the coordinator
    merges per-group partials). Every statement is checked row-exact
    against the single-store oracle; the 2x gate is pushdown vs the
    pull path it replaces.

    Phase 2 — broadcast spatial join (small polygon side shipped to
    each leg, fused kernels per shard, psum/by-key merge) vs the same
    join over pulled rows, count- and group-exact.

    Phase 3 — leg-kill probe: one group hard down; every statement
    must yield a typed ShardUnavailableError (knob off) or a flagged
    `complete=False` merge (knob on). Never a silent wrong answer."""
    from geomesa_tpu.cluster import ClusterDataStore, ShardUnavailableError
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.geometry import Polygon
    from geomesa_tpu.sql import SqlEngine
    from geomesa_tpu.sql.distributed import SQL_DISTRIBUTED
    from geomesa_tpu.store import InMemoryDataStore

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_SQL_N", 2_000_000))
    reps = reps if reps is not None else max(TRIALS, 3)
    sft = parse_spec("pts19", "*geom:Point:srid=4326,name:String,"
                              "val:Integer")
    ids = np.arange(n).astype(str).astype(object)
    names = np.array([f"grp{i}" for i in range(32)], dtype=object)
    batch = FeatureBatch.from_dict(sft, ids, {
        "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)),
        "name": names[rng.integers(0, len(names), n)],
        "val": rng.permutation(n).astype(np.int64),
    })
    zsft = parse_spec("zones19", "*geom:Polygon:srid=4326,zname:String")

    def _box(x0, y0, w, h):
        return Polygon(np.array([[x0, y0], [x0 + w, y0],
                                 [x0 + w, y0 + h], [x0, y0 + h],
                                 [x0, y0]], float))

    zb = FeatureBatch.from_dict(
        zsft, np.array([f"z{i}" for i in range(16)], dtype=object),
        {"geom": np.array([_box(-160 + 20 * (i % 16), -60 + 30 * (i // 8),
                                15, 25) for i in range(16)], dtype=object),
         "zname": np.array([f"zone{i}" for i in range(16)], dtype=object)})

    oracle = InMemoryDataStore()
    groups = [InMemoryDataStore() for _ in range(4)]
    cluster = ClusterDataStore(groups, leg_deadline_s=120)
    for st in (oracle, cluster):
        st.create_schema(sft)
        st.write("pts19", batch)
        st.create_schema(zsft)
        st.write("zones19", zb)
    oe, ce = SqlEngine(oracle), SqlEngine(cluster)

    AGG = [
        "SELECT name, COUNT(*), SUM(val), MIN(val), MAX(val), AVG(val) "
        "FROM pts19 GROUP BY name",
        "SELECT name, COUNT(*) AS cnt FROM pts19 GROUP BY name "
        "ORDER BY cnt DESC LIMIT 5",
        "SELECT COUNT(*), SUM(val), AVG(val) FROM pts19",
        "SELECT name, ST_Extent(geom) FROM pts19 GROUP BY name",
    ]
    JOIN = [
        "SELECT COUNT(*) FROM pts19 p "
        "JOIN zones19 z ON ST_Contains(z.geom, p.geom)",
        "SELECT z.zname, COUNT(*) FROM pts19 p "
        "JOIN zones19 z ON ST_Contains(z.geom, p.geom) GROUP BY z.zname",
    ]

    def _canon(res):
        return sorted(tuple(map(str, r)) for r in res.rows())

    def _run(engine, stmts):
        t0 = time.perf_counter()
        out = [engine.query(s) for s in stmts]
        return time.perf_counter() - t0, out

    def _phase(stmts):
        want = [_canon(oe.query(s)) for s in stmts]
        # warm both paths once, then time
        timings = {}
        exact = True
        modes = []
        for label, knob in (("single", None), ("cluster_pull", "false"),
                            ("distributed", None)):
            eng = oe if label == "single" else ce
            if knob is not None:
                SQL_DISTRIBUTED.set(knob)
            try:
                _run(eng, stmts)  # warm
                samples = []
                for _ in range(reps):
                    dt, res = _run(eng, stmts)
                    samples.append(dt)
                exact = exact and all(
                    _canon(r) == w for r, w in zip(res, want))
                if label == "distributed":
                    modes = [r.plan["mode"] for r in res]
                timings[label] = _p50(samples)
            finally:
                if knob is not None:
                    SQL_DISTRIBUTED.set(None)
        return {
            "single_s": round(timings["single"], 4),
            "cluster_pull_s": round(timings["cluster_pull"], 4),
            "distributed_s": round(timings["distributed"], 4),
            "speedup_vs_pull": round(
                timings["cluster_pull"] / timings["distributed"], 2),
            "exact": bool(exact),
            "plan_modes": sorted(set(modes)),
            "statements": len(stmts),
        }

    out = {"n": n, "groups": 4, "reps": reps}
    out["aggregate"] = _phase(AGG)
    out["join"] = _phase(JOIN)
    cluster.close()

    # -- phase 3: leg-kill probe — typed-or-flagged only ------------------
    class _Down:
        def close(self):
            pass

        def __getattr__(self, key):
            def boom(*a, **kw):
                raise ConnectionError("bench: injected shard loss")
            return boom

    probe = AGG[:2] + JOIN[:1]
    m = min(n, max(n // 100, 10_000))
    sub = batch.take(np.arange(m))
    typed = flagged = wrong = 0
    for allow in (False, True):
        wounded = ClusterDataStore(
            [InMemoryDataStore() for _ in range(4)], allow_partial=allow)
        wounded.create_schema(sft)
        wounded.write("pts19", sub)
        wounded.create_schema(zsft)
        wounded.write("zones19", zb)
        wounded._groups[2] = _Down()
        we = SqlEngine(wounded)
        for stmt in probe:
            try:
                res = we.query(stmt)
                if res.complete is False and res.missing_groups:
                    flagged += 1
                else:
                    wrong += 1
            except ShardUnavailableError:
                typed += 1
        wounded.close()
    out["partial"] = {
        "queries": 2 * len(probe),
        "typed_errors_knob_off": typed,
        "partial_flagged_knob_on": flagged,
        "silently_wrong": wrong,
        "typed_or_flagged_only": bool(
            wrong == 0 and typed == len(probe) and flagged == len(probe)),
    }

    out["gates_pass"] = bool(
        out["aggregate"]["exact"] and out["join"]["exact"]
        and out["aggregate"]["speedup_vs_pull"] >= 2.0
        and out["partial"]["typed_or_flagged_only"])
    return out


# -- config 20: cost-based planner — Z-pruning + strategy crossover -------

def bench_config20(rng, n=None, reps=None):
    """What the cost-based planner buys on cluster reads and SQL.

    Phase 1 — Z-range leg pruning at 1/2/4 groups over a
    selective-vs-broad bbox mix: qps with `geomesa.cluster.prune` on
    vs off, per-query legs-contacted accounting from the coordinator
    plan surface, and an id-exactness gate (every pruned answer must
    match the unpruned one feature-for-feature). The 2x gate is the
    selective mix at 4 groups — exactly the fan-out the pruner
    removes.

    Phase 2 — broadcast-vs-materialize crossover at the estimated
    cardinality boundary: with the threshold above the small side's
    estimate the planner must choose broadcast-join, below both
    estimates it must fall back to exact cluster-materialize, and
    both answers must match the single-store oracle."""
    from geomesa_tpu.cluster import ClusterDataStore
    from geomesa_tpu.cluster.coordinator import CLUSTER_PRUNE
    from geomesa_tpu.cluster.partition import ZPrefixPartitioner
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.geometry import Polygon
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.sql import SqlEngine
    from geomesa_tpu.sql.distributed import SQL_BROADCAST_ROWS
    from geomesa_tpu.store import InMemoryDataStore

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_PLANNER_N", 500_000))
    reps = reps if reps is not None else max(TRIALS, 3)
    sft = parse_spec("pts20", "*geom:Point:srid=4326,name:String,"
                              "val:Integer")
    ids = np.arange(n).astype(str).astype(object)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    names = np.array([f"grp{i}" for i in range(16)], dtype=object)
    batch = FeatureBatch.from_dict(sft, ids, {
        "geom": (x, y),
        "name": names[rng.integers(0, len(names), n)],
        "val": rng.permutation(n).astype(np.int64),
    })

    # selective boxes: small, centered on data points, and PROVABLY
    # single-group at 4 groups (the analytic z-range intersection the
    # pruner computes — the acceptance shape: 1 bbox -> 1 leg)
    part4 = ZPrefixPartitioner(4)
    selective = []
    for i in rng.permutation(n)[:4000]:
        box = (x[i] - 1.5, y[i] - 1.5, x[i] + 1.5, y[i] + 1.5)
        if len(part4.groups_for_ranges(
                part4.covering_ranges([box]))) == 1:
            selective.append(box)
            if len(selective) == 16:
                break
    broad = [(-120.0 + 10 * i, -60.0, 40.0 + 10 * i, 60.0)
             for i in range(4)]

    def _bbox_q(b):
        return Query("pts20", f"BBOX(geom, {b[0]}, {b[1]}, {b[2]}, "
                              f"{b[3]})")

    def _mix(cluster, boxes):
        """One pass over the mix: (elapsed_s, ids_per_box,
        legs_contacted_total)."""
        t0 = time.perf_counter()
        got, legs = [], 0
        for b in boxes:
            res = cluster.query(_bbox_q(b))
            got.append(sorted(res.ids))
            legs += len(cluster.last_plan()["contacted"])
        return time.perf_counter() - t0, got, legs

    out = {"n": n, "reps": reps,
           "selective_boxes": len(selective), "broad_boxes": len(broad)}
    for n_groups in (1, 2, 4):
        cluster = ClusterDataStore(
            [InMemoryDataStore() for _ in range(n_groups)],
            leg_deadline_s=120)
        cluster.create_schema(sft)
        cluster.write("pts20", batch)
        row = {}
        for label, boxes in (("selective", selective), ("broad", broad)):
            per = {}
            exact = True
            for knob in ("off", "on"):
                CLUSTER_PRUNE.set("false" if knob == "off" else None)
                try:
                    _mix(cluster, boxes)  # warm
                    samples, legs = [], 0
                    for _ in range(reps):
                        dt, got, legs = _mix(cluster, boxes)
                        samples.append(dt)
                    per[knob] = {"qps": round(len(boxes)
                                              / _p50(samples), 1),
                                 "legs_contacted": legs}
                    if knob == "off":
                        want = got
                    else:
                        exact = exact and got == want
                finally:
                    CLUSTER_PRUNE.set(None)
            row[label] = {
                "qps_unpruned": per["off"]["qps"],
                "qps_pruned": per["on"]["qps"],
                "speedup": round(per["on"]["qps"]
                                 / per["off"]["qps"], 2),
                "legs_unpruned": per["off"]["legs_contacted"],
                "legs_pruned": per["on"]["legs_contacted"],
                "exact": bool(exact),
            }
        out[f"{n_groups}_groups"] = row
        cluster.close()

    # -- phase 2: strategy crossover at the estimate boundary -------------
    zsft = parse_spec("zones20", "*geom:Polygon:srid=4326,zname:String")

    def _box(x0, y0, w, h):
        return Polygon(np.array([[x0, y0], [x0 + w, y0],
                                 [x0 + w, y0 + h], [x0, y0 + h],
                                 [x0, y0]], float))

    zb = FeatureBatch.from_dict(
        zsft, np.array([f"z{i}" for i in range(16)], dtype=object),
        {"geom": np.array([_box(-160 + 20 * (i % 16),
                                -60 + 30 * (i // 8), 15, 25)
                           for i in range(16)], dtype=object),
         "zname": np.array([f"zone{i}" for i in range(16)],
                           dtype=object)})
    m = min(n, 100_000)
    sub = batch.take(np.arange(m))
    oracle = InMemoryDataStore()
    cluster = ClusterDataStore([InMemoryDataStore() for _ in range(4)],
                               leg_deadline_s=120)
    for st in (oracle, cluster):
        st.create_schema(sft)
        st.write("pts20", sub)
        st.create_schema(zsft)
        st.write("zones20", zb)
    stmt = ("SELECT COUNT(*) FROM pts20 p "
            "JOIN zones20 z ON ST_Contains(z.geom, p.geom)")
    want = list(SqlEngine(oracle).query(stmt).rows())
    ce = SqlEngine(cluster)
    crossover = {}
    ok = True
    for label, threshold, mode in (("above_estimate", None,
                                    "broadcast-join"),
                                   ("below_estimate", "4",
                                    "cluster-materialize")):
        SQL_BROADCAST_ROWS.set(threshold)
        try:
            res = ce.query(stmt)
        finally:
            SQL_BROADCAST_ROWS.set(None)
        cost = (res.plan or {}).get("cost", {})
        crossover[label] = {
            "mode": res.plan["mode"],
            "estimated_rows": cost.get("estimated_rows"),
            "strategy": cost.get("strategy"),
        }
        ok = (ok and res.plan["mode"] == mode
              and cost.get("estimated_rows") is not None
              and list(res.rows()) == want)
    crossover["correct"] = bool(ok)
    out["crossover"] = crossover
    oracle.close()
    cluster.close()

    out["gates_pass"] = bool(
        out["4_groups"]["selective"]["exact"]
        and out["4_groups"]["broad"]["exact"]
        and out["4_groups"]["selective"]["speedup"] >= 2.0
        and out["crossover"]["correct"])
    return out


# -- config 21: elastic topology — hot shard heals via online split -------

def bench_config21(rng, n=None, c=None, synthetic_hot_signal=False):
    """What the elastic topology buys under a hot shard.

    A 4-group cluster serves a hot-corner bbox workload at concurrency
    ``c`` through three phases: (pre) uniform data, (hot) a skewed
    ingest piles 2x the base volume into one group's corner, (post)
    the SLO-driven autoscaler — watching the real per-leg breaker
    latencies — fires an online split of the hot group at its
    key-density median and the same workload runs again. Every 4th
    query is a world-spanning bbox so all legs keep latency samples
    flowing to the autoscaler.

    Gates: the autoscaler fired on its own (an epoch-history entry
    with reason ``auto``), zero acked loss / id-exactness vs a
    single-store oracle across the flip, and the heal itself — the
    density-median split halves the hot group's rows, so the hot LEG's
    p99 (the same per-group signal the autoscaler watches; in a
    multi-process deployment, the shard server's latency) must land
    under 0.75x its hot-phase value. Client-side p50/p99 per phase are
    reported for context but not gated: in this single-process harness
    the GIL serializes the legs, so total scan work — conserved across
    a split — bounds client latency regardless of topology.

    ``synthetic_hot_signal`` (toy-size smoke runs only) feeds the
    autoscaler per-leg latencies derived from actual per-group row
    counts instead of the breaker EWMAs — at toy sizes scheduler noise
    drowns the microsecond scan-cost skew the EWMAs would need, but
    the decision loop, sustain window, split and flip all still run
    for real."""
    import threading

    from geomesa_tpu.cluster import ClusterDataStore
    from geomesa_tpu.cluster.autoscale import (RESHARD_AUTO,
                                               RESHARD_HOT_FACTOR,
                                               RESHARD_HOT_MIN_MS,
                                               RESHARD_HOT_SUSTAIN_S,
                                               Autoscaler)
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_RESHARD_N", 240_000))
    c = c if c is not None else 32
    per_thread = 6
    sft = parse_spec("pts21", "*geom:Point:srid=4326,val:Integer")
    cluster = ClusterDataStore([InMemoryDataStore() for _ in range(4)],
                               names=["g0", "g1", "g2", "g3"],
                               leg_deadline_s=120)
    oracle = InMemoryDataStore()
    for st in (cluster, oracle):
        st.create_schema(sft)

    def write_both(prefix, xs, ys):
        ids = np.array([f"{prefix}{i}" for i in range(len(xs))],
                       dtype=object)
        batch = FeatureBatch.from_dict(sft, ids, {
            "geom": (xs, ys),
            "val": np.arange(len(xs), dtype=np.int64)})
        cluster.write("pts21", batch)
        oracle.write("pts21", batch)

    write_both("u", rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))

    hot_cql = "BBOX(geom, 100, 40, 112, 46)"
    broad_cql = "BBOX(geom, -179, -89, 179, 89)"

    def measure():
        """The c-thread workload; per-query wall latencies (ms)."""
        lats, lock = [], threading.Lock()

        def worker():
            mine = []
            for i in range(per_thread):
                cql = broad_cql if i % 4 == 3 else hot_cql
                t0 = time.perf_counter()
                cluster.query(cql, "pts21")
                mine.append((time.perf_counter() - t0) * 1e3)
            with lock:
                lats.extend(mine)

        threads = [threading.Thread(target=worker) for _ in range(c)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        arr = np.asarray(lats)
        return {"p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3)}

    out = {"n": n, "concurrency": c,
           "queries_per_phase": c * per_thread}
    measure()                      # warm: jit/parse spikes stay out
    out["pre"] = measure()

    # hotspot: one group's corner takes 2x the whole base volume —
    # that leg now scans ~9x the rows of its peers
    m = 2 * n
    write_both("h", rng.uniform(100, 112, m), rng.uniform(40, 46, m))
    out["hot"] = measure()

    # the closed loop: per-leg latencies in, split out. The relative
    # threshold sits well below the hot leg's skew; the absolute floor
    # drops to zero because in-process legs serve sub-millisecond
    RESHARD_AUTO.set("true")
    RESHARD_HOT_FACTOR.set("1.5")
    RESHARD_HOT_SUSTAIN_S.set("5")
    RESHARD_HOT_MIN_MS.set("0")
    try:
        scaler = Autoscaler(cluster)
        if synthetic_hot_signal:
            scaler.observe = lambda: {
                name: group.count("pts21") * 20e-9
                for name, group in zip(cluster._names, cluster._groups)}
        obs_hot = scaler.observe()
        scaler.run_once(now=0.0)
        decision = scaler.run_once(now=6.0)
    finally:
        RESHARD_AUTO.set(None)
        RESHARD_HOT_FACTOR.set(None)
        RESHARD_HOT_SUSTAIN_S.set(None)
        RESHARD_HOT_MIN_MS.set(None)
    out["decision"] = {k: decision.get(k)
                       for k in ("action", "group", "executed",
                                 "blocked", "hot_p99_s")}
    out["post"] = measure()
    obs_post = scaler.observe()

    history = cluster.resharder.status()["history"]
    out["epoch"] = cluster._part.epoch
    out["history"] = history
    auto_fired = any(e.get("reason") == "auto" and e.get("op") == "migrate"
                     for e in history)
    got = cluster.query("INCLUDE", "pts21")
    want = oracle.query("INCLUDE", "pts21")
    exact = (set(got.ids.astype(str)) == set(want.ids.astype(str))
             and cluster.count("pts21") == oracle.count("pts21")
             and set(cluster.query(hot_cql, "pts21").ids.astype(str))
             == set(oracle.query(hot_cql, "pts21").ids.astype(str)))
    out["auto_fired"] = bool(auto_fired)
    out["exact"] = bool(exact)
    hot_group = next((e["src"] for e in history
                      if e.get("reason") == "auto"), None)
    if hot_group is None:
        hot_group = max(obs_hot, key=lambda k: obs_hot.get(k) or 0.0)
    out["hot_group"] = hot_group
    out["leg_p99_ms_hot"] = {
        k: round(v * 1e3, 3) for k, v in obs_hot.items() if v is not None}
    out["leg_p99_ms_post"] = {
        k: round(v * 1e3, 3) for k, v in obs_post.items() if v is not None}
    leg_hot = obs_hot.get(hot_group)
    leg_post = obs_post.get(hot_group)
    out["heal_ratio"] = (round(leg_post / max(leg_hot, 1e-9), 3)
                         if leg_hot is not None and leg_post is not None
                         else None)
    out["gates_pass"] = bool(auto_fired and exact
                             and out["heal_ratio"] is not None
                             and out["heal_ratio"] < 0.75)
    oracle.close()
    cluster.close()
    return out


# -- config 22: multi-tenant QoS — noisy-neighbor isolation ---------------

def bench_config22(rng, n=None, c=None, nq=None, abuse_c=None,
                   abuse_s=None):
    """What the tenant QoS plane buys a polite tenant sharing a server
    with an abusive one, in three phases.

    (A) Baseline: the polite tenant alone runs a read workload of
        ``c`` clients x ``nq`` bbox queries against one web server
        with the QoS plane ON (tokens map two tenants; the polite
        tenant has 4x the abuser's fair-share weight, the abuser has a
        tight in-flight cap and a small ingest row bucket). Every
        query's ids are checked exact against the store oracle;
        latencies give the polite-alone p99.
    (B) Abuse: ``abuse_c`` greedy clients flood the same server under
        the abuser's token — a query flood plus an ingest flood into a
        SEPARATE schema (so polite id-exactness stays meaningful) —
        while the polite tenant re-runs the identical workload. The
        headline gate: polite read p99 under abuse <= 2x the
        polite-alone baseline, still id-exact, and the abuser was
        actually throttled (sheds or row refusals observed).
    (C) Restore: the abuse stops; every tenant's in-flight count and
        row bucket must drain EXACTLY to zero and a final polite run
        must land back within the same 2x envelope.
    """
    import threading

    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.scan.registry import batcher_registry
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.store.remote import RemoteDataStore
    from geomesa_tpu.tenants import (QOS_ENABLED, WEB_AUTH_TOKENS,
                                     tenant_registry)
    from geomesa_tpu.utils.properties import SystemProperty
    from geomesa_tpu.web.server import GeoMesaWebServer

    n = int(n if n is not None
            else os.environ.get("GEOMESA_TPU_BENCH_QOS_N", 200_000))
    c = int(c if c is not None else 8)
    nq = int(nq if nq is not None else 25)
    abuse_c = int(abuse_c if abuse_c is not None else 64)
    abuse_s = float(abuse_s if abuse_s is not None else 0.0)
    out = {"n": n, "polite_clients": c, "queries_per_client": nq,
           "abuse_clients": abuse_c}

    sft = parse_spec("qos22", "dtg:Date,*geom:Point:srid=4326")
    flood_sft = parse_spec("flood22", "dtg:Date,*geom:Point:srid=4326")
    ds = InMemoryDataStore()
    ds.create_schema(sft)
    ds.create_schema(flood_sft)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY, n).astype(np.int64)
    ds.write_dict("qos22", np.arange(n).astype(str).astype(object),
                  {"dtg": ms, "geom": (x, y)})

    def bbox_q(i, w=4.0, h=4.0):
        x0 = -170.0 + (i * 37) % 330
        y0 = -80.0 + (i * 23) % 150
        return Query("qos22",
                     f"BBOX(geom, {x0}, {y0}, {x0 + w}, {y0 + h})")

    # oracle ids for every distinct box the polite workload asks for
    oracle = {k: set(ds.query(bbox_q(k)).ids.astype(str))
              for k in range(c * nq)}

    knobs = [SystemProperty("geomesa.qos.tenant.polite.weight"),
             SystemProperty("geomesa.qos.tenant.abuser.weight"),
             SystemProperty("geomesa.qos.tenant.abuser.max.inflight"),
             SystemProperty("geomesa.qos.tenant.abuser.max.inflight.rows")]

    QOS_ENABLED.set("true")
    WEB_AUTH_TOKENS.set("polite-tok:polite,abuse-tok:abuser")
    knobs[0].set("4")
    knobs[1].set("1")
    knobs[2].set("4")
    knobs[3].set("20000")
    tenant_registry.reset()
    batcher_registry.clear()
    server = GeoMesaWebServer(ds, max_inflight=128).start()

    def polite_phase():
        lat: list = [None] * (c * nq)
        exact = [True] * c
        barrier = threading.Barrier(c)

        def worker(ci):
            client = RemoteDataStore("127.0.0.1", server.port,
                                     auth_token="polite-tok", hedge=False)
            barrier.wait()
            for j in range(nq):
                k = ci * nq + j
                t0 = time.perf_counter()
                res = client.query(bbox_q(k))
                lat[k] = time.perf_counter() - t0
                if set(res.ids.astype(str)) != oracle[k]:
                    exact[ci] = False

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True) for i in range(c)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
        assert not any(v is None for v in lat), "config 22 phase stuck"
        return lat, all(exact)

    try:
        # warmup compiles the scan kernels and materializes the rects
        warm = RemoteDataStore("127.0.0.1", server.port,
                               auth_token="polite-tok", hedge=False)
        for k in range(c * nq):
            warm.query(bbox_q(k))

        # -- phase A: polite alone --------------------------------------
        lat_alone, exact_alone = polite_phase()
        pa = _pcts(lat_alone)
        out["polite_alone"] = {"p50_ms": round(pa["p50"] * 1e3, 2),
                               "p99_ms": round(pa["p99"] * 1e3, 2),
                               "ids_exact": bool(exact_alone)}

        # -- phase B: abuse flood while polite re-runs ------------------
        stop = threading.Event()
        abuse_reqs = [0] * abuse_c

        def abuser(ai):
            client = RemoteDataStore("127.0.0.1", server.port,
                                     auth_token="abuse-tok", hedge=False)
            rows = 500
            fx = np.zeros(rows)
            fy = np.zeros(rows)
            fms = np.full(rows, T0_DAY * MS_DAY, dtype=np.int64)
            seq = 0
            while not stop.is_set():
                try:
                    if ai % 2:
                        ids = np.array([f"f{ai}-{seq}-{i}"
                                        for i in range(rows)], object)
                        seq += 1
                        client.write("flood22", FeatureBatch.from_dict(
                            flood_sft, ids, {"dtg": fms,
                                             "geom": (fx, fy)}))
                    else:
                        client.query_count(bbox_q(ai, w=40.0, h=40.0))
                    abuse_reqs[ai] += 1
                except Exception:
                    # shed 503s / 429s / exhausted client retry budgets
                    # ARE the throttle working; keep hammering
                    abuse_reqs[ai] += 1
                if abuse_s:
                    time.sleep(abuse_s)

        abusers = [threading.Thread(target=abuser, args=(i,),
                                    daemon=True) for i in range(abuse_c)]
        for t in abusers:
            t.start()
        time.sleep(0.3)   # let the flood reach steady state
        lat_abuse, exact_abuse = polite_phase()
        qs = tenant_registry.status()["tenants"]
        throttled = bool(qs.get("abuser", {}).get("sheds", 0) > 0
                         or qs.get("abuser", {}).get("row_refusals", 0) > 0)
        stop.set()
        for t in abusers:
            t.join(60.0)
        pb = _pcts(lat_abuse)
        out["polite_under_abuse"] = {
            "p50_ms": round(pb["p50"] * 1e3, 2),
            "p99_ms": round(pb["p99"] * 1e3, 2),
            "ids_exact": bool(exact_abuse),
            "p99_ratio_vs_alone": round(pb["p99"] / max(pa["p99"], 1e-9),
                                        2)}
        out["abuser"] = {"requests": int(sum(abuse_reqs)),
                         "sheds": qs.get("abuser", {}).get("sheds", 0),
                         "row_refusals": qs.get("abuser", {}).get(
                             "row_refusals", 0),
                         "throttled": throttled}

        # -- phase C: abuse stops; budgets drain exactly ----------------
        deadline = time.perf_counter() + 30.0
        drained = False
        while time.perf_counter() < deadline:
            qs = tenant_registry.status()["tenants"]
            drained = all(v["inflight"] == 0 and v["inflight_rows"] == 0
                          for v in qs.values())
            if drained:
                break
            time.sleep(0.01)
        lat_after, exact_after = polite_phase()
        pc = _pcts(lat_after)
        out["restore"] = {
            "budgets_drained": bool(drained),
            "tenants": {k: {"inflight": v["inflight"],
                            "inflight_rows": v["inflight_rows"]}
                        for k, v in qs.items()},
            "polite_p99_ms": round(pc["p99"] * 1e3, 2),
            "ids_exact": bool(exact_after),
            "p99_ratio_vs_alone": round(pc["p99"] / max(pa["p99"], 1e-9),
                                        2)}
    finally:
        server.stop()
        QOS_ENABLED.set(None)
        WEB_AUTH_TOKENS.set(None)
        for k in knobs:
            k.set(None)
        tenant_registry.reset()
        batcher_registry.clear()

    out["gates_pass"] = bool(
        out["polite_alone"]["ids_exact"]
        and out["polite_under_abuse"]["ids_exact"]
        and out["polite_under_abuse"]["p99_ratio_vs_alone"] <= 2.0
        and out["abuser"]["throttled"]
        and out["restore"]["budgets_drained"]
        and out["restore"]["ids_exact"])
    return out


# -- config 23: materialized views — incremental folds vs re-execution ----

def bench_config23(rng, n=None, commit_rows=None, commits=None,
                   reps=None):
    """What incremental view maintenance buys over full re-execution.

    A standing grouped-aggregate view (COUNT/SUM/AVG/MIN/MAX over 32
    groups) rides a 1M-row table under a 1k-row/commit firehose. Each
    commit is timed end to end — the write-path fold plus a fresh read
    through the LSN-keyed cache — against the O(table) baseline of
    re-running the statement from scratch per refresh. Gates: the
    folded state stays bit-identical to from-scratch re-execution at
    the final LSN (including a delete wave exercising retraction), the
    incremental path wins by >= 5x per commit, and the kill switch off
    leaves the write path untouched and the table contents identical
    to a store that never loaded the subsystem."""
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.sql import SqlEngine
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.views import VIEWS_ENABLED, ViewRegistry

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_VIEWS_N", 1_000_000))
    commit_rows = commit_rows if commit_rows is not None else 1_000
    commits = commits if commits is not None else 20
    reps = reps if reps is not None else max(TRIALS, 3)
    sft = parse_spec("pts23", "*geom:Point:srid=4326,name:String,"
                              "val:Integer")
    names = np.array([f"grp{i}" for i in range(32)], dtype=object)

    def _batch(m, prefix):
        ids = np.array([f"{prefix}{i}" for i in range(m)], dtype=object)
        return FeatureBatch.from_dict(sft, ids, {
            "geom": (rng.uniform(-170, 170, m), rng.uniform(-80, 80, m)),
            "name": names[rng.integers(0, len(names), m)],
            "val": rng.integers(0, 1_000_000, m).astype(np.int64)})

    seed_batch = _batch(n, "s")
    ds = InMemoryDataStore()
    ds.create_schema(sft)
    ds.write("pts23", seed_batch)

    sql = ("SELECT name, COUNT(*) AS c, SUM(val) AS s, AVG(val) AS a, "
           "MIN(val) AS lo, MAX(val) AS hi FROM pts23 GROUP BY name")
    eng = SqlEngine(ds)

    def _canon(res):
        return [tuple(str(v) for v in r) for r in res.rows()]

    out = {"n": n, "commit_rows": commit_rows, "commits": commits,
           "reps": reps}

    # -- baseline: full re-execution per refresh (O(table)) ---------------
    eng.query(sql)  # warm
    samples = [_timed(lambda: eng.query(sql)) for _ in range(reps)]
    full_s = _p50(samples)

    # -- incremental: fold + cached read per firehose commit --------------
    VIEWS_ENABLED.set("true")
    try:
        reg = ViewRegistry(ds, restore=False)
        reg.register("hot23", sql)
        fire = [_batch(commit_rows, f"c{j}_") for j in range(commits)]
        inc_samples = []
        for b in fire:
            t0 = time.perf_counter()
            ds.write("pts23", b)
            reg.result("hot23")
            inc_samples.append(time.perf_counter() - t0)
        inc_s = _p50(inc_samples)

        # a delete wave exercises the retraction path before the gate
        doom = [f"c0_{i}" for i in range(min(commit_rows, 500))]
        ds.delete("pts23", doom)
        exact = _canon(reg.result("hot23")) == _canon(eng.query(sql))
        view_status = reg.get("hot23").status()
        reg.close()
    finally:
        VIEWS_ENABLED.set(None)

    # -- kill switch off: register refuses, write path untouched ----------
    off = InMemoryDataStore()
    off.create_schema(sft)
    off_reg = ViewRegistry(off, restore=False)
    try:
        off_reg.register("x", sql)
        off_refuses = False
    except ValueError:
        off_refuses = True
    off_inert = not off_reg._orig and "write" not in off.__dict__
    m = min(n, 100_000)
    off.write("pts23", seed_batch.take(np.arange(m)))
    twin = InMemoryDataStore()
    twin.create_schema(sft)
    twin.write("pts23", seed_batch.take(np.arange(m)))
    off_exact = (_canon(SqlEngine(off).query(sql))
                 == _canon(SqlEngine(twin).query(sql)))

    out.update({
        "full_reexec_s": round(full_s, 5),
        "incremental_commit_s": round(inc_s, 5),
        "speedup": round(full_s / inc_s, 2) if inc_s else float("inf"),
        "exact_after_firehose_and_deletes": bool(exact),
        "folds": view_status["folds"],
        "rows_folded": view_status["rows_folded"],
        "retraction_fallbacks": view_status["retraction_fallbacks"],
        "off_refuses": bool(off_refuses),
        "off_write_path_inert": bool(off_inert),
        "off_results_identical": bool(off_exact),
    })
    out["gates_pass"] = bool(
        exact and out["speedup"] >= 5.0 and off_refuses
        and off_inert and off_exact)
    return out


# -- config 24: online reindex under mixed load (evolve/ subsystem) -------

def bench_config24(rng, n=None, c=None, write_rows=None):
    """Online reindex of a 1M-row durable type under c=32 mixed load.

    16 writer threads append unique-id batches (tracking every acked
    id) while 16 reader threads run an exact-id ECQL query whose
    expected result set is pinned to the seed data, and the evolver
    reindexes the type from index v2 to v1 in the middle of it all.
    Gates: every reader observation is exact-or-typed (zero silent
    mismatches), no acked write is lost across the flip, the flip
    lands exactly once, and no single write stalls longer than 10 s.
    Two side legs ride along: a crash at a randomly chosen kill point
    followed by resume() that completes the migration exactly once,
    and the kill switch off leaving a twin store bit-identical."""
    import tempfile

    from geomesa_tpu.evolve import EVOLVE_ENABLED, SchemaEvolutionError
    from geomesa_tpu.features import FeatureBatch, parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    n = n if n is not None else int(
        os.environ.get("GEOMESA_TPU_BENCH_EVOLVE_N", 1_000_000))
    c = c if c is not None else 32
    write_rows = write_rows if write_rows is not None else 200
    writers = max(c // 2, 1)
    readers = max(c - writers, 1)
    spec = "*geom:Point:srid=4326,name:String,val:Integer"
    sft = parse_spec("pts24", spec)
    names = np.array([f"grp{i}" for i in range(32)], dtype=object)

    def _batch(m, prefix, name=None, bsft=None):
        ids = np.array([f"{prefix}{i}" for i in range(m)], dtype=object)
        col = (np.full(m, name, dtype=object) if name is not None
               else names[rng.integers(0, len(names), m)])
        return FeatureBatch.from_dict(bsft if bsft is not None else sft,
                                      ids, {
            "geom": (rng.uniform(-170, 170, m), rng.uniform(-80, 80, m)),
            "name": col,
            "val": rng.integers(0, 1_000_000, m).astype(np.int64)})

    out = {"n": n, "c": c, "writers": writers, "readers": readers,
           "write_rows": write_rows}

    with tempfile.TemporaryDirectory() as root:
        ds = InMemoryDataStore(durable_dir=os.path.join(root, "wal"),
                               wal_fsync="never")
        ds.create_schema(sft)
        seed = _batch(n, "s")
        ds.write("pts24", seed)
        # the readers' ground truth: writers only ever append
        # name='writer' rows, so the grp7 id set is frozen for the
        # whole run — across snapshot, catch-up, and the flip itself
        name_col = seed.col("name")
        expected = {seed.ids[i] for i in range(n)
                    if name_col.value(i) == "grp7"}

        EVOLVE_ENABLED.set("true")
        try:
            t0 = time.perf_counter()
            _run_mixed_load(out, rng, ds, _batch, expected, writers,
                            readers, write_rows, SchemaEvolutionError)
            out["online_reindex_s"] = round(time.perf_counter() - t0, 3)

            # -- crash at a random kill point, then resume --------------
            out.update(_crash_resume_leg(rng, ds, SchemaEvolutionError))
        finally:
            EVOLVE_ENABLED.set(None)
        ds.close()

    # -- kill switch off: evolver refuses, twin stays identical ----------
    out.update(_evolve_off_leg(rng, _batch, sft, SchemaEvolutionError))

    out["gates_pass"] = bool(
        out["reader_mismatches"] == 0
        and out["untyped_errors"] == 0
        and out["acked_writes_lost"] == 0
        and out["flips_recorded"] == 1
        and out["write_stall_max_s"] <= 10.0
        and out["resume_completed_once"]
        and out["off_refuses"] and out["off_results_identical"])
    return out


def _run_mixed_load(out, rng, ds, _batch, expected, writers, readers,
                    write_rows, SchemaEvolutionError):
    import threading

    stop = threading.Event()
    acked = [set() for _ in range(writers)]
    stalls = [0.0] * writers
    errs = {"mismatch": 0, "typed": 0, "untyped": 0, "refresh": 0}
    lock = threading.Lock()

    def _writer(w):
        # a correct ingest client: when the flip bumps index_version
        # the held SFT no longer equals the store's (user_data is part
        # of schema identity) and the write is refused before it is
        # journaled — refresh the schema and re-submit the same ids
        k = 0
        cur = ds.get_schema("pts24")
        while not stop.is_set():
            b = _batch(write_rows, f"w{w}_{k}_", name="writer", bsft=cur)
            t0 = time.perf_counter()
            try:
                ds.write("pts24", b)
            except SchemaEvolutionError:
                with lock:
                    errs["typed"] += 1
                continue
            except ValueError:
                cur = ds.get_schema("pts24")
                with lock:
                    errs["refresh"] += 1
                continue
            except Exception:
                with lock:
                    errs["untyped"] += 1
                continue
            stalls[w] = max(stalls[w], time.perf_counter() - t0)
            acked[w].update(b.ids.tolist())
            k += 1

    def _reader():
        while not stop.is_set():
            try:
                res = ds.query("name = 'grp7'", "pts24")
                got = set(res.ids.tolist())
            except SchemaEvolutionError:
                with lock:
                    errs["typed"] += 1
                continue
            except Exception:
                with lock:
                    errs["untyped"] += 1
                continue
            if got != expected:
                with lock:
                    errs["mismatch"] += 1

    threads = ([threading.Thread(target=_writer, args=(w,), daemon=True)
                for w in range(writers)]
               + [threading.Thread(target=_reader, daemon=True)
                  for _ in range(readers)])
    for t in threads:
        t.start()
    time.sleep(0.05)

    t0 = time.perf_counter()
    ds.evolver.reindex("pts24", 1)
    flip_s = time.perf_counter() - t0
    time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=30)

    all_acked = set().union(*acked) if acked else set()
    final = ds.query("INCLUDE", "pts24")
    final_ids = set(final.ids.tolist())
    lost = len(all_acked - final_ids)
    hist = ds.evolver.history
    flips = sum(1 for h in hist
                if h.get("op") == "reindex" and h.get("type") == "pts24")
    out.update({
        "reindex_under_load_s": round(flip_s, 3),
        "index_version": ds.get_schema("pts24").index_version,
        "rows_final": final.n,
        "rows_acked": len(all_acked),
        "reader_mismatches": errs["mismatch"],
        "typed_refusals": errs["typed"],
        "schema_refreshes": errs["refresh"],
        "untyped_errors": errs["untyped"],
        "acked_writes_lost": lost,
        "flips_recorded": flips,
        "write_stall_max_s": round(max(stalls), 3) if stalls else 0.0,
    })


def _crash_resume_leg(rng, ds, SchemaEvolutionError):
    from geomesa_tpu.evolve import Evolver

    phases = Evolver.PHASES
    phase = phases[int(rng.integers(0, len(phases)))]
    before = len([h for h in ds.evolver.history
                  if h.get("op") == "reindex"])

    class _Boom(RuntimeError):
        pass

    def _hook(tag):
        if tag == phase:
            raise _Boom(tag)

    ds.evolver.fault_hook = _hook
    crashed = False
    try:
        ds.evolver.reindex("pts24", 2)
    except _Boom:
        crashed = True
    finally:
        ds.evolver.fault_hook = None
    ds.evolver.resume()
    after = len([h for h in ds.evolver.history
                 if h.get("op") == "reindex"])
    return {
        "crash_phase": phase,
        "crash_injected": crashed,
        "resume_completed_once": (
            after == before + 1
            and ds.get_schema("pts24").index_version == 2),
    }


def _evolve_off_leg(rng, _batch, sft, SchemaEvolutionError):
    from geomesa_tpu.store import InMemoryDataStore

    m = 20_000
    b = _batch(m, "o")
    off = InMemoryDataStore()
    off.create_schema(sft)
    off.write("pts24", b)
    twin = InMemoryDataStore()
    twin.create_schema(sft)
    twin.write("pts24", b)
    try:
        off.evolver.reindex("pts24", 1)
        refuses = False
    except SchemaEvolutionError:
        refuses = True
    same = (set(off.query("name = 'grp3'", "pts24").ids.tolist())
            == set(twin.query("name = 'grp3'", "pts24").ids.tolist())
            and off.query("INCLUDE", "pts24").n
            == twin.query("INCLUDE", "pts24").n)
    return {"off_refuses": bool(refuses),
            "off_results_identical": bool(same)}


# -- config 10: storage integrity — scrub overhead + corrupt recovery -----

def bench_config10(rng):
    """What the integrity layer costs at ingest and buys at recovery.
    A durable ingest takes two checkpoints (retention keeps both);
    recovery is then timed three ways — clean reopen (newest
    checkpoint + short tail), reopen after a bit flip corrupts the
    newest checkpoint (must fall back to the PRIOR checkpoint, not a
    full log replay, with id-exact state), and the same ingest again
    with a background scrubber hashing every artifact on a tight
    cadence (its steady-state overhead on ingest qps)."""
    import shutil
    import tempfile

    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.integrity import flip_bit
    from geomesa_tpu.integrity.scrub import Scrubber
    from geomesa_tpu.integrity.verify import ids_digest
    from geomesa_tpu.store import InMemoryDataStore
    from geomesa_tpu.wal.snapshot import checkpoint_dirs

    rows = int(os.environ.get("GEOMESA_TPU_BENCH_INTEGRITY_ROWS",
                              200_000))
    chunk = max(rows // 50, 1)
    spec = "dtg:Date,*geom:Point:srid=4326"
    x = rng.uniform(-180, 180, rows)
    y = rng.uniform(-90, 90, rows)
    ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY,
                      rows).astype(np.int64)
    ids = np.arange(rows).astype(str).astype(object)

    def ingest(ds, checkpoints_at=()):
        t0 = time.perf_counter()
        for i, lo in enumerate(range(0, rows, chunk)):
            hi = min(lo + chunk, rows)
            ds.write_dict("ais10", ids[lo:hi],
                          {"dtg": ms[lo:hi],
                           "geom": (x[lo:hi], y[lo:hi])})
            if i in checkpoints_at:
                ds.checkpoint()
        return time.perf_counter() - t0

    out: dict = {"rows": rows}
    nchunks = (rows + chunk - 1) // chunk
    d = tempfile.mkdtemp(prefix="geomesa-integrity-bench-")
    try:
        ds = InMemoryDataStore(durable_dir=d, wal_fsync="never")
        ds.create_schema(parse_spec("ais10", spec))
        # checkpoint mid-ingest and at the end: keep=2 retains both,
        # plus the log back to the older one
        base_s = ingest(ds, checkpoints_at={nchunks // 2 - 1,
                                            nchunks - 1})
        want = ids_digest(ds, "ais10")
        ds.close()
        out["ingest_s"] = round(base_s, 3)
        out["ingest_rows_per_s"] = round(rows / base_s, 1)

        ckpts = checkpoint_dirs(d)
        newest_lsn, newest_path = ckpts[-1]
        prior_lsn = ckpts[-2][0] if len(ckpts) > 1 else 0

        # clean recovery: newest checkpoint + (near-empty) tail
        t0 = time.perf_counter()
        ds2 = InMemoryDataStore(durable_dir=d, wal_fsync="never")
        clean_s = time.perf_counter() - t0
        clean_rep = ds2.journal.last_report
        ds2.close()

        # silent corruption of the newest checkpoint's payload
        flip_bit(os.path.join(newest_path, "ais10.bin"))
        t0 = time.perf_counter()
        ds3 = InMemoryDataStore(durable_dir=d, wal_fsync="never")
        corrupt_s = time.perf_counter() - t0
        rep = ds3.journal.last_report
        got = ids_digest(ds3, "ais10")
        ds3.close()
        out["recovery"] = {
            "clean_reopen_s": round(clean_s, 3),
            "clean_checkpoint_lsn": clean_rep.checkpoint_lsn,
            "corrupt_reopen_s": round(corrupt_s, 3),
            "checkpoints_skipped": rep.checkpoints_skipped,
            "fallback_checkpoint_lsn": rep.checkpoint_lsn,
            # the gate: prior checkpoint used (not LSN-1 full replay)
            # and the recovered id set matches the pre-crash store
            "fell_back_to_prior": bool(rep.checkpoints_skipped == 1
                                       and rep.checkpoint_lsn == prior_lsn
                                       and prior_lsn > 0),
            "full_replay_avoided": bool(rep.checkpoint_lsn > 0),
            "ids_exact": bool(got == want),
            "newest_lsn": newest_lsn,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # scrub overhead: the same ingest with the scrubber re-hashing the
    # whole durable root every 250ms. The comparison baseline is a
    # second no-scrub ingest — the FIRST one above paid the jit/ingest
    # warm-up and would make the scrubbed run look free (or negative)
    def timed_ingest(with_scrubber):
        d2 = tempfile.mkdtemp(prefix="geomesa-integrity-bench-scrub-")
        try:
            ds = InMemoryDataStore(durable_dir=d2, wal_fsync="never")
            ds.create_schema(parse_spec("ais10", spec))
            scrubber = (Scrubber(journal=ds.journal,
                                 interval_s=0.25).start()
                        if with_scrubber else None)
            s = ingest(ds, checkpoints_at={nchunks // 2 - 1,
                                           nchunks - 1})
            if scrubber is not None:
                scrubber.stop()
                if scrubber.runs == 0:
                    scrubber.run_once()  # ingest beat the first tick
            ds.close()
            return s, scrubber
        finally:
            shutil.rmtree(d2, ignore_errors=True)

    warm_s, _ = timed_ingest(with_scrubber=False)
    scrub_s, scrubber = timed_ingest(with_scrubber=True)
    out["scrub"] = {
        "interval_s": 0.25,
        "baseline_ingest_s": round(warm_s, 3),
        "ingest_s": round(scrub_s, 3),
        "ingest_rows_per_s": round(rows / scrub_s, 1),
        "overhead_pct": round((scrub_s / warm_s - 1.0) * 100, 1),
        "scrub_runs": scrubber.runs,
        "clean": bool(scrubber.last_report is None
                      or scrubber.last_report["ok"]),
    }
    return out


# -- north star: store-level 100M BBOX+time p50 ---------------------------

def _build_big_store(x, y, ms):
    """The shared 100M-row store for config 5 + northstar."""
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    ds = InMemoryDataStore()
    ds.create_schema(parse_spec("ais", "dtg:Date,*geom:Point:srid=4326"))
    ids = np.arange(len(x)).astype(str).astype(object)
    t0 = time.perf_counter()
    ds.write_dict("ais", ids, {"dtg": ms, "geom": (x, y)})
    return ds, time.perf_counter() - t0


def bench_northstar(ds, write_s, x, y, ms):
    ecql = ("BBOX(geom, -80, 30, -60, 45) AND "
            "dtg DURING 2016-08-07T00:00:00Z/2016-09-06T00:00:00Z")
    t0 = time.perf_counter()
    res = ds.query(ecql, "ais")   # index build + compile
    first_s = time.perf_counter() - t0
    times = []
    for _ in range(9):
        t0 = time.perf_counter()
        res = ds.query(ecql, "ais")
        times.append(time.perf_counter() - t0)
    # identical-IDs contract vs brute force
    t_lo = int(np.datetime64("2016-08-07", "ms").astype(np.int64))
    t_hi = int(np.datetime64("2016-09-06", "ms").astype(np.int64))

    def cpu_pass():
        bmask = ((x >= -80) & (x <= -60) & (y >= 30) & (y <= 45)
                 & (ms > t_lo) & (ms < t_hi))
        return np.flatnonzero(bmask)

    # measured CPU baseline at the full 100M (single-threaded
    # vectorized numpy — the CQEngine-analog stand-in, same convention
    # as configs 1/2: stronger than CQEngine's per-object iteration).
    # The warm-up pass doubles as the exactness oracle.
    bidx = cpu_pass()
    cpu_s = _p50([_timed(cpu_pass) for _ in range(3)])
    ok = np.array_equal(np.sort(res.ids.astype(np.int64)), bidx)
    _pc = _pcts(times)
    p50 = _pc["p50"]
    return {"p50_ms": round(p50 * 1e3, 2),
            "p95_ms": round(_pc["p95"] * 1e3, 2),
            "p99_ms": round(_pc["p99"] * 1e3, 2),
            "cpu_p50_ms": round(cpu_s * 1e3, 2),
            "vs_baseline": round(cpu_s / p50, 2),
            "first_query_s": round(first_s, 2),
            "write_s": round(write_s, 2),
            "n": len(x), "hits": res.n, "ids_exact": bool(ok)}


def main(argv=None):
    global CONFIGS
    import argparse
    ap = argparse.ArgumentParser(
        description="geomesa-tpu benchmark driver")
    ap.add_argument("--only", action="append", default=None,
                    metavar="CONFIG",
                    help="run only these configs (repeatable or "
                         "comma-separated); accepts the bare key ('9', "
                         "'10', 'northstar') or the full result name "
                         "('9_replicated_reads', '10_integrity')")
    args = ap.parse_args(argv)
    if args.only:
        # "9_replicated_reads" and "9" both select config 9
        keys = [k for spec in args.only for k in spec.split(",") if k]
        CONFIGS = {k if k == "northstar" or k.isdigit()
                   else k.split("_", 1)[0] for k in keys}

    import jax
    import jax.numpy as jnp
    from jax import lax

    from geomesa_tpu.scan import zscan

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU (first device is {dev.platform}); the "
              "benchmark measures the chip only", file=sys.stderr)
        sys.exit(2)

    load_start = _load_gate()
    rng = np.random.default_rng(1234)
    out: dict = {"configs": {}, "load_1m": round(load_start, 2)}

    need_big = CONFIGS & {"3", "4", "5", "6", "northstar"}
    bx = by = bms = None
    if need_big:
        bx, by, bms = big_points(rng)

    if "1" in CONFIGS:
        out["configs"]["1_store_bbox_1m"] = bench_config1(rng)

    if "2" in CONFIGS:
        # GDELT-ish 10M slice for the primary kernel metric
        x = rng.uniform(-180, 180, N)
        y = rng.uniform(-90, 90, N)
        ms = rng.integers(T0_DAY * MS_DAY, T1_DAY * MS_DAY,
                          N).astype(np.int64)
        c2 = bench_config2(jax, jnp, lax, zscan, x, y, ms)
        out["configs"]["2_z3_kernel_10m"] = c2
        del x, y, ms

    if "3" in CONFIGS:
        out["configs"]["3_dwithin_join_10m_x_1k"] = bench_config3(
            rng, bx[:10_000_000], by[:10_000_000])

    if "4" in CONFIGS:
        out["configs"]["4_knn_50m_k100"] = bench_config4(rng, bx, by)

    if "6" in CONFIGS:
        m = min(N, len(bx))
        out["configs"]["6_concurrent_bbox"] = bench_config6(
            rng, bx[:m], by[:m], bms[:m])

    if "7" in CONFIGS:
        out["configs"]["7_durable_ingest"] = bench_config7(rng)

    if "8" in CONFIGS:
        out["configs"]["8_faulty_network"] = bench_config8(rng)

    if "9" in CONFIGS:
        out["configs"]["9_replicated_reads"] = bench_config9(rng)

    if "10" in CONFIGS:
        out["configs"]["10_integrity"] = bench_config10(rng)

    if "11" in CONFIGS:
        out["configs"]["11_cluster"] = bench_config11(rng)

    if "12" in CONFIGS:
        out["configs"]["12_hot_tiles"] = bench_config12(rng)

    if "13" in CONFIGS:
        out["configs"]["13_tail_latency"] = bench_config13(rng)
    if "14" in CONFIGS:
        out["configs"]["14_streaming"] = bench_config14(rng)
    if "15" in CONFIGS:
        out["configs"]["15_geofence"] = bench_config15(rng)
    if "16" in CONFIGS:
        out["configs"]["16_ingest"] = bench_config16(rng)
    if "17" in CONFIGS:
        out["configs"]["17_observability"] = bench_config17(rng)
    if "18" in CONFIGS:
        out["configs"]["18_health"] = bench_config18(rng)
    if "19" in CONFIGS:
        out["configs"]["19_distributed_sql"] = bench_config19(rng)
    if "20" in CONFIGS:
        out["configs"]["20_planner"] = bench_config20(rng)
    if "21" in CONFIGS:
        out["configs"]["21_reshard"] = bench_config21(rng)
    if "22" in CONFIGS:
        out["configs"]["22_multitenant"] = bench_config22(rng)
    if "23" in CONFIGS:
        out["configs"]["23_matviews"] = bench_config23(rng)
    if "24" in CONFIGS:
        out["configs"]["24_evolve"] = bench_config24(rng)

    big_ds = None
    if CONFIGS & {"5", "northstar"}:
        big_ds, write_s = _build_big_store(bx, by, bms)

    if "northstar" in CONFIGS:
        ns = bench_northstar(big_ds, write_s, bx, by, bms)
        out["configs"]["northstar_100m_bbox_time"] = ns
        out["p50_ms_100m"] = ns["p50_ms"]
        out["p99_ms_100m"] = ns["p99_ms"]

    if "5" in CONFIGS:
        out["configs"]["5_contains_100m_x_10k"] = bench_config5(
            rng, big_ds, bx, by)

    load_end = _load_1m()
    out["load_1m_end"] = round(load_end, 2)
    out["load_ok"] = bool(load_start <= LOAD_MAX and load_end <= LOAD_MAX)

    c2 = out["configs"].get("2_z3_kernel_10m", {})
    out.update({
        "metric": "z3_bbox_time_filter_rate",
        "value": c2.get("rate", 0.0),
        "unit": "features/sec/chip",
        "vs_baseline": c2.get("vs_baseline", 0.0),
        "n": c2.get("n", N),
        "reps": REPS,
        "hits": c2.get("hits", 0),
        "ids_exact": c2.get("ids_exact", False),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
