#!/usr/bin/env python
"""Bring-up smoke: drive the store's main path once on one TPU chip.

Builds the bench's north-star store (``bench.big_points``: AIS-shaped
lanes plus uniform noise, schema ``dtg:Date,*geom:Point:srid=4326``)
from ``--seed`` at ``--rows`` (100M by default, the per-chip scale the
north star names), loads it through ``InMemoryDataStore.write_dict`` and
answers:

  (a) the north-star BBOX+time query (exact host tier);
  (b) wide BBOX+time queries past ``geomesa.scan.host.rows`` (gathered
      and dense device tiers);
  (d) 32 concurrent BBOX queries through a QueryBatcher (fused kernel);
  (e) batched KNN, k=100, Q=8, through ``knn_process``;
  (f) ST_Contains counts over 1,024 polygons through ``contains_process``;
  (g) REST requests to ``web/server.py`` started in this process.

Every answer is checked id for id (counts for the join) against a plain
numpy oracle over the same arrays. Each phase prints one JSON line; the
last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
``ok`` needs every phase exact and a TPU as the first device: on any
other platform the phases still run (the CPU rehearsal) and the script
exits 1.

``--chips 4`` runs only the mesh path instead: DistributedDataStore over
``data_mesh(4)`` with the same rows, its shards checked to sit on four
distinct devices, and the BBOX count and ids, ``ring_dwithin_counts``,
``distributed_knn`` and ``distributed_histogram`` checked against the
oracle.

Usage: python chip_smoke.py [--rows N] [--seed S] [--chips 1|4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.parse
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import MS_DAY, T0_DAY, T1_DAY, big_points  # noqa: E402

FULL_ROWS = 100_000_000
SPEC = "dtg:Date,*geom:Point:srid=4326"
TYPE = "ais"


def _ms(day: str) -> int:
    return int(np.datetime64(day, "ms").astype(np.int64))


def _during(t0: str, t1: str) -> str:
    return f"dtg DURING {t0}T00:00:00Z/{t1}T00:00:00Z"


# (name, boxes, (t0, t1) or None, tier the explain text must show)
NORTH_STAR = ("a_northstar", [(-80, 30, -60, 45)],
              ("2016-08-07", "2016-09-06"), "Index-pruned host scan")
GATHERED = ("b_gathered", [(-100, -50, 100, 50)],
            ("2016-08-01", "2016-08-21"), "Index-pruned device scan")
DENSE = ("b_dense", [(-160, -70, 160, 70)],
         ("2016-07-20", "2016-10-20"), "Device scan:")


def ecql(boxes, during) -> str:
    sp = " OR ".join(f"BBOX(geom, {x0}, {y0}, {x1}, {y1})"
                     for x0, y0, x1, y1 in boxes)
    if len(boxes) > 1:
        sp = f"({sp})"
    return sp if during is None else f"{sp} AND {_during(*during)}"


class Data:
    """The generated columns plus an x-sorted view for the oracles."""

    def __init__(self, rows: int, seed: int):
        self.x, self.y, self.ms = big_points(np.random.default_rng(seed),
                                             rows)
        self.n = rows
        self.ids = np.arange(rows).astype(str).astype(object)
        self.xorder = np.argsort(self.x, kind="stable")
        self.xs = self.x[self.xorder]

    def slab(self, x0: float, x1: float) -> np.ndarray:
        """Rows with x0 <= x <= x1."""
        lo = np.searchsorted(self.xs, x0, side="left")
        hi = np.searchsorted(self.xs, x1, side="right")
        return self.xorder[lo:hi]

    def box_rows(self, boxes, during=None) -> np.ndarray:
        """Sorted rows inside any closed box (and the open interval)."""
        parts = []
        for x0, y0, x1, y1 in boxes:
            r = self.slab(x0, x1)
            parts.append(r[(self.y[r] >= y0) & (self.y[r] <= y1)])
        r = np.unique(np.concatenate(parts))
        if during is not None:
            t0, t1 = _ms(during[0]), _ms(during[1])
            r = r[(self.ms[r] > t0) & (self.ms[r] < t1)]
        return r

    def knn_rows(self, qx: float, qy: float, k: int) -> np.ndarray:
        """k nearest rows in f64, ties broken by row id."""
        r = 0.5
        while True:
            c = self.slab(qx - r, qx + r)
            d2 = (self.x[c] - qx) ** 2 + (self.y[c] - qy) ** 2
            inside = d2 <= r * r
            if inside.sum() >= k or len(c) == self.n:
                c, d2 = c[inside], d2[inside]
                return c[np.lexsort((c, d2))][:k]
            r *= 2


class Compiles:
    """Counts compile requests (JAX's backend-compile event wraps both a
    compile and a load from the persistent cache), their seconds, and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax
        self.n = self.secs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.n, self.secs, self.hits, self.misses)

    def delta(self, before) -> dict:
        n, s, h, m = (a - b for a, b in zip(self.snap(), before))
        return {"compile_requests": n, "compile_s": round(s, 3),
                "cache_hits": h, "cache_misses": m}


def _counter(name: str) -> int:
    from geomesa_tpu.metrics import metrics
    return int(metrics.snapshot()["counters"].get(name, 0))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _query_phase(ds, data: Data, spec) -> dict:
    _, boxes, during, tier = spec
    text = ecql(boxes, during)
    lines: list[str] = []
    first, first_s = _timed(
        lambda: ds.query(text, TYPE, explain_out=lines.append))
    warm, warm_s = _timed(lambda: ds.query(text, TYPE))
    want = data.ids[data.box_rows(boxes, during)]
    taken = [ln.strip() for ln in lines
             if any(t[3] in ln for t in (NORTH_STAR, GATHERED, DENSE))]
    return {"hits": int(warm.n),
            "exact": bool(np.array_equal(first.ids, want)
                          and np.array_equal(warm.ids, want)),
            "tier": taken[0] if taken else None,
            "tier_ok": any(tier in ln for ln in taken),
            "first_s": first_s, "wall_s": warm_s}


def _batched_phase(ds, data: Data, seed: int) -> dict:
    from geomesa_tpu.index.api import Query
    from geomesa_tpu.scan.batcher import QueryBatcher
    rng = np.random.default_rng(seed + 1)
    boxes = [(float(x0), float(y0), float(x0) + 10, float(y0) + 10)
             for x0, y0 in zip(rng.uniform(-170, 160, 32),
                               rng.uniform(-80, 70, 32))]
    queries = [Query(TYPE, ecql([b], None)) for b in boxes]

    def burst():
        batcher = QueryBatcher(ds, max_batch=32, linger_us=200_000,
                               adaptive=False)
        res = [None] * len(queries)

        def one(i):
            res[i] = batcher.query(queries[i])

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        return res, batcher

    failed0 = _counter("batcher.dispatch.failed")
    (res, batcher), first_s = _timed(burst)
    (warm, _), warm_s = _timed(burst)
    failed = _counter("batcher.dispatch.failed") - failed0
    exact = all(r is not None and w is not None
                and np.array_equal(r.ids, data.ids[data.box_rows([b])])
                and np.array_equal(w.ids, r.ids)
                for r, w, b in zip(res, warm, boxes))
    return {"queries": len(queries), "dispatches": batcher.batches,
            "coalesced": batcher.coalesced_queries,
            "hits": int(sum(r.n for r in res if r is not None)),
            "exact": bool(exact), "dispatch_failed": failed,
            "tier": "fused batch scan",
            "tier_ok": failed == 0 and batcher.coalesced_queries > 1,
            "first_s": first_s, "wall_s": warm_s}


KNN_POINTS = [(10.0, 10.0), (-120.0, 40.0), (0.0, 0.0), (150.0, -30.0),
              (-60.0, -60.0), (80.0, 20.0), (-10.0, 55.0), (100.0, 5.0)]


def _knn_phase(ds, data: Data, k: int = 100) -> dict:
    from geomesa_tpu.analytics.processes import knn_process
    qx = np.array([p[0] for p in KNN_POINTS])
    qy = np.array([p[1] for p in KNN_POINTS])
    first, first_s = _timed(lambda: knn_process(ds, TYPE, qx, qy, k))
    warm, warm_s = _timed(lambda: knn_process(ds, TYPE, qx, qy, k))
    exact = all(np.array_equal(f[0], data.ids[data.knn_rows(x, y, k)])
                and np.array_equal(w[0], f[0])
                for f, w, (x, y) in zip(first, warm, KNN_POINTS))
    return {"queries": len(KNN_POINTS), "k": k, "exact": bool(exact),
            "first_s": first_s, "wall_s": warm_s}


def _diamonds(seed: int, count: int):
    """(cx, cy, w, h) of diamond polygons, and their WKT geometries."""
    from geomesa_tpu.geometry import parse_wkt
    rng = np.random.default_rng(seed + 2)
    cx = rng.uniform(-175, 175, count)
    cy = rng.uniform(-85, 85, count)
    w = rng.uniform(0.05, 0.5, count)
    h = rng.uniform(0.05, 0.5, count)
    polys = [parse_wkt(
        f"POLYGON (({a - b} {c}, {a} {c - d}, {a + b} {c}, {a} {c + d}, "
        f"{a - b} {c}))") for a, c, b, d in zip(cx, cy, w, h)]
    return (cx, cy, w, h), polys


def _contains_phase(ds, data: Data, seed: int, count: int = 1024) -> dict:
    from geomesa_tpu.analytics.processes import contains_process
    (cx, cy, w, h), polys = _diamonds(seed, count)
    first, first_s = _timed(lambda: contains_process(ds, TYPE, polys)[0])
    warm, warm_s = _timed(lambda: contains_process(ds, TYPE, polys)[0])
    want = np.zeros(count, np.int64)
    for i in range(count):
        r = data.slab(cx[i] - w[i], cx[i] + w[i])
        want[i] = int((np.abs(data.x[r] - cx[i]) / w[i]
                       + np.abs(data.y[r] - cy[i]) / h[i] < 1).sum())
    return {"polygons": count, "hits": int(want.sum()),
            "exact": bool(np.array_equal(first, want)
                          and np.array_equal(warm, want)),
            "first_s": first_s, "wall_s": warm_s}


def _web_phase(ds, data: Data) -> dict:
    from geomesa_tpu.web.server import GeoMesaWebServer
    srv = GeoMesaWebServer(ds).start()
    base = f"http://127.0.0.1:{srv.port}/rest"

    def get(path, **params):
        url = f"{base}/{path}?{urllib.parse.urlencode(params)}"
        with urllib.request.urlopen(url, timeout=600) as r:
            return json.loads(r.read())

    def requests():
        ns = get(f"query/{TYPE}", cql=ecql(NORTH_STAR[1], NORTH_STAR[2]),
                 properties="dtg")
        cnt = get(f"count/{TYPE}", cql=ecql(GATHERED[1], GATHERED[2]))
        qx, qy = KNN_POINTS[0]
        knn = get(f"knn/{TYPE}", x=qx, y=qy, k=100)
        return ns, cnt, knn

    try:
        (ns, cnt, knn), first_s = _timed(requests)
        _, warm_s = _timed(requests)
    finally:
        srv.stop()
    want_ns = data.ids[data.box_rows(NORTH_STAR[1], NORTH_STAR[2])]
    got_ns = np.array([f["id"] for f in ns["features"]], dtype=object)
    want_cnt = len(data.box_rows(GATHERED[1], GATHERED[2]))
    want_knn = data.ids[data.knn_rows(*KNN_POINTS[0], 100)]
    exact = (np.array_equal(np.sort(got_ns.astype(str)),
                            np.sort(want_ns.astype(str)))
             and cnt["count"] == want_cnt
             and np.array_equal(np.asarray(knn["ids"], object), want_knn))
    return {"requests": 3, "hits": len(got_ns), "count": cnt["count"],
            "exact": bool(exact), "first_s": first_s, "wall_s": warm_s}


def _run_phases(phases, comp: Compiles, emit) -> list[dict]:
    results = []
    for name, fn in phases:
        before = comp.snap()
        results.append(_phase(name, emit,
                              lambda: {**fn(), **comp.delta(before)}))
    return results


def _phase(name: str, emit, fn) -> dict:
    """Run one phase; an exception is a failed phase, not a crash."""
    t0 = time.perf_counter()
    try:
        out = {"phase": name, **fn()}
        out["ok"] = bool(out.get("exact")) and out.get("tier_ok", True)
    except Exception as e:  # noqa: BLE001 - reported, run continues
        import traceback
        traceback.print_exc()
        out = {"phase": name, "ok": False, "error": repr(e)[:500]}
    out["phase_s"] = time.perf_counter() - t0
    emit(out)
    return out


def _load(ds, data: Data) -> dict:
    from geomesa_tpu.features import parse_spec
    failed0 = _counter("store.ingest.index_build.failed")
    ds.create_schema(parse_spec(TYPE, SPEC))
    _, load_s = _timed(lambda: ds.write_dict(
        TYPE, data.ids, {"dtg": data.ms, "geom": (data.x, data.y)}))
    failed = _counter("store.ingest.index_build.failed") - failed0
    return {"rows": ds.count(TYPE), "exact": ds.count(TYPE) == data.n,
            "tier_ok": failed == 0, "index_build_failed": failed,
            "load_s": load_s}


def run_single(rows: int, seed: int, emit) -> list[dict]:
    """Phases (a), (b) and (d)-(g) on the first device."""
    from geomesa_tpu.store import InMemoryDataStore
    comp = Compiles()
    data, gen_s = _timed(lambda: Data(rows, seed))
    emit({"phase": "data", "rows": rows, "seed": seed, "gen_s": gen_s})
    ds = InMemoryDataStore()
    phases = [
        ("load", lambda: _load(ds, data)),
        (NORTH_STAR[0], lambda: _query_phase(ds, data, NORTH_STAR)),
        (GATHERED[0], lambda: _query_phase(ds, data, GATHERED)),
        (DENSE[0], lambda: _query_phase(ds, data, DENSE)),
        ("d_batched", lambda: _batched_phase(ds, data, seed)),
        ("e_knn", lambda: _knn_phase(ds, data)),
        ("f_contains", lambda: _contains_phase(ds, data, seed)),
        ("g_web", lambda: _web_phase(ds, data)),
    ]
    return _run_phases(phases, comp, emit)


def _sharded_on(arr, n_devices: int) -> dict:
    devs = {s.device for s in arr.addressable_shards if s.data.size}
    return {"devices": len(devs), "ok": len(devs) == n_devices}


def run_mesh(rows: int, seed: int, emit, n_devices: int = 4) -> list[dict]:
    """The mesh path alone: DistributedDataStore over data_mesh(n)."""
    from geomesa_tpu.parallel import (data_mesh, ring_dwithin_counts,
                                      shard_points)
    from geomesa_tpu.store import DistributedDataStore
    comp = Compiles()
    data, gen_s = _timed(lambda: Data(rows, seed))
    emit({"phase": "data", "rows": rows, "seed": seed, "gen_s": gen_s})
    mesh = data_mesh(n_devices)
    if mesh.devices.size != n_devices:
        raise RuntimeError(f"need {n_devices} devices, "
                           f"found {mesh.devices.size}")
    ds = DistributedDataStore(mesh)

    def placement():
        st = ds._state(TYPE)
        st.ensure_index()
        seg = st.segments[0]
        cols = {c: _sharded_on(getattr(seg, c), n_devices)
                for c in ("xhi", "xlo", "yhi", "ylo", "tday", "tms")}
        used = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat}
        return {"columns": cols, "bytes_in_use": used,
                "exact": all(c["ok"] for c in cols.values())}

    def bbox():
        text = ecql(DENSE[1], DENSE[2])
        want = data.box_rows(DENSE[1], DENSE[2])
        lines: list[str] = []
        cnt, count_s = _timed(lambda: ds.query_count(text, TYPE))
        res, query_s = _timed(lambda: ds.query(text, TYPE,
                                               explain_out=lines.append))
        ns = ds.query(ecql(NORTH_STAR[1], NORTH_STAR[2]), TYPE)
        tier = [ln.strip() for ln in lines if "Distributed scan" in ln]
        return {"count": int(cnt), "hits": int(res.n),
                "tier": tier[0] if tier else None, "tier_ok": bool(tier),
                "exact": bool(cnt == len(want)
                              and np.array_equal(res.ids, data.ids[want])
                              and np.array_equal(ns.ids, data.ids[
                                  data.box_rows(NORTH_STAR[1],
                                                NORTH_STAR[2])])),
                "count_s": count_s, "query_s": query_s}

    def ring():
        rng = np.random.default_rng(seed + 3)
        m, radius = 16 * n_devices, 0.5
        rx = rng.uniform(-170, 170, m)
        ry = rng.uniform(-80, 80, m)
        left = shard_points(data.x, data.y, mesh)
        right = shard_points(rx, ry, mesh)
        sh = {"left_x": _sharded_on(left[0], n_devices)}
        (sure, band), first_s = _timed(lambda: ring_dwithin_counts(
            *left[:3], *right[:3], mesh, radius))
        want = np.zeros(data.n, np.int64)
        for qx, qy in zip(rx, ry):
            r = data.slab(qx - radius, qx + radius)
            d2 = (data.x[r] - qx) ** 2 + (data.y[r] - qy) ** 2
            np.add.at(want, r[d2 <= radius * radius], 1)
        got = sure[:data.n].astype(np.int64)
        for i in np.flatnonzero(band[:data.n]):  # band rows: exact f64
            got[i] = int(((data.x[i] - rx) ** 2 + (data.y[i] - ry) ** 2
                          <= radius * radius).sum())
        return {"right": m, "pairs": int(want.sum()),
                "band_rows": int((band[:data.n] > 0).sum()),
                "placement": sh, "tier_ok": sh["left_x"]["ok"],
                "exact": bool(np.array_equal(got, want)),
                "first_s": first_s}

    def knn():
        first_s = None
        exact = True
        for qx, qy in KNN_POINTS:
            got, s = _timed(lambda: ds.knn(TYPE, qx, qy, 100))
            first_s = s if first_s is None else first_s
            exact &= np.array_equal(got, data.ids[data.knn_rows(qx, qy,
                                                                100)])
        return {"queries": len(KNN_POINTS), "k": 100, "exact": bool(exact),
                "first_s": first_s}

    def histogram():
        lo, hi, nbins = T0_DAY * MS_DAY, T1_DAY * MS_DAY, 100
        got, first_s = _timed(lambda: ds.histogram(TYPE, "dtg", nbins,
                                                   lo, hi))
        # the kernel's own f32 arithmetic (parallel/mesh._hist_fn)
        v = data.ms.astype(np.float32)
        keep = (v >= np.float32(lo)) & (v <= np.float32(hi))
        b = ((v[keep] - np.float32(lo))
             * np.float32(nbins / (hi - lo))).astype(np.int32)
        want = np.bincount(np.clip(b, 0, nbins - 1), minlength=nbins)
        return {"bins": nbins, "hits": int(got.sum()),
                "exact": bool(np.array_equal(got, want)),
                "first_s": first_s}

    phases = [("load", lambda: _load(ds, data)),
              ("mesh_placement", placement),
              ("mesh_bbox", bbox),
              ("mesh_ring_dwithin", ring),
              ("mesh_knn", knn),
              ("mesh_histogram", histogram)]
    return _run_phases(phases, comp, emit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=FULL_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from geomesa_tpu import native, store  # noqa: F401 - sets the cache

    def emit(obj):
        print(json.dumps(obj, default=float), flush=True)

    if args.rows != FULL_ROWS:
        emit({"cut": {"rows": args.rows, "from": FULL_ROWS}})
    emit({"compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "cache_dir_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "native_loaded": native.load() is not None})
    devs = jax.devices()
    run = run_single if args.chips == 1 else run_mesh
    t0 = time.perf_counter()
    results = run(args.rows, args.seed, emit)
    mem = {str(d): {k: v for k, v in (d.memory_stats() or {}).items()
                    if k in ("bytes_in_use", "peak_bytes_in_use",
                             "bytes_limit")}
           for d in devs[:args.chips]}
    emit({"total_s": time.perf_counter() - t0, "device_memory": mem,
          "phases_ok": [r["phase"] for r in results if r["ok"]],
          "phases_failed": [r["phase"] for r in results if not r["ok"]]})
    ok = (all(r["ok"] for r in results) and devs[0].platform == "tpu"
          and len(devs) >= args.chips)
    print(json.dumps({"ok": ok, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
