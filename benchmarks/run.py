#!/usr/bin/env python
"""Run one benchmark cell once and print its result as the last line.

    python benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration (``configs/<config>.json``: schema, rows, the law of each
column, each law a module ``laws/<law>.py``), its traffic mix
(``traffic/<traffic>.json``) and the mix's request kind, a module
``generators/<generator>.py`` named in the mix, and, with ``--trace 1``,
one reader per per-layer metric (``metrics/<metric>.py``,
``read(run) -> float | None``).

A generator module supplies, for its kind of request: ``Stream(mix, table,
seed)`` (the requests, drawn from the seed), ``warmup(ctx, mix, seed)``
(set-up's requests), ``submit(ctx, q)`` and ``answer(result)`` (the timed
call and what the caller holds after it), ``size(answer)``, ``facts(ctx,
q, result)`` (what the per-layer readers need), ``check(table, q,
answer)`` (numbers compared with its plain reference, each held to its
entry in ``LIMITS``) and ``control(table, q)`` (the reference in lower
precision in the program's place).

A configuration names the store that holds it under ``"store"``:
``"memory"`` (the default) is ``InMemoryDataStore`` on one chip, ``"mesh"``
is ``DistributedDataStore`` over ``data_mesh(chips)``, the cell's chips.
A mesh store needs a cell of two chips or more, and a memory store a cell
of one: any other pairing ends the run with no result.

A run generates the table from the seed, loads it through the store's
``write_dict``, warms up on the generator's set-up requests, then lets the
mix's clients send requests in a closed loop for ``--seconds``. A request
is timed from submit until its answer is in hand. After the window, the
answers (all of them, or a sample drawn from the seed that holds the
largest) are checked against the reference; the numbers compared and their
limits are printed last on stderr and under ``compared`` in the result
line.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")
sys.path[:0] = [HERE, ROOT]

import datagen  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402

JOIN_GRACE_S = 120   # a request in flight at the close may finish this late
STORES = ("memory", "mesh")
MESH_COLUMNS = ("xhi", "xlo", "yhi", "ylo", "tday", "tms")


class Usage(Exception):
    pass


def load_cell(name: str, bench: dict | None = None):
    """The cell ``name`` of ``bench`` (by default ``BENCHMARK.json``)."""
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise Usage(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as fh:
        config = json.load(fh)
    store = config.get("store", "memory")
    if store not in STORES:
        raise Usage(f"configuration {conf['name']!r} names store {store!r}; "
                    f"one of {', '.join(STORES)}")
    if (store == "mesh") != (cell["chips"] > 1):
        raise Usage(f"a {store} store in a cell of {cell['chips']} chip(s): "
                    "a mesh takes two or more, a memory store one")
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as fh:
        mix = json.load(fh)
    gen = _module("generators", mix["generator"])

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in reported]
    return types.SimpleNamespace(name=name, chips=cell["chips"],
                                 config=config, mix=mix, gen=gen, e2e=e2e,
                                 layer=layer)


def _module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, loaded by path."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def check_device(chips: int):
    """A run needs a TPU and the cell's chips; anything else ends it with
    no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise Usage(f"needs {chips} TPU chip(s); JAX found "
                    f"{len(devs)} {devs[0].platform} device(s)")


class Compiles:
    """Compile requests (JAX's backend-compile event, which also fires on a
    load from the persistent cache) and their seconds (copied from
    chip_smoke.py)."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration


def load_store(table, config, chips: int):
    """The configuration's store, loaded with the table: the row count is
    checked, and a mesh store's device columns must sit on ``chips``
    distinct devices."""
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import DistributedDataStore, InMemoryDataStore
    mesh = config.get("store", "memory") == "mesh"
    if mesh:
        from geomesa_tpu.parallel import data_mesh
        ds = DistributedDataStore(data_mesh(chips))
    else:
        ds = InMemoryDataStore()
    name = config["type_name"]
    ds.create_schema(parse_spec(name, config["spec"]))
    ds.write_dict(name, table.ids, datagen.to_store(table))
    if ds.count(name) != table.n:
        raise RuntimeError("the store holds another row count than written")
    if mesh:
        st = ds._state(name)
        st.ensure_index()
        for seg in st.segments:
            for c in MESH_COLUMNS:
                on = {s.device for s in getattr(seg, c).addressable_shards
                      if s.data.size}
                if len(on) != chips:
                    raise RuntimeError(f"mesh column {c} sits on {len(on)} "
                                       f"device(s), not {chips}")
    return ds


def drive(ctx, gen, stream, clients: int, seconds: float, annotate: bool):
    """Closed loop: each client sends its next request when the last one
    has answered, until ``seconds`` have passed; requests in flight at the
    close finish and count. Returns (records, window_s, unfinished)."""
    lock = threading.Lock()
    records = []
    if annotate:
        from jax.profiler import TraceAnnotation
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def client():
        while True:
            with lock:
                if time.perf_counter() >= deadline:
                    return
                q = next(stream)
            rec = {"seq": q.seq, "cls": q.cls, "q": q}
            t0 = time.perf_counter()
            try:
                if annotate:
                    with TraceAnnotation(f"bench.query#{q.seq}"):
                        res = gen.submit(ctx, q)
                    with TraceAnnotation(f"bench.answer#{q.seq}"):
                        got = gen.answer(res)
                else:
                    res = gen.submit(ctx, q)
                    got = gen.answer(res)
                rec["ok"] = True
            except Exception as e:  # noqa: BLE001 - a failed request
                rec["ok"], rec["error"] = False, repr(e)[:300]
                res = got = None
            rec["t1"] = time.perf_counter()
            rec["latency_ms"] = (rec["t1"] - t0) * 1e3
            if res is not None:
                rec["answer"] = got
                rec["hits"] = gen.size(got)
                rec.update(gen.facts(ctx, q, res))
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, deadline + JOIN_GRACE_S
                           - time.perf_counter()))
    unfinished = sum(t.is_alive() for t in threads)
    end = max([r["t1"] for r in records] + [deadline])
    return records, end - t_start, unfinished


def percentile(lat: list, p: float) -> float:
    return float(np.percentile(np.asarray(lat, dtype=np.float64), p))


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def compare(gen, table, records, seed: int, cap: int):
    """The generator's numbers, summed over the compared answers: all of
    them, or ``cap`` drawn from the seed with the largest among them."""
    done = [r for r in records if r["ok"]]
    if len(done) > cap:
        big = max(range(len(done)), key=lambda i: done[i]["hits"])
        rest = [i for i in range(len(done)) if i != big]
        pick = np.random.default_rng([seed, 3]).choice(rest, cap - 1,
                                                       replace=False)
        done = [done[big]] + [done[i] for i in sorted(pick)]
    sums = dict.fromkeys(gen.LIMITS, 0)
    for r in done:
        for k, v in gen.check(table, r["q"], r["answer"]).items():
            sums[k] += v
    return sums, len(done)


def run(argv=None, *, require_chip: bool = True, rows: int | None = None,
        control: bool = False, bench: dict | None = None) -> dict:
    """One run; returns the result object. ``rows``, ``control`` and
    ``bench`` exist for the CPU rehearsal: a row cut, the generator's
    control answering in the program's place, and a benchmark definition
    in place of ``BENCHMARK.json``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # no cell runs a join: write_dict's join prewarm would compile kernels
    # that no request uses (geomesa.join.prewarm)
    os.environ["GEOMESA_JOIN_PREWARM"] = "false"
    if require_chip:
        check_device(cell.chips)
    import jax
    from geomesa_tpu.audit import global_audit
    from geomesa_tpu.obs import tracer
    from geomesa_tpu.obs.trace import TRACE_MAX_SPANS, TRACE_SAMPLE
    from geomesa_tpu.scan.registry import batcher_registry, shared_batcher

    comp = Compiles()
    config, mix, gen = cell.config, cell.mix, cell.gen
    if control:
        gen = types.SimpleNamespace(
            **{k: getattr(gen, k) for k in dir(gen) if not k.startswith("_")})
        gen.submit = lambda ctx, q: cell.gen.control(ctx.table, q)
        gen.answer = lambda res: res
        gen.facts = lambda ctx, q, res: {}
    t0 = time.perf_counter()
    table = datagen.generate(config, args.seed, rows)
    t1 = time.perf_counter()
    ds = load_store(table, config, cell.chips)
    t2 = time.perf_counter()
    ctx = types.SimpleNamespace(store=ds, batcher=shared_batcher(ds),
                                type_name=config["type_name"], table=table)
    n_warm = gen.warmup(ctx, mix, args.seed)
    t3 = time.perf_counter()
    stream = gen.Stream(mix, table, args.seed)

    if args.trace:
        TRACE_SAMPLE.set("1")
        TRACE_MAX_SPANS.set("1000000")
        tracer.clear()
        trace_dir = os.path.join(OUT, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_trace_options())
    audit_mark = len(global_audit().events)
    compiles0, compile_s0 = comp.n, comp.secs
    setup_s = time.perf_counter() - T_PROC
    print(json.dumps({"setup": {"start_s": t0 - T_PROC, "generate_s": t1 - t0,
                                "load_s": t2 - t1, "warmup_s": t3 - t2,
                                "warmup_requests": n_warm,
                                "compiles": compiles0,
                                "compile_s": compile_s0}}),
          file=sys.stderr, flush=True)

    if args.trace:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.window"):
            records, window_s, unfinished = drive(
                ctx, gen, stream, mix["clients"], args.seconds, True)
        jax.profiler.stop_trace()
    else:
        records, window_s, unfinished = drive(
            ctx, gen, stream, mix["clients"], args.seconds, False)
    compiles_in_window = [comp.n - compiles0, comp.secs - compile_s0]
    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    audit = list(global_audit().events)[audit_mark:]

    lat = [r["latency_ms"] if r["ok"] else float("inf") for r in records]
    ok = sum(r["ok"] for r in records)
    e2e = {"setup_s": setup_s,
           "p50_ms": percentile(lat, 50) if lat else float("inf"),
           "p95_ms": percentile(lat, 95) if lat else float("inf"),
           "qps": ok / window_s}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    # a request still open JOIN_GRACE_S after the close never answered
    result = {"correct": False, "attempted": len(records) + unfinished,
              "failed": len(records) - ok + unfinished}
    if args.trace:
        xplane = next(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                      for f in fs if f.endswith(".xplane.pb"))
        recs = trace_reduce.load(xplane)
        win = next(iter(trace_reduce.Reduction(
            recs, 0, 1 << 62).annotations("bench.window").values()))
        red = trace_reduce.Reduction(recs, *win)
        view = types.SimpleNamespace(
            records=records, audit=audit, rows=table.n, trace=red,
            spans=[tracer.get(t["trace_id"]) or []
                   for t in tracer.traces(limit=1 << 30)],
            peak=roofline.peaks(device["kind"]) if require_chip else None)
        metrics = {}
        for m in cell.layer:
            v = _module("metrics", m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.e2e}

    # the program's state goes before the reference runs
    batcher_registry.clear()
    del ds, ctx
    sums, n_cmp = compare(gen, table, records, args.seed, mix["compare"])
    compared = {k: {"value": v, "limit": gen.LIMITS[k]}
                for k, v in sums.items()}
    tiers: dict = {}
    for r in records:
        t = r.get("tier", "none")
        tiers[t] = tiers.get(t, 0) + 1
    print(json.dumps({"answers_compared": n_cmp, "tiers": tiers,
                      "device_residual": sum(bool(r.get("device_residual"))
                                             for r in records),
                      "compiles_in_window": compiles_in_window,
                      "window_s": window_s, "rows": table.n}),
          file=sys.stderr)
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    result.update(
        correct=bool(n_cmp > 0 and result["failed"] == 0
                     and all(v["value"] <= v["limit"]
                             for v in compared.values())),
        metrics=metrics, device=device, compared=compared)
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except Usage as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
