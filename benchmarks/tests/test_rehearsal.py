"""CPU rehearsal: every cell of BENCHMARK.json end to end at a row cut.

The run skips the look for a chip (``require_chip=False``) and cuts the
table to ``ROWS``; everything else is the run the driver makes. The control
(the f32 reference in the program's place) and planted faults in the
program must each turn ``correct`` false.
"""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run

ROWS = 200_000
SEED = 2**31 + 12345
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(cell, trace=0, seed=SEED, module=run, **kw):
    res = module.run(["--workload", cell, "--seed", str(seed),
                      "--seconds", "2", "--trace", str(trace)],
                     require_chip=False, rows=ROWS, **kw)
    json.loads(json.dumps(res))          # one line of plain JSON
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(res["device"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run(cell):
    res = _run(cell, trace=1)
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # host-clock readers find their spans and audit events on any backend;
    # device readers find nothing on the CPU and leave their metric out
    assert {"plan_ms", "scan_ms"} <= set(res["metrics"])
    assert "device_idle_share" not in res["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = _run(cell, control=True)
    assert res["correct"] is False
    assert res["compared"]["mismatched_ids"]["value"] > 0


def _drop_last(idx):
    return idx[:-1]


def _repeat_first(idx):
    return np.concatenate([idx[:1], idx]) if len(idx) else idx


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_drop_last, _repeat_first])
def test_altered_answer_is_not_correct(cell, fault, monkeypatch):
    """A row left out, or one row answered twice, where the store produces
    its answer."""
    from geomesa_tpu.store.memory import InMemoryDataStore
    orig = InMemoryDataStore._execute
    monkeypatch.setattr(InMemoryDataStore, "_execute",
                        lambda self, *a, **kw: fault(orig(self, *a, **kw)))
    res = _run(cell)
    assert res["correct"] is False
    assert res["compared"]["mismatched_ids"]["value"] > 0


def test_missing_boundary_patch_is_not_correct(monkeypatch):
    from geomesa_tpu.store.memory import InMemoryDataStore
    monkeypatch.setattr(InMemoryDataStore, "_patch_mask",
                        lambda self, st, mask, *a, **kw: mask)
    res = _run("ais-region")
    assert res["correct"] is False
    assert res["failed"] == 0 and res["compared"]["mismatched_ids"]["value"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(run.HERE, "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _digest(root):
    out = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


COUNT_GENERATOR = """
import importlib.util, os, sys
_spec = importlib.util.spec_from_file_location(
    "count_box_base", os.path.join(os.path.dirname(__file__), "bbox_time.py"))
bt = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = bt
_spec.loader.exec_module(bt)
import numpy as np

LIMITS = {"count_error": 0}
Stream = bt.Stream

def warmup(ctx, mix, seed):
    return 0

def submit(ctx, q):
    return ctx.store.query_count(q.ecql(ctx.table.geom, ctx.table.dtg),
                                 ctx.type_name)

def answer(res):
    return int(res)

def size(got):
    return got

def facts(ctx, q, res):
    return {}

def check(table, q, got):
    return {"count_error": abs(got - len(bt.rows(table, q)))}

def control(table, q):
    return len(bt.rows(table, q, np.float32))
"""


def test_new_cell_kind_law_and_metric_are_files_only(tmp_path, monkeypatch):
    """A later PR adds a configuration with a new data law, a mix of a new
    kind of request (counts through ``query_count``), a per-layer metric
    and a cell as new files and new BENCHMARK.json entries; no existing file
    changes."""
    shutil.copytree(run.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    root = tmp_path / "benchmarks"
    before = _digest(root)
    conf_entry = next(c for c in BENCH["configs"] if c["name"] == "ais-50m")
    config = json.load(open(os.path.join(run.ROOT, conf_entry["file"])))
    config["name"] = "ais-flagged"
    config["spec"] = "flag:Integer," + config["spec"]
    config["columns"]["flag"] = {"law": "coin", "p": 0.3}
    (root / "configs/ais-flagged.json").write_text(json.dumps(config))
    (root / "laws/coin.py").write_text(
        "def make(rng, spec, n, ctx):\n"
        "    return (rng.random(n) < spec['p']).astype('int64')\n")
    (root / "generators/count_box.py").write_text(COUNT_GENERATOR)
    mix = json.load(open(os.path.join(run.HERE, "traffic", "region.json")))
    mix["generator"] = "count_box"
    mix["classes"] = [dict(mix["classes"][0], name="harbour", count=4,
                           share=[0.001, 0.01])]
    (root / "traffic/harbour-count.json").write_text(json.dumps(mix))
    (root / "metrics/count_per_query.py").write_text(
        "def read(run):\n"
        "    ok = [r['hits'] for r in run.records if r['ok']]\n"
        "    return sum(ok) / len(ok) if ok else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(conf_entry, name="ais-flagged",
                                 file="benchmarks/configs/ais-flagged.json"))
    bench["workloads"].append({"name": "ais-harbour-count",
                               "config": "ais-flagged",
                               "traffic": "harbour-count", "chips": 1,
                               "why": "rehearsal of an added cell"})
    bench["per_layer"].append({"name": "count_per_query", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "planner", "moves": "p50_ms",
                               "workloads": ["ais-harbour-count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for name in ("datagen", "roofline", "trace_reduce"):   # the copy's own
        monkeypatch.delitem(sys.modules, name)
    spec = importlib.util.spec_from_file_location(
        "run_copy", root / "run.py")
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    res = _run("ais-harbour-count", trace=1, module=copy)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == {"count_error"}
    assert res["metrics"]["count_per_query"]["value"] > 0
    ctl = _run("ais-harbour-count", module=copy, control=True)
    assert ctl["correct"] is False
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
