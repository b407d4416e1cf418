"""CPU rehearsal of the per-layer metrics read from the program's spans: a
traced run of each cell reports them where its traffic reaches the step,
and each reader finds nothing in a window without its span."""

import json
import os
import types

import pytest

import run

ROWS = 200_000
SEED = 2**31 + 4242
BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"]
                if m["source"] == "program_span"
                and m["name"] not in ("plan_ms", "scan_ms")]
ALWAYS = {"index_search_ms", "gather_scan_ms", "boundary_patch_ms"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_span_metrics(cell, monkeypatch):
    records = []
    drive = run.drive

    def keep(*a, **kw):
        out = drive(*a, **kw)
        records.extend(out[0])
        return out
    monkeypatch.setattr(run, "drive", keep)
    res = run.run(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                   "--trace", "1"], require_chip=False, rows=ROWS)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()
           if k in SPAN_METRICS}
    want = set(ALWAYS)
    if cell == "gdelt-filtered":
        want.add("host_residual_ms")
    if any(r.get("hits", 0) > 100_000 for r in records):
        want.add("id_gather_ms")
    assert set(got) == want
    assert all(v > 0 for v in got.values())


# what the parent's program leaves in a window: the batcher's and the
# store's whole-request spans, none of the steps
PARENT = [[{"kind": "batcher-wait", "duration_ms": 9.0},
           {"kind": "dispatch", "duration_ms": 8.0},
           {"kind": "store-scan", "duration_ms": 7.0}]]


@pytest.mark.parametrize("spans", [[], PARENT], ids=["empty", "parent"])
@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_finds_nothing_without_its_span(name, spans):
    reader = run._module("metrics", name)
    assert reader.read(types.SimpleNamespace(spans=spans)) is None
