"""CPU rehearsal of a mesh cell: a configuration that names ``"store":
"mesh"`` and a four-chip workload entry are all a later cell needs.

The benchmark definition is ``BENCHMARK.json`` with one configuration
(``ais-50m`` plus ``"store": "mesh"``) and one four-chip cell added in
memory; no file of the benchmark changes. The run goes through
``run.run`` end to end on four virtual CPU devices (``conftest.py``).
"""

import copy
import json
import os

import pytest

import run
from test_rehearsal import BENCH, ROWS, SEED

CELL = "ais-mesh-region"


def _bench(tmp_path, store="mesh", chips=4) -> dict:
    conf_entry = next(c for c in BENCH["configs"] if c["name"] == "ais-50m")
    with open(os.path.join(run.ROOT, conf_entry["file"])) as fh:
        config = json.load(fh)
    config["store"] = store
    path = tmp_path / "ais-mesh.json"
    path.write_text(json.dumps(config))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append(dict(conf_entry, name="ais-mesh",
                                 file=str(path)))
    bench["workloads"].append({"name": CELL, "config": "ais-mesh",
                               "traffic": "region", "chips": chips,
                               "why": "rehearsal of a mesh cell"})
    return bench


def _run(bench, trace=0):
    res = run.run(["--workload", CELL, "--seed", str(SEED), "--seconds", "2",
                   "--trace", str(trace)],
                  require_chip=False, rows=ROWS, bench=bench)
    json.loads(json.dumps(res))
    return res


def _summary(err: str) -> dict:
    """The run's summary line on stderr (tiers, compiles in the window)."""
    return next(json.loads(ln) for ln in err.splitlines()
                if ln.startswith('{"answers_compared"'))


def test_mesh_cell_runs_and_is_correct(tmp_path, monkeypatch, capsys):
    from geomesa_tpu.store import DistributedDataStore
    seen = {}
    load = run.load_store

    def spy(table, config, chips):
        ds = load(table, config, chips)
        seg = ds._state(config["type_name"]).segments[0]
        seen["store"] = type(ds)
        seen["devices"] = {c: len({s.device for s in
                                   getattr(seg, c).addressable_shards})
                           for c in run.MESH_COLUMNS}
        return ds

    monkeypatch.setattr(run, "load_store", spy)
    res = _run(_bench(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["mismatched_ids"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert seen["store"] is DistributedDataStore
    assert seen["devices"] == dict.fromkeys(run.MESH_COLUMNS, 4)
    summary = _summary(capsys.readouterr().err)
    assert {"host-candidates", "mesh-dense"} <= set(summary["tiers"])
    assert "other" not in summary["tiers"]
    assert summary["compiles_in_window"][0] == 0


def test_mesh_traced_run(tmp_path):
    res = _run(_bench(tmp_path), trace=1)
    assert res["correct"] is True, res["compared"]
    assert {"plan_ms", "scan_ms"} <= set(res["metrics"])


def _main(bench, monkeypatch, capsys) -> tuple:
    """``run.main`` on the cell, with ``bench`` in place of the file."""
    load = run.load_cell
    monkeypatch.setattr(run, "load_cell", lambda name, _=None: load(name,
                                                                    bench))
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "2",
                   "--trace", "0"])
    out = capsys.readouterr()
    return rc, out.out, out.err


@pytest.mark.parametrize("store,chips", [("mesh", 1), ("memory", 4)])
def test_store_and_chips_must_agree(tmp_path, monkeypatch, capsys, store,
                                    chips):
    """A mesh on one chip cannot shard; a memory store on four leaves
    three idle. Either ends the run before any work: exit 2, no result."""
    rc, out, err = _main(_bench(tmp_path, store=store, chips=chips),
                         monkeypatch, capsys)
    assert rc == 2 and out == ""
    assert f"a {store} store in a cell of {chips} chip(s)" in err


def test_unknown_store_is_refused(tmp_path, monkeypatch, capsys):
    rc, out, err = _main(_bench(tmp_path, store="disk"), monkeypatch, capsys)
    assert rc == 2 and out == "" and "'disk'" in err
