"""chip_smoke.py's phases in-process on the CPU, at a few thousand rows.

The rows are few, so ``geomesa.scan.host.rows`` is lowered to put the
wide queries on the device tiers they take at 100M rows on the chip."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
from geomesa_tpu.store.memory import HOST_SCAN_ROWS  # noqa: E402

ROWS = 20_000


@pytest.fixture
def device_tiers():
    HOST_SCAN_ROWS.set("1000")
    yield
    HOST_SCAN_ROWS.set(None)


def _failed(results):
    return [r for r in results if not r["ok"]]


def test_single_chip_phases_exact(device_tiers):
    lines = []
    results = chip_smoke.run_single(ROWS, 0, lines.append)
    assert not _failed(results), _failed(results)
    by = {r["phase"]: r for r in results}
    assert set(by) == {"load", "a_northstar", "b_gathered", "b_dense",
                       "d_batched", "e_knn", "f_contains",
                       "g_web"}
    assert by["a_northstar"]["tier"].startswith("Index-pruned host scan")
    assert by["b_gathered"]["tier"].startswith("Index-pruned device scan")
    assert by["b_dense"]["tier"].startswith("Device scan")
    assert by["b_dense"]["hits"] > 0
    assert by["d_batched"]["coalesced"] > 1
    assert by["d_batched"]["dispatch_failed"] == 0


def test_mesh_phases_exact(device_tiers):
    results = chip_smoke.run_mesh(ROWS, 0, lambda _: None, n_devices=4)
    assert not _failed(results), _failed(results)
    placed = next(r for r in results if r["phase"] == "mesh_placement")
    assert all(c["devices"] == 4 for c in placed["columns"].values())


def test_cpu_run_is_not_ok(device_tiers, capsys):
    assert chip_smoke.main(["--rows", "2000"]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[0]) == {"cut": {"rows": 2000,
                                          "from": chip_smoke.FULL_ROWS}}
    last = json.loads(out[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_failed_batch_dispatch_fails_the_phase(device_tiers, monkeypatch):
    """A fused dispatch that raises is replayed per query, so callers see
    success; the smoke must still fail the phase."""
    from geomesa_tpu.features import parse_spec
    from geomesa_tpu.store import InMemoryDataStore

    data = chip_smoke.Data(ROWS, 0)
    ds = InMemoryDataStore()
    ds.create_schema(parse_spec(chip_smoke.TYPE, chip_smoke.SPEC))
    ds.write_dict(chip_smoke.TYPE, data.ids,
                  {"dtg": data.ms, "geom": (data.x, data.y)})

    def broken(queries, explain_out=None):
        raise RuntimeError("device lost")

    monkeypatch.setattr(ds, "query_batched", broken)
    out = chip_smoke._batched_phase(ds, data, 0)
    assert out["exact"] and out["dispatch_failed"] > 0
    assert not out["tier_ok"]


def test_failed_ingest_index_build_fails_the_load(monkeypatch):
    from geomesa_tpu.store import InMemoryDataStore

    def broken(st):
        raise RuntimeError("compile failed")

    monkeypatch.setattr(InMemoryDataStore, "_EAGER_INDEX_ROWS", 1)
    monkeypatch.setattr(InMemoryDataStore, "_prewarm_join",
                        staticmethod(broken))
    out = chip_smoke._load(InMemoryDataStore(), chip_smoke.Data(2000, 0))
    assert out["exact"] and out["index_build_failed"] == 1
    assert not out["tier_ok"]
