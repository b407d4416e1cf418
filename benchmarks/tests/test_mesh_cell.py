"""CPU rehearsal of the accepted four-chip cell ``mesh-ais-region``: the
entry of ``BENCHMARK.json`` itself (``ais-100m-mesh``, ``"store": "mesh"``)
through ``run.run`` on four virtual CPU devices at a row cut, traced.

The span readers read the run's own spans. The CPU trace has no device
plane, so the two roofline readers are given the run's spans beside a
recorded-format trace of the two mesh kernels on four chips
(``trace_reduce.Reduction`` over flat records, as ``trace_small.json``).
"""

import json
import types

import pytest

import mesh_roofline
import run
import trace_reduce

CELL = "mesh-ais-region"
ROWS = 200_000
SEED = 2**31 + 2727
SPAN_READERS = ("host_candidates_ms", "mesh_scan_ms", "mesh_patch_ms")
ROOFLINES = ("mesh_zscan_roofline", "mesh_compact_roofline")
V5E = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def traced():
    """(result, the view the readers were given, the run's records)."""
    seen, records = {}, []
    module, drive = run._module, run.drive

    def spy_module(kind, name):
        mod = module(kind, name)
        if kind == "metrics":
            read = mod.read
            mod.read = lambda view: (seen.setdefault("view", view),
                                     read(view))[1]
        return mod

    def keep(*a, **kw):
        out = drive(*a, **kw)
        records.extend(out[0])
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(run, "_module", spy_module)
    mp.setattr(run, "drive", keep)
    try:
        res = run.run(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "2", "--trace", "1"],
                      require_chip=False, rows=ROWS)
    finally:
        mp.undo()
    json.loads(json.dumps(res))
    return res, seen["view"], records


def test_cell_is_the_accepted_mesh_entry():
    cell = run.load_cell(CELL)
    assert cell.chips == 4 and cell.config["store"] == "mesh"
    assert cell.config["rows"] == 100_000_000
    assert {m["name"] for m in cell.layer} >= set(SPAN_READERS + ROOFLINES)


def test_traced_cell_is_correct(traced):
    res, _view, records = traced
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["mismatched_ids"]["value"] == 0
    assert res["failed"] == 0
    assert {r["tier"] for r in records} == {"host", "host-candidates",
                                            "mesh-dense"}


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_reports(traced, name):
    res, _view, _records = traced
    assert res["metrics"][name]["value"] > 0


def test_mesh_scan_spans_count_every_hit(traced):
    _res, view, records = traced
    scans = mesh_roofline.scans(view)
    assert len(scans) == sum(r["tier"] == "mesh-dense" for r in records)
    for a in scans:
        assert a["rows"] == ROWS and a["segments"] == 1 and a["shards"] == 4
        assert sum(a["shard_hits"]) == a["hits"] <= a["cap"]
        assert a["d2h_bytes"] == 4 * a["cap"]


def _kernel_trace(secs: dict) -> trace_reduce.Reduction:
    """A window of 1 s in which each of four chips runs each kernel once,
    for its share of ``secs[kernel]``."""
    recs = []
    for chip in range(4):
        t = 1_000_000
        for k, (kernel, s) in enumerate(secs.items()):
            dur = int(s * 1e9 / 4)
            recs.append({"plane": f"/device:TPU:{chip}",
                         "line": "XLA Modules", "name": f"{kernel}({k})",
                         "start_ns": t, "dur_ns": dur})
            t += dur
    return trace_reduce.Reduction(recs, 0, 1_000_000_000)


@pytest.mark.parametrize("name", ROOFLINES)
def test_roofline_reader_reports(traced, name):
    _res, view, _records = traced
    secs = {mesh_roofline.SCAN_KERNEL: 2e-4,
            mesh_roofline.COMPACT_KERNEL: 3e-3}
    chip = types.SimpleNamespace(**dict(vars(view), trace=_kernel_trace(secs),
                                         peak=V5E))
    scans = mesh_roofline.scans(view)
    if name == "mesh_zscan_roofline":
        nbytes = sum(25 * a["rows"] for a in scans)
        s = secs[mesh_roofline.SCAN_KERNEL]
    else:
        nbytes = sum(a["rows"] + 4 * a["cap"] for a in scans)
        s = secs[mesh_roofline.COMPACT_KERNEL]
    got = run._module("metrics", name).read(chip)
    assert got == pytest.approx(100 * nbytes / 819e9 / s, rel=1e-6)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ROOFLINES)
def test_roofline_reader_finds_nothing_without_its_kernel(traced, name):
    """The parent's program writes no ``mesh-scan`` span and names its
    shard-mapped pass ``jit_body``: the reader leaves its metric out."""
    _res, view, _records = traced
    reader = run._module("metrics", name)
    parent = types.SimpleNamespace(
        spans=[], trace=_kernel_trace({"jit_body": 2e-4,
                                       mesh_roofline.COMPACT_KERNEL: 3e-3}),
        peak=V5E)
    assert reader.read(parent) is None
    cpu = types.SimpleNamespace(**dict(vars(view), peak=None))
    assert reader.read(cpu) is None


def test_mesh_roofline_bytes():
    # 100M rows x (6 columns x 4 B + 1 mask byte) = 2.5 GB
    assert mesh_roofline.scan_bytes(100_000_000) == 2_500_000_000
    # 100M mask bytes read + 2^27 int32 hit rows written
    assert mesh_roofline.compact_bytes(100_000_000, 1 << 27) \
        == 100_000_000 + 536_870_912
    assert mesh_roofline.compact_bytes(1_000, 0) == 1_000
