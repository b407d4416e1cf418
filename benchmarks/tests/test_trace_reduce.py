"""trace_reduce.py: busy and idle arithmetic, kernel time by name and gap
labels, by hand on a synthetic trace, and on a small trace recorded on the
chip (``data/trace_small.json``, cut from an ais-region traced run)."""

import json
import os

import pytest

import trace_reduce

TPU = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, end):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": end - start}


SYNTH = [
    ev(TPU, "XLA Ops", "fusion.1", 100, 150),
    ev(TPU, "XLA Ops", "fusion.2", 140, 200),      # overlaps fusion.1
    ev(TPU, "XLA Ops", "fusion.3", 300, 350),
    ev(TPU, "XLA Ops", "fusion.1", 900, 1100),     # runs past the window
    ev(TPU, "XLA Modules", "jit__mask_body(12)", 100, 200),
    ev(TPU, "XLA Modules", "jit__gather_scan_mask(13)", 300, 350),
    ev(TPU, "XLA Modules", "jit__mask_body(12)", 900, 1100),
    ev(HOST, "python", "bench.window", 0, 1000),
    ev(HOST, "python", "bench.query#0", 50, 400),
    ev(HOST, "python", "bench.ids#0", 400, 700),
    ev(HOST, "python", "bench.query#1", 700, 1000),
    ev(HOST, "python", "PjRt execute", 0, 1000),   # not ours: ignored
]


def test_busy_idle_and_kernels_by_hand():
    red = trace_reduce.Reduction(SYNTH, 0, 1000)
    # union of ops inside [0, 1000): 100-200, 300-350, 900-1000 = 250 ns
    assert red.busy_s == pytest.approx(250e-9)
    assert red.window_s == pytest.approx(1000e-9)
    k = red.kernel_s()
    assert k["jit__mask_body"] == pytest.approx(200e-9)   # 100 + 100 clipped
    assert k["jit__gather_scan_mask"] == pytest.approx(50e-9)
    q = red.annotations("bench.query")
    assert q == {"0": (50, 400), "1": (700, 1000)}
    inside = red.kernel_s(within=[q["0"]])
    assert inside == pytest.approx({"jit__mask_body": 100e-9,
                                    "jit__gather_scan_mask": 50e-9})


def test_gaps_labelled_by_the_deepest_annotation():
    red = trace_reduce.Reduction(SYNTH, 0, 1000)
    gaps = red.gaps()
    # idle 0-100 (mid 50: query#0), 200-300 (query#0), 350-900 (mid 625:
    # ids#0 is the deepest annotation there)
    assert gaps == [(550, "bench.ids"), (100, "bench.query"),
                    (100, "bench.query")]
    assert sum(g for g, _ in gaps) / 1e9 == pytest.approx(
        red.window_s - red.busy_s)
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "jit__mask_body"
    assert bd["idle_gaps"][0] == ["bench.ids", pytest.approx(550e-9)]


def test_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_small.json")
    with open(path) as fh:
        rec = json.load(fh)
    red = trace_reduce.Reduction(rec["records"], *rec["window_ns"])
    assert red.devices == [TPU]
    assert 0 < red.busy_s < red.window_s
    gap_s = sum(g for g, _ in red.gaps()) / 1e9
    assert gap_s == pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    k = red.kernel_s()
    assert set(rec["kernels"]) <= set(k)
    for name, secs in rec["kernels"].items():
        assert k[name] == pytest.approx(secs, rel=1e-9)
    assert red.annotations("bench.query")
