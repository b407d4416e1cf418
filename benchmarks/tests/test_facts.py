"""``bbox_time.facts``: the tier each store's explain text names.

One text per tier of each store, as the stores write them (the lines
before the tier line are the plan's and name no tier). The single chip's
tiers keep their names; the mesh store's have names of their own, so no
reader of single-chip tiers counts mesh work.
"""

import types

import pytest

from generators import bbox_time

BOX = "BBOX(geom, 7.87, -6.08, 45.2, 19.6)"
DURING = "dtg DURING 2016-08-10T15:40:24.390Z/2016-09-25T09:37:56.233Z"
PLAN = (f"Planning 'ais' filter=({BOX} AND {DURING})\n"
        f"  Strategy options for '({BOX} AND {DURING})':\n"
        f"    Selected: z3[primary=({BOX} AND {DURING}), secondary=None, "
        "cost=4599]\n")

TEXTS = {
    # (store, tier): (the tier's lines, candidates)
    ("memory", "host"): (
        "  Index-pruned host scan: 301 hit(s) of 200000, 1 box(es), "
        "1 interval(s)\n  Hits: 301", 0),
    ("memory", "gathered"): (
        "  Index-pruned device scan: 2210 candidate row(s) of 200000, "
        "1 box(es), 1 interval(s)\n  Boundary recheck: 4 candidate(s)\n"
        "  Hits: 690", 2210),
    ("memory", "dense"): (
        "  Device scan: 1 box(es), 1 interval(s), n=200000\n"
        "  Boundary recheck: 3 candidate(s)\n  Hits: 147895", 0),
    ("memory", "dense-pallas"): (
        "  Pallas device scan: 1 box(es), 1 interval(s), n=200000\n"
        "  Hits: 147895", 0),
    ("memory", "batched"): (
        f"Batched 'ais' filter=({BOX} AND {DURING})\n  Hits: 12", 0),
    ("mesh", "host"): (
        "  Index-pruned host scan: 301 hit(s) of 200000, 1 box(es), "
        "1 interval(s)\n  Hits: 301", 0),
    ("mesh", "host-candidates"): (
        "  Index-pruned host candidate scan: 2210 candidate row(s) of "
        "200000, 1 box(es), 1 interval(s)\n  Hits: 690", 2210),
    ("mesh", "mesh-dense"): (
        "  Distributed scan over 4 device(s), 1 segment(s), n=200000, "
        "1 box(es), 1 interval(s)\n  Hits: 147895", 0),
    ("any", "other"): ("  Store is empty", 0),
}


def _facts(text: str) -> dict:
    ctx = types.SimpleNamespace(table=types.SimpleNamespace(types={}))
    q = bbox_time.Request(0, "c", (0.0, 0.0, 1.0, 1.0), None, [])
    res = types.SimpleNamespace(explain=types.SimpleNamespace(text=text))
    return bbox_time.facts(ctx, q, res)


@pytest.mark.parametrize("key", list(TEXTS), ids="-".join)
def test_tier_of_each_store(key):
    lines, cand = TEXTS[key]
    f = _facts(PLAN + lines)
    assert f["tier"] == key[1].replace("dense-pallas", "dense")
    assert f["candidates"] == cand
    assert f["device_residual"] is False and f["pred_types"] == []


def test_device_residual_and_no_explain():
    f = _facts(PLAN + "  Device scan: 1 box(es), 1 interval(s), n=9\n"
               "  Device residual scan (dense)\n  Hits: 3")
    assert (f["tier"], f["device_residual"]) == ("dense", True)
    assert bbox_time.facts(types.SimpleNamespace(table=None),
                           bbox_time.Request(0, "c", (0, 0, 1, 1), None, []),
                           object())["tier"] == "other"
