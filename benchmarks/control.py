#!/usr/bin/env python
"""The control: the cell's reference in lower precision, in the program's
place.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 [--queries K]

For each seed it draws the cell's table and the first ``K`` requests of the
run's stream (K = the mix's ``compare``, as many as a run compares), answers
them with the generator's ``control`` and checks each answer as a run does
(``check``). A limit is sound only if this reads well above it on every
seed (PERF.md). Only numpy: no program, no device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import datagen  # noqa: E402
import run  # noqa: E402


def control(cell, seed: int, k: int, rows: int | None = None) -> dict:
    t0 = time.perf_counter()
    gen = cell.gen
    table = datagen.generate(cell.config, seed, rows)
    stream = gen.Stream(cell.mix, table, seed)
    sums, per_query = dict.fromkeys(gen.LIMITS, 0), []
    for _ in range(k):
        q = next(stream)
        got = gen.control(table, q)
        c = gen.check(table, q, got)
        for name, v in c.items():
            sums[name] += v
        per_query.append([q.cls, len(got), c])
    return {"seed": seed, **sums, "queries": k, "per_query": per_query,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    k = args.queries or cell.mix["compare"]
    for s in args.seeds.split(","):
        print(json.dumps(control(cell, int(s), k)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
