"""BBOX + DURING (+ attribute predicates) queries through the shared batcher.

A mix (``traffic/<name>.json``, ``"generator": "bbox_time"``) lists query
classes. Per cycle a class sends ``count`` queries; each holds a ``share``
of the table's rows (of the window's rows with ``"share_of": "window"``;
log-uniform over the range) inside a window of
``days`` (log-uniform, the k-th share with the k-th window), with a box of
lon:lat ``aspect`` centred on a row of an ``anchor`` group (weighted) or
covering the ``"world"``, and draws 1 to n predicates from the mix's
``pool``.

Every seed sends the same set of sizes (shares and windows), in an order
fixed by the mix's ``order_seed``, so a window of any seed holds the same
work. Everything else is drawn afresh from the run seed for every query:
the window's start, the centre, the aspect, the predicates and their
values. A box is sized by rows, not degrees, so a fresh place does not
change how much the query scans: its half-height is the distance (lon
scaled by the aspect) within which a sample of the window's rows holds the
share.

Box edges, time bounds and predicate thresholds are anchored on generated
rows: each edge is the coordinate (time, value) of a row inside the query,
either exactly (the row lies on the closed bound, or one millisecond inside
an open one) or one f64 ulp beside it (the row lies just outside). So every
query has rows on or within one f32 ulp of its bounds, the exact f64
boundary patch has work in every query, and an answer computed in f32 is
wrong in nearly every query.

The reference (``rows``) is plain numpy over the generated arrays: ECQL's
closed BBOX, DURING open at both ends, IN/=/<=/>= on the stored values.
"""

from __future__ import annotations

import dataclasses
import sys

import numpy as np

SAMPLE = 1 << 20        # rows a query's bounds are drawn from
CHUNK = 1 << 24         # reference rows per block, so temporaries stay small
MS_DAY = 86_400_000
WORLD = (-180.0, -90.0, 180.0, 90.0)
WARM_MIX = 2            # the mix's own queries in the warm-up

# numbers compared with the reference, each with its limit (PERF.md): the
# answers are exact ids, so a single id too many, too few or twice fails
LIMITS = {"mismatched_ids": 0}


@dataclasses.dataclass
class Pred:
    op: str            # "IN", "=", "<=", ">="
    attr: str
    value: object      # list of str for IN, a number otherwise

    def ecql(self) -> str:
        if self.op == "IN":
            vals = ", ".join(f"'{v}'" for v in self.value)
            return f"{self.attr} IN ({vals})"
        return f"{self.attr} {self.op} {_num(self.value)}"


@dataclasses.dataclass
class Request:
    seq: int
    cls: str
    box: tuple                  # (xmin, ymin, xmax, ymax) float64
    during: tuple | None        # (t0, t1) epoch ms, both exclusive
    preds: list

    def ecql(self, geom: str, dtg: str) -> str:
        parts = ["BBOX({}, {})".format(geom, ", ".join(_num(v)
                                                      for v in self.box))]
        if self.during is not None:
            t0, t1 = self.during
            parts.append(f"{dtg} DURING {_iso(t0)}/{_iso(t1)}")
        parts += [p.ecql() for p in self.preds]
        return " AND ".join(parts)


def _num(v) -> str:
    if isinstance(v, (int, np.integer)):
        return str(int(v))
    return np.format_float_positional(float(v), unique=True, trim="-")


def _iso(ms: int) -> str:
    return str(np.datetime64(int(ms), "ms")) + "Z"


def _log(bounds, u: float) -> float:
    lo, hi = bounds
    return lo * (hi / lo) ** u


def sizes(mix: dict) -> list:
    """(class, share, days) of each query of a cycle, in the cycle's order:
    the same for every seed."""
    slots = []
    for c in mix["classes"]:
        for k in range(c["count"]):
            u = (k + 0.5) / c["count"]
            slots.append((c, _log(c["share"], u) if "share" in c else 1.0,
                          _log(c["days"], u) if "days" in c else None))
    order = np.random.default_rng(mix["order_seed"]).permutation(len(slots))
    return [slots[i] for i in order]


class Stream:
    """Endless request stream for one run: cycle after cycle of the mix."""

    def __init__(self, mix: dict, table, seed: int, stream: int = 2):
        self.mix = mix
        self.t = table
        self.rng = np.random.default_rng([seed, stream])
        x, y = table.cols[table.geom]
        ms = table.cols[table.dtg]
        self.pick = self.rng.integers(0, table.n, min(SAMPLE, table.n))
        self.sx, self.sy, self.st = x[self.pick], y[self.pick], ms[self.pick]
        self.t_lo, self.t_hi = int(ms.min()), int(ms.max()) + 1
        self.slots = sizes(mix)
        self.seq = 0

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        c, share, days = self.slots[self.seq % len(self.slots)]
        q = self.draw(c["name"], share, days, c.get("anchor", "world"),
                      c.get("aspect", [1, 1]), c.get("predicates", [0, 0]),
                      of_window=c.get("share_of") == "window")
        q.seq = self.seq
        self.seq += 1
        return q

    def draw(self, cls, share, days, anchor, aspect, npred,
             all_preds: bool = False, of_window: bool = False) -> Request:
        during, inwin = None, np.arange(len(self.pick))
        if days is not None:
            span = days * MS_DAY
            t0 = self.rng.uniform(self.t_lo, max(self.t_lo,
                                                 self.t_hi - span))
            during = (int(t0), int(t0 + span))
            inwin = np.flatnonzero((self.st > during[0])
                                   & (self.st < during[1]))
        want = int(share * len(inwin if of_window else self.pick))
        box = WORLD
        if anchor != "world" and 0 < want < len(inwin):
            box = self._box(inwin, want, anchor,
                            self.rng.uniform(*aspect))
        box, during = self._anchored(box, during, box == WORLD)
        pool = self.mix.get("pool", [])
        k = len(pool) if all_preds else int(
            self.rng.integers(npred[0], npred[1] + 1))
        picks = sorted(self.rng.choice(len(pool), k, replace=False)) \
            if k else []
        return Request(0, cls, box, during, [self._pred(pool[i])
                                             for i in picks])

    def _box(self, inwin, want: int, anchor: dict, aspect: float) -> tuple:
        """The box of lon:lat ``aspect`` around a fresh centre that holds
        ``want`` of the window's sampled rows."""
        names = list(anchor)
        g = names[int(self.rng.choice(len(names), p=np.array(
            [anchor[n] for n in names], float) / sum(anchor.values())))]
        if g == "uniform":
            cx, cy = self.rng.uniform(-180, 180), self.rng.uniform(-90, 90)
        else:
            a, b = self.t.groups[g]
            ours = inwin[(self.pick[inwin] >= a) & (self.pick[inwin] < b)]
            i = self.rng.choice(ours if len(ours) else inwin)
            cx, cy = float(self.sx[i]), float(self.sy[i])
        d = np.maximum(np.abs(self.sx[inwin] - cx) / aspect,
                       np.abs(self.sy[inwin] - cy))
        h = float(np.partition(d, want - 1)[want - 1]) + 1e-9
        return (max(-180.0, cx - aspect * h), max(-90.0, cy - h),
                min(180.0, cx + aspect * h), min(90.0, cy + h))

    def _coin(self) -> bool:
        return bool(self.rng.integers(2))

    def _anchored(self, box, during, world: bool):
        """Move each bound onto the extreme sampled row inside the query:
        exactly onto it, or one f64 ulp (one ms) so that it falls outside."""
        x0, y0, x1, y1 = box
        m = ((self.sx >= x0) & (self.sx <= x1)
             & (self.sy >= y0) & (self.sy <= y1))
        if during is not None:
            m &= (self.st > during[0]) & (self.st < during[1])
        i = np.flatnonzero(m)
        if len(i) < 2:          # an empty patch of sea: leave it as drawn
            return tuple(float(b) for b in box), during
        if not world:
            lo, hi = [], []
            for v in (self.sx[i], self.sy[i]):
                a, b = float(v.min()), float(v.max())
                # a shift never turns the box inside out
                a2 = a if self._coin() else float(np.nextafter(a, np.inf))
                b2 = b if self._coin() else float(np.nextafter(b, -np.inf))
                lo.append(a2 if a2 <= b2 else a)
                hi.append(b2 if a2 <= b2 else b)
            box = (lo[0], lo[1], hi[0], hi[1])
        if during is not None:
            ts = self.st[i]
            t0, t1 = int(ts.min()), int(ts.max())
            t0 = t0 - 1 if self._coin() else t0
            t1 = t1 + 1 if self._coin() else t1
            during = (t0, t1) if t1 - t0 >= 2 else (t0 - 1, t1 + 1)
        return tuple(float(b) for b in box), during

    def _pred(self, p: dict) -> Pred:
        attr = p["attr"]
        if p["op"] == "IN":
            _codes, vocab = self.t.cols[attr]
            k = int(self.rng.integers(p["count"][0], p["count"][1] + 1))
            pick = self.rng.choice(len(vocab), k, replace=False)
            return Pred("IN", attr, sorted(str(vocab[i]) for i in pick))
        if "values" in p:
            return Pred(p["op"], attr, p["values"][
                int(self.rng.integers(len(p["values"])))])
        # a threshold on a sampled row's own value, moved by ``offset`` or,
        # without one, left on the row or one f64 ulp beside it
        col = self.t.cols[attr]
        v = col[self.pick[int(self.rng.integers(len(self.pick)))]]
        if np.issubdtype(col.dtype, np.integer):
            return Pred(p["op"], attr, int(v) + int(p.get("offset", 0)))
        if "offset" in p:
            return Pred(p["op"], attr, float(v) + float(p["offset"]))
        if not self._coin():
            v = np.nextafter(v, np.inf if p["op"] == ">=" else -np.inf)
        return Pred(p["op"], attr, float(v))


def warmup(ctx, mix: dict, seed: int) -> int:
    """Set-up: one dense world query with every predicate of the pool (the
    dense z3 kernel and the device residual's compares), the gathered z3
    kernel at each power-of-two row count it pads candidates to between
    the host tier's cap and the dense tier (called as the store calls it:
    boxes found by the plan reach these classes only by chance), and a few
    of the mix's own queries. A mesh store evaluates its candidates on the
    host and takes the dense tier's hit-row sizes instead (``_warm_mesh``).
    No answer is read: only the timed call compiles. Returns the number of
    requests sent."""
    from geomesa_tpu.index.zkeys import SCAN_BLOCK_THRESHOLD
    from geomesa_tpu.scan import zscan
    from geomesa_tpu.store import DistributedDataStore
    from geomesa_tpu.store.memory import HOST_SCAN_ROWS
    s = Stream(mix, ctx.table, seed, stream=4)
    days = (s.t_hi - s.t_lo) / MS_DAY
    submit(ctx, s.draw("warm", 1.0, 0.6 * days, "world", [1, 1], [0, 0],
                       all_preds=True))
    n = ctx.table.n
    lo = int(HOST_SCAN_ROWS.get())
    hi = float(SCAN_BLOCK_THRESHOLD.get()) * n
    if isinstance(ctx.store, DistributedDataStore):
        _warm_mesh(ctx, s, lo)
    else:
        try:
            data = ctx.store._state(ctx.type_name).scan_data
            sq = zscan.make_query([WORLD], [(s.t_lo, s.t_hi)])
            k = lo.bit_length()
            while (1 << (k - 1)) < hi:
                zscan.scan_mask_at(data, sq, np.arange(min(1 << k, n),
                                                       dtype=np.int32))
                k += 1
        except (AttributeError, TypeError) as e:  # the store's tiers moved
            print(f"warm-up: gathered classes not warmed ({e!r})",
                  file=sys.stderr)
    for _ in range(WARM_MIX):
        submit(ctx, next(s))
    return 1 + WARM_MIX


def _warm_mesh(ctx, s: Stream, lo: int):
    """The mesh store's dense tier compacts its sharded mask into a buffer
    of hit rows padded to a power of two: warm each size from the host
    tier's cap to the whole table, called as the store calls it."""
    from geomesa_tpu.parallel import mesh
    from geomesa_tpu.scan import zscan
    sq = zscan.make_query([WORLD], [(s.t_lo, s.t_hi)])
    for seg in ctx.store._state(ctx.type_name).segments:
        mask = mesh.distributed_scan_mask(seg, sq)
        k = max(lo - 1, 1).bit_length()
        while (1 << (k - 1)) < seg.n:
            mesh._mask_hit_rows(mask, 1 << k).block_until_ready()
            k += 1


def submit(ctx, q: Request):
    """The timed call: one query through the shared batcher."""
    from geomesa_tpu.index.api import Query
    return ctx.batcher.query(Query(ctx.type_name,
                                   q.ecql(ctx.table.geom, ctx.table.dtg)))


def answer(res):
    """What the caller holds once the request is done: the feature ids."""
    return res.ids


def size(got) -> int:
    """How large an answer is (the compared sample keeps the largest)."""
    return len(got)


def facts(ctx, q: Request, res) -> dict:
    """The tier the plan chose, its candidates, whether the device residual
    ran, and the predicates' types: what the per-layer readers need. The
    mesh store's tiers have names of their own (``host-candidates``, the
    f64 host scan of the index's candidates; ``mesh-dense``, the sharded
    scan), so no reader counts them as the single chip's work."""
    ex = getattr(res, "explain", None)
    text = ex.text if ex is not None else ""
    tier, cand = "other", 0
    for ln in text.splitlines():
        ln = ln.strip()
        if ln.startswith("Index-pruned host scan"):
            tier = "host"
        elif ln.startswith("Index-pruned device scan:"):
            tier, cand = "gathered", int(ln.split(":")[1].split()[0])
        elif ln.startswith("Index-pruned host candidate scan:"):
            tier, cand = "host-candidates", int(ln.split(":")[1].split()[0])
        elif ln.startswith(("Device scan:", "Pallas device scan:")):
            tier = "dense"
        elif ln.startswith("Distributed scan over"):
            tier = "mesh-dense"
        elif ln.startswith("Batched"):
            tier = "batched"
        else:
            continue
        break
    return {"tier": tier, "candidates": cand,
            "device_residual": "Device residual scan (dense)" in text,
            "pred_types": [ctx.table.types[p.attr] for p in q.preds]}


def _cmp(a: np.ndarray, op: str, v):
    if op == "<=":
        return a <= v
    if op == ">=":
        return a >= v
    if op == "=":
        return a == v
    raise ValueError(op)


def rows(table, q: Request, dtype=np.float64) -> np.ndarray:
    """Sorted row indices matching ``q``. ``np.float64`` is the
    configuration's precision; the control passes ``np.float32``: every
    value compared, and every bound it is compared with, rounded to f32
    first, the 32-bit lane a lower-precision scan would use for all of
    them. Small integer attributes (codes, counts) are exact in f32."""
    x, y = table.cols[table.geom]
    ms = table.cols[table.dtg]
    f = np.dtype(dtype).type
    xmin, ymin, xmax, ymax = (f(v) for v in q.box)
    in_codes = {}
    for p in q.preds:
        if p.op == "IN":
            _codes, vocab = table.cols[p.attr]
            in_codes[p.attr] = np.flatnonzero(
                np.isin(vocab.astype(str), np.asarray(p.value, dtype=str)))
    out = []
    for s in range(0, table.n, CHUNK):
        sl = slice(s, min(s + CHUNK, table.n))
        cx = x[sl].astype(dtype, copy=False)
        cy = y[sl].astype(dtype, copy=False)
        m = (cx >= xmin) & (cx <= xmax) & (cy >= ymin) & (cy <= ymax)
        if q.during is not None:
            t = ms[sl]
            t0, t1 = q.during
            if dtype != np.float64:
                t, t0, t1 = t.astype(dtype), f(t0), f(t1)
            m &= (t > t0) & (t < t1)
        for p in q.preds:
            col = table.cols[p.attr]
            if p.op == "IN":
                m &= np.isin(col[0][sl], in_codes[p.attr])
            elif dtype != np.float64 or col.dtype.kind == "f":
                m &= _cmp(col[sl].astype(dtype), p.op, f(p.value))
            else:
                m &= _cmp(col[sl], p.op, p.value)
        out.append(np.flatnonzero(m) + s)
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def check(table, q: Request, got) -> dict:
    """Ids in one answer and not the other (the symmetric difference), plus
    every id the answer holds more than once."""
    want = table.ids[rows(table, q)]
    got = np.asarray(got, dtype=object)
    if got.ndim != 1:
        return {"mismatched_ids": len(want) + got.size}
    if len(got) == len(want) and bool((got == want).all()):
        return {"mismatched_ids": 0}
    uniq = set(got.tolist())
    return {"mismatched_ids": len(uniq ^ set(want.tolist()))
            + len(got) - len(uniq)}


def control(table, q: Request):
    """The control's answer: the reference in f32 in the program's place."""
    return table.ids[rows(table, q, np.float32)]
