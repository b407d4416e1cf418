"""Profiler trace -> device busy time, kernel time by name, idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs as flat records ``{"plane", "line", "name",
"start_ns", "dur_ns"}``: every event of the device planes (``/device:TPU:N``)
and the harness's own host annotations (names starting ``bench.``).
``Reduction`` works on those records only, so a test can feed it a small
recorded trace (``tests/data/trace_small.json``).

- Busy time is the union of the intervals of one device's op line (``XLA
  Ops``, else ``XLA Modules``, else all its lines), clipped to the window,
  averaged over the devices that ran anything.
- Kernel time is the sum of the module events (``XLA Modules``) of one name,
  the jit name with its ``(id)`` suffix removed, e.g. ``jit__mask_body``,
  clipped to the window.
- Each idle gap is labelled with the deepest ``bench.*`` annotation that
  covers its midpoint: what the harness was waiting on at the time.

``python benchmarks/trace_reduce.py <file.xplane.pb>`` prints the planes,
lines and the most frequent names, to see how a new kernel is named.
"""

from __future__ import annotations

import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
MODULE_LINE = "XLA Modules"
ANNOTATION = "bench."


def load(path: str) -> list[dict]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith(ANNOTATION):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": e.name, "start_ns": int(e.start_ns),
                                "dur_ns": int(e.duration_ns)})
    return out


def kernel_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduction:
    def __init__(self, records: list[dict], t0_ns: int, t1_ns: int):
        self.t0, self.t1 = int(t0_ns), int(t1_ns)
        self.window_s = (self.t1 - self.t0) / 1e9
        dev = [r for r in records if DEVICE_PLANE.match(r["plane"])]
        self.devices = sorted({r["plane"] for r in dev})
        self.busy = {}
        for d in self.devices:
            mine = [r for r in dev if r["plane"] == d]
            lines = {r["line"] for r in mine}
            pick = next((ln for ln in OP_LINES if ln in lines), None)
            iv = [(max(r["start_ns"], self.t0),
                   min(r["start_ns"] + r["dur_ns"], self.t1))
                  for r in mine if pick is None or r["line"] == pick]
            self.busy[d] = _union([(s, e) for s, e in iv if e > s])
        self.modules = [r for r in dev if r["line"] == MODULE_LINE
                        and r["start_ns"] < self.t1
                        and r["start_ns"] + r["dur_ns"] > self.t0]
        self.notes = [r for r in records
                      if r["name"].startswith(ANNOTATION)
                      and not DEVICE_PLANE.match(r["plane"])]

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices used."""
        used = [b for b in self.busy.values() if b]
        if not used:
            return 0.0
        return sum(sum(e - s for s, e in b) for b in used) / len(used) / 1e9

    def kernel_s(self, names=None, within=None) -> dict:
        """Device seconds per kernel name, optionally only the names in
        ``names`` and only events that start inside one of the ``within``
        (start_ns, end_ns) intervals."""
        out: dict = {}
        for r in self.modules:
            k = kernel_name(r["name"])
            if names is not None and k not in names:
                continue
            if within is not None and not any(
                    s <= r["start_ns"] < e for s, e in within):
                continue
            end = min(r["start_ns"] + r["dur_ns"], self.t1)
            out[k] = out.get(k, 0.0) + (end - max(r["start_ns"],
                                                  self.t0)) / 1e9
        return out

    def annotations(self, name: str) -> dict:
        """{tag: (start_ns, end_ns)} of the harness annotations named
        ``name#tag`` (the harness tags each request with its sequence
        number)."""
        out = {}
        for r in self.notes:
            base, _, tag = r["name"].partition("#")
            if base == name:
                out[tag] = (r["start_ns"], r["start_ns"] + r["dur_ns"])
        return out

    def gaps(self) -> list:
        """Idle intervals of the first busy device, with a label each."""
        b = next((v for v in self.busy.values() if v), [])
        edges = [self.t0] + [x for s, e in b for x in (s, e)] + [self.t1]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                out.append((e - s, self._label((s + e) // 2)))
        return sorted(out, reverse=True)

    def _label(self, t: int) -> str:
        cover = [r for r in self.notes
                 if r["start_ns"] <= t < r["start_ns"] + r["dur_ns"]]
        if not cover:
            return "outside any harness call"
        deepest = min(cover, key=lambda r: r["dur_ns"])
        return deepest["name"].partition("#")[0]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.kernel_s().items(), key=lambda kv: -kv[1])[:top]
        gaps = [[label, ns / 1e9] for ns, label in self.gaps()[:top]]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def summary(path: str, top: int = 15) -> str:
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"plane {plane.name}")
        for line in plane.lines:
            counts: dict = {}
            for e in line.events:
                counts[e.name] = counts.get(e.name, 0) + 1
            common = sorted(counts.items(), key=lambda kv: -kv[1])[:top]
            lines.append(f"  line {line.name!r}: {sum(counts.values())} "
                         f"events; {common}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(summary(sys.argv[1]))
