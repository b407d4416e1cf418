"""Mesh dense tier, host side: mean ``mesh-patch`` span time over the
requests that ran one (the boundary candidates, their two-float and f64
verdicts, and the splice of the flipped rows into the hit rows; host
clock)."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "mesh-patch"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
