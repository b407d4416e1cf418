"""Mesh dense tier, device side: mean ``mesh-scan`` span time over the
requests that ran one (the shard-mapped z3 pass, the hit count, the
compaction into hit rows and their download; host clock)."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "mesh-scan"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
