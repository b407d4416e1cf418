"""Boundary patch: mean ``boundary-patch`` span time over the requests
that ran one (the exact f64 recheck of rows on a query bound's cell and
the compaction of the mask into sorted rows; host clock)."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "boundary-patch"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
