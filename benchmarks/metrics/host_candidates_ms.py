"""Mesh host candidate tier: mean ``host-candidates`` span time over the
requests that ran one (the f64 evaluation of the index's candidate rows
on the host, and their sort; host clock)."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "host-candidates"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
