"""Host residual: mean ``residual`` span time over the requests whose
residual ran on the host (``batch.take`` of every column at the
candidates, then the filter in f64; host clock)."""


def read(run):
    per = []
    for t in run.spans:
        ms = [s["duration_ms"] for s in t if s["kind"] == "residual"
              and s.get("attrs", {}).get("where") == "host"]
        if ms:
            per.append(sum(ms))
    return sum(per) / len(per) if per else None
