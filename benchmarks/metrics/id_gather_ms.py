"""Deferred id gather: mean ``result-ids`` span time over the requests
that had one (the object-array gather of a result of more than 100,000
ids, run when the caller first reads them; host clock)."""


def read(run):
    ms = [s["duration_ms"] for t in run.spans for s in t
          if s["kind"] == "result-ids"]
    return sum(ms) / len(ms) if ms else None
