"""Index search: the window's ``index-search`` span time over its requests
(the z-range decomposition and the host tier's exact pass,
``zkeys.search_rows``; host clock)."""


def read(run):
    traces = [t for t in run.spans if any(s["kind"] == "store-scan"
                                          for s in t)]
    ms = [s["duration_ms"] for t in traces for s in t
          if s["kind"] == "index-search"]
    return sum(ms) / len(traces) if ms else None
