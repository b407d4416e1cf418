"""Scan and result assembly: mean of the audit events' ``scan_time_ms``
(from the start of ``_execute``, through the tier's scan, boundary patch
and residual, to the end of ``_finish_query``; host clock)."""


def read(run):
    ev = run.audit
    return sum(e.scan_time_ms for e in ev) / len(ev) if ev else None
