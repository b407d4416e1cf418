"""Planner time: mean of the audit events' ``plan_time_ms`` over the
window's queries (``store/memory.py`` ``_matching_rows`` times strategy
choice and plan-cache lookup on the host clock)."""


def read(run):
    ev = run.audit
    return sum(e.plan_time_ms for e in ev) / len(ev) if ev else None
