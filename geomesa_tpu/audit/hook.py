"""The unified query-audit hook: every query surface (memory / mesh /
remote / replicated / cluster) records its ``QueryEvent`` through
``audit_query`` so the audit plane is complete instead of
store-dependent.

Three pieces make that work:

- **global fallback logger** — a store constructed without an explicit
  ``AuditLogger`` records into the process-wide ring (JSONL path from
  ``geomesa.audit.path``), so ``/rest/audit`` on a server fronting a
  cluster coordinator or remote client still answers;
- **delegation suppression** — a fronting tier (cluster coordinator,
  replica router) records ONE event for the whole query and runs its
  delegate legs under ``delegated_scope()``; the inner stores' hooks
  see the contextvar and skip, so one logical query never
  double-audits. The scope is a contextvar, so it survives the
  coordinator's ``contextvars.copy_context()``-wrapped scatter
  threads;
- **context enrichment** — the hook stamps each event with the current
  trace id, the authenticated principal (web tier sets
  ``principal_scope``), and the cache/hedge flags instrumentation set
  on the trace (obs.set_flag), without any surface having to plumb
  those arguments through.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading

from ..utils.properties import SystemProperty
from .events import AuditLogger

__all__ = ["AUDIT_PATH", "global_audit", "audit_query",
           "delegated_scope", "principal_scope", "current_principal"]

AUDIT_PATH = SystemProperty("geomesa.audit.path", None)

_global: AuditLogger | None = None
_global_lock = threading.Lock()

_suppress: contextvars.ContextVar = contextvars.ContextVar(
    "geomesa_audit_suppress", default=False)
_principal: contextvars.ContextVar = contextvars.ContextVar(
    "geomesa_audit_principal", default=None)


def global_audit() -> AuditLogger:
    """The process-wide fallback logger (lazy; picks up
    ``geomesa.audit.path`` at first use)."""
    global _global
    with _global_lock:
        if _global is None:
            _global = AuditLogger(path=AUDIT_PATH.get())
        return _global


def _reset_global():
    """Test hook: drop the cached global logger so a changed
    ``geomesa.audit.path`` takes effect."""
    global _global
    with _global_lock:
        _global = None


@contextlib.contextmanager
def delegated_scope():
    """Mark the dynamic extent of a fronting tier's delegate calls:
    inner surfaces skip auditing (the tier records the one event)."""
    token = _suppress.set(True)
    try:
        yield
    finally:
        _suppress.reset(token)


@contextlib.contextmanager
def principal_scope(principal: str | None):
    token = _principal.set(principal)
    try:
        yield
    finally:
        _principal.reset(token)


def current_principal() -> str | None:
    return _principal.get()


def audit_query(audit: AuditLogger | None, surface: str,
                type_name: str, filter_str: str, hints: dict | None,
                plan_ms: float, scan_ms: float, hits: int, *,
                index: str | None = None,
                rows_scanned: int | None = None,
                batched: bool = False,
                user: str | None = None) -> bool:
    """Record one query through the shared hook. ``audit`` is the
    surface's own logger (None -> global fallback). Returns False when
    suppressed by an enclosing ``delegated_scope``."""
    if _suppress.get():
        return False
    from ..obs import current_trace_id, get_flag
    from ..tenants import active_tenant
    logger = audit if audit is not None else global_audit()
    logger.record(
        type_name, filter_str, hints or {},
        round(float(plan_ms), 3), round(float(scan_ms), 3), int(hits),
        user=user or current_principal() or "unknown",
        trace_id=current_trace_id(), surface=surface, index=index,
        rows_scanned=rows_scanned,
        cache_hit=bool(get_flag("cache_hit", False)),
        batched=batched,
        hedged=bool(get_flag("hedged", False)),
        tenant=active_tenant())
    return True
