"""Runtime telemetry: compile churn, device memory, transfer bytes.

The serving layer keeps several jit/plan shape-class caches (the query
batcher's fused-scan plans, the standing-filter sets' kernel shapes,
the join prewarm) whose MISSES predict XLA retraces — the single
biggest latency cliff on an accelerator tier. This collector is the
one place those caches report to: per-domain, per-shape-class
compile-vs-hit counts, fused-dispatch wall timers, host<->device
transfer bytes, sampled device memory (current, high-water mark,
live buffer count/bytes), and the backend compiles that actually ran
(one ``jax.monitoring`` listener, which also notes each compile on the
current trace span, so a trace shows which step of which request
recompiled).

Everything lands twice: in the labeled metrics registry
(``runtime.compile{domain,class,outcome}`` counters,
``runtime.compile.backend`` counter, ``runtime.dispatch{domain,class}``
timers, ``runtime.device.bytes`` gauges,
``runtime.h2d.bytes``/``runtime.d2h.bytes`` counters) for
scraping, and in an internal table the ``GET /rest/runtime`` snapshot
serves directly.

Device memory sampling NEVER force-initializes jax: it only looks if
``jax`` is already in ``sys.modules``, prefers ``device.memory_stats()``
(absent or None on CPU backends), and falls back to summing
``jax.live_arrays()`` byte sizes — so a CPU-only tier degrades to
host-buffer accounting instead of erroring.

Kill switch: ``geomesa.runtime.enabled`` (default true) — re-read per
call, so the bench's on/off overhead phases and a live operator both
work without restarts.
"""

from __future__ import annotations

import sys
import threading
import time

from ..metrics import metrics, sanitize_key
from ..utils.properties import SystemProperty

__all__ = ["RuntimeCollector", "runtime", "RUNTIME_ENABLED"]

RUNTIME_ENABLED = SystemProperty("geomesa.runtime.enabled", "true")

# fires for every XLA backend compile, a persistent-cache load included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _cls(shape) -> str:
    """A shape class (tuple of type/version/pow2 caps, or anything
    else a cache keys on) as a bounded metric-safe label value."""
    if isinstance(shape, (tuple, list)):
        return sanitize_key("/".join(str(x) for x in shape))
    return sanitize_key(str(shape))


class RuntimeCollector:
    def __init__(self, registry=metrics):
        self._registry = registry
        self._lock = threading.Lock()
        # (domain, class) -> [hits, misses]
        self._compiles: dict[tuple[str, str], list] = {}
        # (domain, class) -> [count, total_s, max_s]
        self._dispatches: dict[tuple[str, str], list] = {}
        self._h2d_bytes = 0
        self._d2h_bytes = 0
        self._mem: dict[str, dict] = {}     # device label -> stats
        self._live_buffers = 0
        self._live_bytes = 0
        self._live_bytes_hwm = 0
        self._mem_samples = 0
        self._mem_sampled_at: float | None = None
        self._backend_compiles = [0, 0.0]   # count, seconds
        self._watching = False

    @staticmethod
    def enabled() -> bool:
        return str(RUNTIME_ENABLED.get()).lower() in ("true", "1", "yes")

    # -- cache + dispatch hooks --------------------------------------------

    def note_plan_probe(self, domain: str, shape, hit: bool):
        """One shape-class cache probe: a miss is a predicted compile."""
        if not self.enabled():
            return
        cls = _cls(shape)
        with self._lock:
            row = self._compiles.setdefault((domain, cls), [0, 0])
            row[0 if hit else 1] += 1
        self._registry.counter(
            "runtime.compile",
            labels={"domain": domain, "class": cls,
                    "outcome": "hit" if hit else "miss"})

    def note_dispatch(self, domain: str, shape, seconds: float,
                      h2d_bytes: int = 0, d2h_bytes: int = 0):
        """One device dispatch: wall seconds + transfer bytes."""
        if not self.enabled():
            return
        cls = _cls(shape)
        with self._lock:
            row = self._dispatches.setdefault((domain, cls),
                                              [0, 0.0, 0.0])
            row[0] += 1
            row[1] += seconds
            row[2] = max(row[2], seconds)
            self._h2d_bytes += int(h2d_bytes)
            self._d2h_bytes += int(d2h_bytes)
        self._registry.observe("runtime.dispatch", seconds,
                               labels={"domain": domain, "class": cls})
        if h2d_bytes:
            self._registry.counter("runtime.h2d.bytes", int(h2d_bytes))
        if d2h_bytes:
            self._registry.counter("runtime.d2h.bytes", int(d2h_bytes))

    # -- backend compiles --------------------------------------------------

    def watch_compiles(self):
        """Register the backend-compile listener once; a no-op until jax
        is loaded (called as each kernel module sets up its cache)."""
        jax = sys.modules.get("jax")
        if jax is None:
            return
        with self._lock:
            if self._watching:
                return
            self._watching = True
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)

    def _on_compile(self, event: str, seconds: float, **_):
        if event != COMPILE_EVENT or not self.enabled():
            return
        with self._lock:
            self._backend_compiles[0] += 1
            self._backend_compiles[1] += seconds
        self._registry.counter("runtime.compile.backend")
        from .trace import annotate
        annotate("compile", seconds=round(seconds, 6))

    # -- device memory -----------------------------------------------------

    def sample_device_memory(self):
        """Sample device memory if jax is already loaded (a telemetry
        thread must never be the thing that initializes a backend)."""
        if not self.enabled():
            return
        jax = sys.modules.get("jax")
        if jax is None:
            return
        per_dev: dict[str, dict] = {}
        try:
            devices = jax.devices()
        except Exception:  # noqa: BLE001 — backend may be mid-init
            return
        for d in devices:
            label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            stats = None
            try:
                fn = getattr(d, "memory_stats", None)
                stats = fn() if callable(fn) else None
            except Exception:  # noqa: BLE001 — CPU backends raise/None
                stats = None
            if not stats:
                continue
            in_use = int(stats.get("bytes_in_use", 0) or 0)
            peak = int(stats.get("peak_bytes_in_use", in_use) or in_use)
            per_dev[label] = {"bytes_in_use": in_use,
                              "peak_bytes_in_use": peak}
        live_n = live_b = 0
        try:
            for arr in jax.live_arrays():
                live_n += 1
                live_b += int(getattr(arr, "nbytes", 0) or 0)
        except Exception:  # noqa: BLE001
            pass
        with self._lock:
            for label, st in per_dev.items():
                prev = self._mem.get(label, {})
                st["hwm_bytes"] = max(st["peak_bytes_in_use"],
                                      int(prev.get("hwm_bytes", 0)))
                self._mem[label] = st
            self._live_buffers = live_n
            self._live_bytes = live_b
            self._live_bytes_hwm = max(self._live_bytes_hwm, live_b)
            self._mem_samples += 1
            self._mem_sampled_at = time.time()
        reg = self._registry
        for label, st in per_dev.items():
            reg.gauge("runtime.device.bytes", st["bytes_in_use"],
                      labels={"device": label})
            reg.gauge("runtime.device.bytes.peak", st["peak_bytes_in_use"],
                      labels={"device": label})
        reg.gauge("runtime.device.live_buffers", live_n)
        reg.gauge("runtime.device.live_bytes", live_b)

    # -- surfaces ----------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``GET /rest/runtime`` document (JSON-safe)."""
        with self._lock:
            compiles: dict[str, dict] = {}
            for (domain, cls), (hits, misses) in self._compiles.items():
                compiles.setdefault(domain, {})[cls] = {
                    "hits": hits, "misses": misses}
            dispatches: dict[str, dict] = {}
            for (domain, cls), (n, tot, mx) in self._dispatches.items():
                dispatches.setdefault(domain, {})[cls] = {
                    "count": n,
                    "total_ms": round(tot * 1e3, 3),
                    "mean_ms": round(tot / n * 1e3, 3) if n else 0.0,
                    "max_ms": round(mx * 1e3, 3)}
            return {
                "enabled": self.enabled(),
                "compile": compiles,
                "backend_compile": {
                    "count": self._backend_compiles[0],
                    "total_ms": round(self._backend_compiles[1] * 1e3, 3)},
                "dispatch": dispatches,
                "transfer": {"h2d_bytes": self._h2d_bytes,
                             "d2h_bytes": self._d2h_bytes},
                "device_memory": {
                    "devices": {k: dict(v) for k, v in self._mem.items()},
                    "live_buffers": self._live_buffers,
                    "live_bytes": self._live_bytes,
                    "live_bytes_hwm": self._live_bytes_hwm,
                    "samples": self._mem_samples,
                    "sampled_at": self._mem_sampled_at,
                },
            }

    def clear(self):
        with self._lock:
            self._compiles.clear()
            self._dispatches.clear()
            self._h2d_bytes = self._d2h_bytes = 0
            self._mem.clear()
            self._live_buffers = self._live_bytes = 0
            self._live_bytes_hwm = 0
            self._mem_samples = 0
            self._mem_sampled_at = None
            self._backend_compiles = [0, 0.0]


runtime = RuntimeCollector()
