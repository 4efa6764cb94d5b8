"""Process-local metrics registry: counters, gauges, histograms
(DESIGN.md §14).

One module-global :class:`Registry` is the single place the FL data and
control planes publish numbers into — ``RoundLog``/``StreamRoundLog``
(round wire legs, loss, satisfaction), ``ota.AggregateInfo`` (uplink
bytes, truncation, misalignment, noise), the client's local update
(local steps), the retrieval engine (query counts, arena uploads and
their bytes), the serving engine (token throughput), and a
``jax.monitoring`` hook counting jit traces/compiles — instead of each
subsystem growing ad-hoc report fields. Reads are ``snapshot()`` (a
plain nested dict, the JSONL/export payload) or ``get(name, **labels)``
for one value.

Metric taxonomy:

- **counter** — monotonically accumulating float (``inc``): byte
  ledgers, row/query/event counts, jit retraces.
- **gauge** — last-write-wins float (``set_gauge``): per-round rates
  (truncation rate, misalignment), losses.
- **histogram** — running {count, total, min, max} summary
  (``observe``): staleness discounts.
  (No buckets: the consumers are regression diffs and dashboards fed
  from JSONL, not quantile queries.)

Labels: optional keyword labels qualify a series
(``inc("ota.rows", 3, kind="int4")`` keys the series
``ota.rows{kind=int4}``). The un-labelled and labelled series are
distinct.

Publishing is host-arithmetic only (dict update under a lock) and
always on — the values are already host numbers where the calls sit;
no call site reads the device to publish a metric, traced or not.

The jax hook (``install_jax_hooks``, installed on first import of
``repro.obs``) listens on ``jax.monitoring`` duration events:
``jax.retraces`` counts jaxpr traces (one per jit cache *miss* — a
cached dispatch emits nothing, so a flat retrace counter across rounds
IS the cache-hit signal), ``jax.compiles``/``jax.compile_seconds``
count backend (XLA) compilations and their cost. The retrace-storm
regression guard in ``tests/test_obs.py`` reads ``jax.retraces``.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

_LabelKey = Tuple[Tuple[str, str], ...]


def _series(name: str, labels: Dict[str, Any]) -> str:
    """Canonical series name: ``name`` or ``name{k=v,...}`` (sorted)."""
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Registry:
    """Thread-safe process-local metrics store."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Dict[str, float]] = {}

    # -- writes ---------------------------------------------------------
    def inc(self, name: str, value: float = 1.0, **labels: Any) -> None:
        key = _series(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels: Any) -> None:
        key = _series(name, labels)
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, name: str, value: float, **labels: Any) -> None:
        key = _series(name, labels)
        v = float(value)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                self._hists[key] = {"count": 1, "total": v, "min": v, "max": v}
            else:
                h["count"] += 1
                h["total"] += v
                h["min"] = min(h["min"], v)
                h["max"] = max(h["max"], v)

    # -- reads ----------------------------------------------------------
    def get(self, name: str, default: Optional[float] = None, **labels: Any):
        """One series' value: counter/gauge float, histogram dict."""
        key = _series(name, labels)
        with self._lock:
            if key in self._counters:
                return self._counters[key]
            if key in self._gauges:
                return self._gauges[key]
            if key in self._hists:
                return dict(self._hists[key])
        return default

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Plain-dict view: {"counters": {...}, "gauges": {...},
        "histograms": {series: {count,total,min,max}}}."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: dict(v) for k, v in self._hists.items()},
            }

    def reset(self) -> None:
        """Zero every series (fresh bench/test scope)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


REGISTRY = Registry()

# module-level aliases: the instrumentation call-site idiom
# (``metrics.inc("fl.uplink_bytes", n)``)
inc = REGISTRY.inc
set_gauge = REGISTRY.set_gauge
observe = REGISTRY.observe
get = REGISTRY.get
snapshot = REGISTRY.snapshot
reset = REGISTRY.reset


# ---------------------------------------------------------------------------
# jax lower/compile hook: jit retrace + XLA compile accounting
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_hooks_installed = False
_hooks_lock = threading.Lock()


def _on_duration_event(event: str, duration_secs: float, **kw: Any) -> None:
    if event == _TRACE_EVENT:
        REGISTRY.inc("jax.retraces")
    elif event == _COMPILE_EVENT:
        REGISTRY.inc("jax.compiles")
        REGISTRY.inc("jax.compile_seconds", duration_secs)


def install_jax_hooks() -> None:
    """Register the ``jax.monitoring`` listener (idempotent).

    ``jax.monitoring`` has no per-listener unregister, so this installs
    exactly once per process; the listener writes into the module
    ``REGISTRY``, which ``reset()`` re-zeroes without re-registering.
    Importing ``repro.obs`` installs the hook — the listener itself
    fires only on trace/compile events, never on cached dispatches, so
    steady-state rounds pay nothing.
    """
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
        _hooks_installed = True
