"""Unified telemetry layer (DESIGN.md §14): span tracing, a process-
local metrics registry, and JSONL / Perfetto exporters shared by the FL
data plane (``core/ota.py``, ``core/wire.py``), the control plane
(``fl/server.py``, ``retrieval/engine.py``), and the serving engine.

The instrumentation idiom::

    from repro import obs

    with obs.span("fold", rows=k):
        ...
    obs.metrics.inc("ota.uplink_bytes", nbytes)

Tracing is off by default and ``obs.span`` is a near-no-op then;
``with obs.enabled(): ...`` turns one block's telemetry on,
``obs.disabled()`` forces it off (the overhead baseline the
``benchmarks/bench_obs.py --smoke`` bar compares against). Metrics are
always-on host arithmetic. Importing this package installs the jax
trace/compile hook feeding the ``jax.retraces`` counter.
"""

from repro.obs import export, metrics, trace
from repro.obs.trace import (
    NULL_SPAN,
    SpanEvent,
    Tracer,
    disabled,
    enabled,
    get_tracer,
    is_enabled,
    span,
)

metrics.install_jax_hooks()

__all__ = [
    "NULL_SPAN",
    "SpanEvent",
    "Tracer",
    "disabled",
    "enabled",
    "export",
    "get_tracer",
    "is_enabled",
    "metrics",
    "span",
    "trace",
]
