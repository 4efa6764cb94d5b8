"""Host-side span tracer with a Chrome/Perfetto ``trace_event`` exporter
(DESIGN.md §14).

One process-global :class:`Tracer` records *spans* — named, nested
host-time intervals — around the FL round pipeline (``fl/server.py``:
``plan -> channel_sample -> client_train -> uplink_encode -> fold ->
finalize -> optimizer -> broadcast_encode -> feedback``), each client's
local update inside ``client_train`` (``fl/client.py``: ``client.update
> client.setup, client.batch, client.dispatch, client.delta,
uplink_encode``; then one ``client.loss_read`` for the whole cohort),
the retrieval query path (``retrieval/engine.py``: ``retrieval.query >
retrieval.upload``), and the serving engine's prefill/decode steps
(``serve/engine.py``).

Spans measure *host* wall-clock. jax dispatch is asynchronous, so a span
around a jitted call times its dispatch, not its device execution; a
span ends on the device's clock only where its stage ends in a host
sync: ``client.loss_read`` (the round's one blocking read of the
cohort's losses), ``plan`` (the top-k results' ``np.asarray``), and
``retrieval.upload``, which waits for its transfer while tracing is on
(the query blocks on a result of that upload anyway). Device time is
the profiler's to give: while enabled, every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so under ``jax.profiler``
tracing the stages sit in the ``.xplane.pb`` host plane beside the
device's programs, on the same clock.

Design constraints:

- **Near-zero overhead when disabled** (the default): ``span()`` is one
  global attribute check returning a shared no-op context-manager
  singleton — no allocation, no clock read, no profiler annotation. The
  disabled path leaves every instrumented computation byte-identical to
  the uninstrumented program (spans only observe; ``tests/test_obs.py``
  pins this).
- **Monotonic clocks**: timestamps are ``time.perf_counter_ns`` relative
  to the tracer's epoch, exported in microseconds (the ``trace_event``
  unit).
- **Nested spans**: a per-thread depth counter tracks nesting; events
  are appended at span *exit*, so children complete before parents and
  the Perfetto ``ph: "X"`` (complete-event) nesting is reconstructed
  from ts/dur containment on one track per thread.

Enable either through the context managers (``with trace.enabled(): ...``
— the bench/test idiom, restores the previous state) or imperatively
(``get_tracer().enable()``). ``export_perfetto`` emits the Chrome
``trace_event`` JSON (``{"traceEvents": [{"ph": "X", "ts", "dur",
"name", ...}]}``) that chrome://tracing and ui.perfetto.dev load
directly.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Set

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One completed span: [ts_us, ts_us + dur_us] on thread ``tid``."""

    name: str
    ts_us: float  # start, µs since the tracer epoch (monotonic)
    dur_us: float
    depth: int  # nesting depth at entry (0 = top level on its thread)
    tid: int
    args: Optional[Dict[str, Any]] = None


class _NullSpan:
    """Shared no-op span: what ``span()`` returns while disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """A live span (context manager); records itself on exit and holds a
    profiler annotation of its name open meanwhile."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_depth", "_note")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        local = self._tracer._local
        self._depth = getattr(local, "depth", 0)
        local.depth = self._depth + 1
        self._note = TraceAnnotation(self._name)
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = time.perf_counter_ns()
        self._note.__exit__(None, None, None)
        tracer = self._tracer
        tracer._local.depth = self._depth
        if tracer.enabled:  # disabled mid-span: drop, don't record
            tracer._events.append(
                SpanEvent(
                    name=self._name,
                    ts_us=(self._t0 - tracer._epoch_ns) / 1e3,
                    dur_us=(t1 - self._t0) / 1e3,
                    depth=self._depth,
                    tid=threading.get_ident(),
                    args=self._args or None,
                )
            )
        return False


class Tracer:
    """Process-local span recorder. Disabled (and empty) by default."""

    def __init__(self) -> None:
        self.enabled = False
        self._events: List[SpanEvent] = []
        self._epoch_ns = time.perf_counter_ns()
        self._local = threading.local()

    # -- lifecycle ------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def reset(self) -> "Tracer":
        """Drop recorded events and restart the epoch clock."""
        self._events = []
        self._epoch_ns = time.perf_counter_ns()
        return self

    # -- recording ------------------------------------------------------
    def span(self, name: str, **args: Any) -> Any:
        """Context manager timing one named span (no-op when disabled)."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args if args else None)

    # -- inspection -----------------------------------------------------
    @property
    def events(self) -> List[SpanEvent]:
        return list(self._events)

    def span_names(self) -> Set[str]:
        return {e.name for e in self._events}

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name {count, total_us, max_us} rollup."""
        out: Dict[str, Dict[str, float]] = {}
        for e in self._events:
            s = out.setdefault(e.name, {"count": 0, "total_us": 0.0, "max_us": 0.0})
            s["count"] += 1
            s["total_us"] += e.dur_us
            s["max_us"] = max(s["max_us"], e.dur_us)
        return out

    # -- export ---------------------------------------------------------
    def export_perfetto(self, path: Optional[str] = None) -> str:
        """Chrome/Perfetto ``trace_event`` JSON for the recorded spans.

        Complete events (``ph: "X"``) carry ``ts``/``dur`` in µs; one
        ``tid`` per recording thread reconstructs nesting by interval
        containment. Returns the JSON string; with ``path`` also writes
        it there (the CI telemetry artifact).
        """
        pid = os.getpid()
        events = [
            {
                "name": e.name,
                "ph": "X",
                "ts": e.ts_us,
                "dur": e.dur_us,
                "pid": pid,
                "tid": e.tid,
                "cat": "repro",
                **({"args": e.args} if e.args else {}),
            }
            for e in sorted(self._events, key=lambda e: e.ts_us)
        ]
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        text = json.dumps(doc, indent=1, sort_keys=True)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
                f.write("\n")
        return text


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer instance."""
    return _TRACER


def is_enabled() -> bool:
    return _TRACER.enabled


def span(name: str, **args: Any) -> Any:
    """Module-level span helper — THE instrumentation call site idiom.

    ``with span("fold"): ...`` costs one attribute check and a shared
    singleton return when tracing is off.
    """
    t = _TRACER
    if not t.enabled:
        return NULL_SPAN
    return _Span(t, name, args if args else None)


@contextlib.contextmanager
def enabled(*, fresh: bool = True) -> Iterator[Tracer]:
    """Enable tracing for the block; restore the prior state after.

    ``fresh`` (default) resets recorded events and the epoch first, so
    the block's trace stands alone — the bench/test idiom.
    """
    t = _TRACER
    prev = t.enabled
    if fresh:
        t.reset()
    t.enable()
    try:
        yield t
    finally:
        t.enabled = prev


@contextlib.contextmanager
def disabled() -> Iterator[Tracer]:
    """Force tracing off for the block (overhead-comparison baseline)."""
    t = _TRACER
    prev = t.enabled
    t.disable()
    try:
        yield t
    finally:
        t.enabled = prev
