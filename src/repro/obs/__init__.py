"""Observability substrate for the serving path (DESIGN.md §9).

Three instruments behind one bundle:

  * `SpanTracer`   — host-side span timing, ring-buffered, Chrome-trace
                     export, and a `jax.profiler.TraceAnnotation` for
                     every span while a profiler session is active
                     (obs/trace.py);
  * `MetricsRegistry` — counters / gauges / fixed-bucket histograms with
                     Prometheus-text and JSON exposition (obs/metrics.py);
  * `EventLog`     — structured JSONL event stream (per-request route
                     decisions) (obs/events.py).

Gating contract: METRICS ARE ALWAYS ON — they back typed engine
statistics (`ServingEngine.stats`) and cost nanoseconds per batch.
The span RING and EVENTS are gated by `Observability.enabled` (default
OFF); profiler annotations are not: any active profiler session sees
every span, enabled or not. Outside a session a disabled span costs
one attribute check and one profiler-state query, which is how the <5%
hot-path overhead budget is enforced (ci.sh --assert-obs measures the
ENABLED path against that budget too).

Components take an optional `obs=` handle and fall back to the module
default (`DEFAULT`), so a process normally has one telemetry scope;
tests and benchmarks build private `Observability()` instances for
isolation.
"""
from __future__ import annotations

from typing import Optional

from repro.obs.events import EventLog
from repro.obs.metrics import (DEFAULT_LATENCY_BOUNDS_US, Counter, Gauge,
                               Histogram, MetricsRegistry,
                               geometric_bounds)
from repro.obs.trace import NULL_SPAN, SpanTracer, annotation, named_scope

__all__ = ["Observability", "DEFAULT", "get_obs", "enable", "disable",
           "reset_default", "SpanTracer", "MetricsRegistry", "EventLog",
           "Counter", "Gauge", "Histogram", "geometric_bounds",
           "DEFAULT_LATENCY_BOUNDS_US", "named_scope", "NULL_SPAN"]


class Observability:
    """One telemetry scope: tracer + registry + event log + the enable
    switch for the gated instruments."""

    def __init__(self, enabled: bool = False, trace_capacity: int = 8192,
                 event_capacity: int = 1 << 16,
                 event_path: Optional[str] = None):
        self.tracer = SpanTracer(capacity=trace_capacity)
        self.registry = MetricsRegistry()
        self.events = EventLog(capacity=event_capacity, path=event_path)
        self.tracer.enabled = enabled
        self.enabled = enabled

    # -- switches ------------------------------------------------------------
    def enable(self) -> "Observability":
        self.tracer.enabled = True
        self.enabled = True
        return self

    def disable(self) -> "Observability":
        self.tracer.enabled = False
        self.enabled = False
        return self

    # -- hot-path helpers ----------------------------------------------------
    def span(self, name: str, ring: bool = True):
        """Timed span: into the ring when enabled and `ring`, into any
        active profiler session always; a shared no-op otherwise.
        `ring=False` marks per-iteration regions (profiler only)."""
        if self.enabled:
            return self.tracer.span(name, ring)
        return annotation(name)

    def emit(self, record) -> bool:
        """Gated event emission; returns whether the record was taken."""
        if not self.enabled:
            return False
        self.events.emit(record)
        return True

    def reset(self):
        """Fresh instruments, switch state preserved (tests/benches)."""
        self.tracer.reset()
        self.registry.reset()
        self.events.clear()


#: process-default scope: what instrumented components use unless handed
#: an explicit `obs=`; disabled (metrics-only) out of the box.
DEFAULT = Observability(enabled=False)


def get_obs(obs: Optional[Observability] = None) -> Observability:
    return obs if obs is not None else DEFAULT


def reset_default(enabled: bool = False, **kw) -> Observability:
    """Tear down and re-create the process-default scope.

    Test fixtures call this between tests so metric/event state from a
    component built without an explicit `obs=` cannot bleed across
    tests (`tests/conftest.py`). Handles cached from the OLD bundle
    keep working against the old instruments — isolation comes from
    `get_obs()` resolving to the fresh bundle at the next lookup, not
    from invalidating old references."""
    global DEFAULT
    DEFAULT = Observability(enabled=enabled, **kw)
    return DEFAULT


def enable() -> Observability:
    """Switch the process-default scope on (span ring + events)."""
    return DEFAULT.enable()


def disable() -> Observability:
    return DEFAULT.disable()
