"""Campaign telemetry: one injectable observability object for the stack.

``Telemetry`` bundles the two observability pieces every layer shares:

* a ``MetricsRegistry`` (labeled Counter/Gauge/Histogram series,
  ``snapshot()`` -> JSON) — see ``repro.telemetry.metrics``;
* a ``SpanTracer`` (nested timing spans, bounded ring buffer, Chrome
  ``trace_event`` export for Perfetto) — see ``repro.telemetry.trace``;

plus the injected monotonic ``clock`` both read, which is also the clock
the instrumented call sites (``Campaign.run`` tile walls, fabric busy
windows, serving latencies) use instead of raw ``time.perf_counter()`` —
inject ``repro.dse_campaign.fabric.FakeClock`` and every telemetry
timestamp in the system becomes deterministic.

A tracing ``Telemetry`` mirrors each span into the JAX profiler as a
``repro.<name>`` annotation, and records JAX's lowering and compilation
time spans as ``lower`` / ``compile`` roots (``fun_name`` attr): the first
tracing ``Telemetry`` built registers one ``jax.monitoring`` listener,
which records each compile into the tracing ``Telemetry`` (or several)
with a span open on the compiling thread.

``NullTelemetry`` is the default everywhere and the disabled-path
contract: **metrics still count** (they are O(1) scalar writes, and
back-compat surfaces like ``TileEvaluator.fused_launches`` read them) but
**tracing is free** — ``span()`` returns a process-wide no-op singleton,
nothing is buffered, no JAX listener is registered, and the instrumented
hot paths add <2% throughput overhead (gated in
``benchmarks/dse_campaign.py``).

The one rule that keeps observability safe: no instrumented value may feed
computation.  Metrics and spans are readings; the frontier identity gates
(streamed == one-shot, distributed == single-process, instrumented ==
uninstrumented) stay bitwise with telemetry on, off, or null.

Usage::

    from repro.telemetry import Telemetry

    tel = Telemetry()
    campaign = Campaign(workloads, config, telemetry=tel)
    campaign.run()
    tel.snapshot()                        # metrics -> JSON dict
    tel.export_trace("trace.json")        # open in Perfetto

See ``docs/observability.md`` for the span/metric glossary.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Callable, Dict, Optional

from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, metric_value)
from repro.telemetry.trace import (NULL_SPAN, NULL_TRACER, NullTracer,
                                   SpanRecord, SpanTracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullTelemetry",
    "SpanRecord", "SpanTracer", "Telemetry", "coerce_telemetry",
    "metric_value",
]


# JAX's time-span events recorded as spans, by the span name they get
JAX_SPANS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}

_live: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()
_listener_lock = threading.Lock()
_listening = False


def _on_jax_time_span(event: str, start_time: float, end_time: float,
                      **kwargs) -> None:
    """``jax.monitoring`` time-span listener: one of JAX's lowerings or
    compilations as a root span of each live tracing ``Telemetry`` with a
    span open on the compiling thread, so the request that paid for the
    compile holds it.  JAX calls this as the compile ends: the span ends
    now on the tracer's clock and lasts JAX's measured duration."""
    name = JAX_SPANS.get(event)
    if name is None:
        return
    with _listener_lock:
        tels = list(_live)
    for tel in tels:
        tr = tel.tracer
        if tr.open_depth():
            t1 = tr.clock()
            tr.record(name, t1 - (end_time - start_time), t1,
                      fun_name=kwargs.get("fun_name"))


def _watch_jax(tel: "Telemetry") -> None:
    """Add ``tel`` to the listener's audience; register the listener once."""
    global _listening
    with _listener_lock:
        _live.add(tel)
        if not _listening:
            import jax.monitoring
            jax.monitoring.register_event_time_span_listener(
                _on_jax_time_span)
            _listening = True


class Telemetry:
    """The injectable observability bundle: metrics + tracer + clock."""

    tracing = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 wall_clock: Callable[[], float] = time.time,
                 trace_capacity: int = 65536):
        self.clock = clock
        self.metrics = MetricsRegistry(clock=clock)
        self.tracer = SpanTracer(clock=clock, wall_clock=wall_clock,
                                 capacity=trace_capacity)
        _watch_jax(self)

    # -- tracing -------------------------------------------------------------

    def span(self, name: str, **attrs):
        """Context manager timing one named span (see ``SpanTracer.span``)."""
        return self.tracer.span(name, **attrs)

    def chrome_trace(self, process_name: str = "repro-campaign") -> Dict:
        return self.tracer.chrome_trace(process_name)

    def export_trace(self, path: str,
                     process_name: str = "repro-campaign") -> str:
        return self.tracer.export(path, process_name)

    # -- metrics -------------------------------------------------------------

    def counter(self, name: str, **labels) -> Counter:
        return self.metrics.counter(name, **labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self.metrics.gauge(name, **labels)

    def histogram(self, name: str, max_samples: int = 8192,
                  **labels) -> Histogram:
        return self.metrics.histogram(name, max_samples=max_samples, **labels)

    def snapshot(self) -> Dict:
        return self.metrics.snapshot()


class NullTelemetry(Telemetry):
    """The default: real (cheap) metrics, no tracing.

    Every component that is not handed a ``Telemetry`` constructs its OWN
    ``NullTelemetry`` — registries are per-owner, so two engines' counters
    never alias (``engine.fused_launches`` stays an engine-local reading).
    ``span()`` short-circuits to the shared no-op singleton.
    """

    tracing = False

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.metrics = MetricsRegistry(clock=clock)
        self.tracer = NULL_TRACER

    def span(self, name: str = "", **attrs):
        return NULL_SPAN


def coerce_telemetry(telemetry: Optional[Telemetry]) -> Telemetry:
    """``None`` -> a fresh per-owner ``NullTelemetry`` (the default path)."""
    return telemetry if telemetry is not None else NullTelemetry()
