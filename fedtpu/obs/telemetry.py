"""The Telemetry facade: one object gating tracer + registry on a mode.

``FedConfig.telemetry`` selects how much the framework measures itself:

- ``"off"``   — nothing. ``span()`` returns a shared no-op, metric getters
  return shared no-op instruments. The per-round wire/phase accounting on
  round records stays (it is part of the round() API, and its thread-safe
  counters are a correctness fix, not telemetry).
- ``"basic"`` (default) — the metrics registry is live (counters, gauges,
  histograms; exportable as Prometheus text). ``span()`` returns the bare
  ``jax.profiler`` annotation: nothing is recorded in memory, and the span
  reaches a profiler session (``--profile-rounds``) whenever one is
  listening, at the cost of a flag test when none is. Measured overhead:
  well under 1% of round wall time (``bench.py --telemetry-microbench``,
  artifacts/TELEMETRY_MICROBENCH.json).
- ``"trace"`` — basic plus the span tracer (in-memory spans, Chrome-trace
  export, the same annotation). Spans cost ~a microsecond each; fine for
  diagnosis runs, off the default path.

Each engine/server owns ONE Telemetry instance (its registry is that
component's metric namespace); the FT helpers receive the owning
component's registry and fall back to the process-global one when
constructed standalone.
"""

from __future__ import annotations

from typing import Optional

from fedtpu.obs.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from fedtpu.obs.trace import NULL_SPAN, SpanTracer, profiler_span

TELEMETRY_MODES = ("off", "basic", "trace")


class _NullCounter:
    __slots__ = ()
    kind = "counter"
    value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        return None


class _NullGauge:
    __slots__ = ()
    kind = "gauge"
    value = 0.0

    def set(self, value: float) -> None:
        return None

    def inc(self, amount: float = 1.0) -> None:
        return None


class _NullHistogram:
    __slots__ = ()
    kind = "histogram"
    count = 0
    sum = 0.0

    def observe(self, value: float) -> None:
        return None


_NULL_COUNTER = _NullCounter()
_NULL_GAUGE = _NullGauge()
_NULL_HISTOGRAM = _NullHistogram()


def validate_telemetry_mode(mode: str) -> str:
    if mode not in TELEMETRY_MODES:
        raise ValueError(
            f"unknown telemetry mode {mode!r}; have off | basic | trace"
        )
    return mode


class Telemetry:
    """Mode-gated bundle of one :class:`MetricsRegistry` and (in ``trace``
    mode) one :class:`SpanTracer`."""

    def __init__(self, mode: str = "basic",
                 registry: Optional[MetricsRegistry] = None,
                 role: Optional[str] = None):
        self.mode = validate_telemetry_mode(mode)
        # Process/component identity for multi-process trace stitching and
        # the flight recorder's dump filenames: "primary", "backup",
        # "client:<addr>", "engine", ... Settable post-construction (the
        # components that own a Telemetry stamp it).
        self.role = role
        self.enabled = mode != "off"
        self.tracing = mode == "trace"
        # A registry exists even in off mode (so handing
        # ``telemetry.registry`` to the FT modules is unconditional); the
        # off gate lives in the instrument getters below.
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = SpanTracer() if self.tracing else None

    # ------------------------------------------------------------- spans
    def span(self, name: str, parent=None, **args):
        if self.tracer is not None:
            return self.tracer.span(name, parent=parent, **args)
        return profiler_span(name, args) if self.enabled else NULL_SPAN

    def trace_events(self):
        return self.tracer.events() if self.tracer is not None else []

    def export_trace(self, path: str) -> None:
        """Write the collected spans as a Perfetto-loadable Chrome trace.
        No-op below ``trace`` mode (nothing was collected). The dump's
        ``metadata`` block (trace id, role, pid, wall_start) is what
        ``tools/trace_merge.py`` keys on when stitching per-process files
        into one federation timeline."""
        if self.tracer is None:
            return
        import os

        from fedtpu.obs.trace import write_chrome_trace

        write_chrome_trace(
            self.tracer.events(), path,
            metadata={
                "trace_id": self.tracer.trace_id,
                "role": self.role or f"pid{os.getpid()}",
                "pid": os.getpid(),
                "wall_start": self.tracer.wall_start,
            },
        )

    # ----------------------------------------------------------- metrics
    def counter(self, name: str, help: str = "", labels=None) -> Counter:
        if not self.enabled:
            return _NULL_COUNTER  # type: ignore[return-value]
        return self.registry.counter(name, help, labels)

    def gauge(self, name: str, help: str = "", labels=None) -> Gauge:
        if not self.enabled:
            return _NULL_GAUGE  # type: ignore[return-value]
        return self.registry.gauge(name, help, labels)

    def histogram(self, name: str, help: str = "", labels=None,
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        if not self.enabled:
            return _NULL_HISTOGRAM  # type: ignore[return-value]
        return self.registry.histogram(name, help, labels, buckets=buckets)

    def export_prometheus(self, path: str) -> None:
        from fedtpu.obs.exporters import write_prometheus

        write_prometheus(self.registry, path)


# Shared disabled instance for components whose config has no telemetry
# field (or that predate one) — all calls are no-ops.
NULL_TELEMETRY = Telemetry("off")
