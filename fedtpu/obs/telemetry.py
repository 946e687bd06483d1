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

Set-up is a process's fact, not a component's: :meth:`Telemetry.phase`
opens the span ``fed.setup.<phase>`` like any other and adds its wall to
``fedtpu_setup_seconds{phase}`` in the PROCESS-GLOBAL registry
(:func:`~fedtpu.obs.registry.get_global_registry`), where the gauges
outlive the engine that set them and describe the newest one
(:func:`setup_snapshot`).

Each engine/server owns ONE Telemetry instance (its registry is that
component's metric namespace); the FT helpers receive the owning
component's registry and fall back to the process-global one when
constructed standalone.
"""

from __future__ import annotations

import time
from typing import Optional

from fedtpu.obs.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_global_registry,
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


SETUP_SPAN_PREFIX = "fed.setup."
SETUP_METRIC_PREFIX = "fedtpu_setup_"
SETUP_ROOT = "fed.setup.build"  # entering it starts a new engine's set


class _SetupPhase:
    """One set-up phase: the span ``name`` (``fed.setup.<phase>``) and, on
    exit, its host wall added to ``fedtpu_setup_seconds{phase}`` in the
    process-global registry. ``compiles`` phases also hear what jax says
    about tracing, lowering, compiling and the cache while they are open
    (:class:`fedtpu.obs.profile.SetupCompileListener`)."""

    __slots__ = ("_span", "_phase", "_listener", "_t0")
    id = None

    def __init__(self, span, name: str, compiles: bool):
        if not name.startswith(SETUP_SPAN_PREFIX):
            raise ValueError(
                f"a set-up phase is named {SETUP_SPAN_PREFIX}<phase>, "
                f"got {name!r}"
            )
        registry = get_global_registry()
        if name == SETUP_ROOT:
            registry.forget(SETUP_METRIC_PREFIX)
        self._span = span
        self._phase = name[len(SETUP_SPAN_PREFIX):]
        self._listener = None
        if compiles:
            from fedtpu.obs.profile import SetupCompileListener

            self._listener = SetupCompileListener(
                registry, self._phase, _add_setup_seconds)

    def __enter__(self) -> "_SetupPhase":
        self._span.__enter__()
        if self._listener is not None:
            self._listener.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.perf_counter() - self._t0
        try:
            if self._listener is not None:
                self._listener.__exit__(*exc)
        finally:
            self._span.__exit__(*exc)
        _add_setup_seconds(self._phase, wall)


def _add_setup_seconds(phase: str, seconds: float) -> None:
    get_global_registry().gauge(
        "fedtpu_setup_seconds",
        "host wall of a set-up phase of the process's newest engine (span "
        "fed.setup.<phase>), summed over the phase's occurrences; under a "
        "phase that can compile, <phase>.trace / .lower / .compile / "
        ".cache_load: what jax reported inside it, net of each other",
        labels={"phase": phase},
    ).inc(seconds)


def setup_snapshot(ndigits: Optional[int] = None) -> dict:
    """The set-up of the process's newest engine as the global registry
    holds it: ``{"seconds": {phase: s}, "<counter>": value, ...}`` with the
    ``fedtpu_setup_`` prefix dropped (the ``/statusz`` ``setup`` block);
    rounded to ``ndigits`` for a log line."""
    out: dict = {}
    for name, rows in get_global_registry().snapshot().items():
        if not name.startswith(SETUP_METRIC_PREFIX):
            continue
        key = name[len(SETUP_METRIC_PREFIX):]
        for row in rows:
            value = row["value"] if ndigits is None else round(
                row["value"], ndigits)
            if row["labels"]:
                out.setdefault(key, {})[row["labels"]["phase"]] = value
            else:
                out[key] = value
    return out


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

    def phase(self, name: str, compiles: bool = False):
        """A set-up phase: the span ``name`` (``fed.setup.<phase>``) plus
        its wall in ``fedtpu_setup_seconds{phase}`` of the process-global
        registry; ``compiles=True`` also charges jax's compile events that
        fire inside it. Nothing in ``off`` mode."""
        if not self.enabled:
            return NULL_SPAN
        return _SetupPhase(self.span(name), name, compiles)

    def setup_gauge(self, name: str, help: str = "") -> Gauge:
        """A ``fedtpu_setup_*`` count beside the phases, in the same
        process-global registry (and forgotten with them when the next
        engine's ``fed.setup.build`` opens)."""
        if not self.enabled:
            return _NULL_GAUGE  # type: ignore[return-value]
        return get_global_registry().gauge(name, help)

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
