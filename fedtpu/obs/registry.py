"""Thread-safe metrics registry: counters, gauges, histograms.

Replaces the ad-hoc measurement state that used to live scattered across the
round loop — mutable-list byte accumulators shared between thread-pool
workers without a lock (the ``bytes_up = [0]`` pattern the PR-3 tentpole
retires), closure variables in codecs, and silent state flips in the FT
modules — with one typed, lockable home. The shape follows the Prometheus
client-library data model (counter / gauge / histogram, optional label
sets) because that is the schema :func:`prometheus_text` renders, but the
implementation is deliberately dependency-free: plain ``threading.Lock``
per metric, no background threads, no jax import (the FT modules must stay
importable without initialising a backend).

Cost model: one ``inc``/``observe`` is a lock acquire + a float add —
tens of nanoseconds. That is why the per-round *wire accounting* in
:meth:`fedtpu.transport.federation.PrimaryServer.round` uses bare
:class:`Counter` objects unconditionally (correctness under threads is not
a telemetry feature), while the *cumulative* registry is only touched when
``FedConfig.telemetry != "off"``.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Tuple

# Default histogram buckets, in seconds: spans phase timings from sub-ms
# decode work to multi-minute straggler waits. Cumulative ("le") rendering
# happens at export time; observation stores per-bucket counts.
DEFAULT_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class Counter:
    """Monotonically increasing, thread-safe float counter.

    Also usable standalone (outside any registry) as the safe replacement
    for the mutable-list accumulator pattern: workers ``inc()`` without
    external locking, the owner reads ``.value`` after the join.
    """

    kind = "counter"

    def __init__(self) -> None:
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter increment must be >= 0, got {amount}")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Thread-safe settable value (last-write-wins)."""

    kind = "gauge"

    def __init__(self) -> None:
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram: per-bucket counts + sum + count + min/max."""

    kind = "histogram"

    def __init__(self, buckets: Iterable[float] = DEFAULT_BUCKETS) -> None:
        bs = sorted(float(b) for b in buckets)
        if not bs:
            raise ValueError("histogram needs at least one bucket bound")
        self.buckets: Tuple[float, ...] = tuple(bs)
        self._counts = [0] * (len(bs) + 1)  # last slot = +Inf overflow
        self._sum = 0.0
        self._count = 0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            i = 0
            for i, b in enumerate(self.buckets):  # noqa: B007
                if value <= b:
                    break
            else:
                i = len(self.buckets)
            self._counts[i] += 1
            self._sum += value
            self._count += 1
            self._min = min(self._min, value)
            self._max = max(self._max, value)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def snapshot(self) -> dict:
        """{"count", "sum", "min", "max", "buckets": {le: cumulative}}."""
        with self._lock:
            cum, out = 0, {}
            for b, c in zip(self.buckets, self._counts):
                cum += c
                out[b] = cum
            return {
                "count": self._count,
                "sum": self._sum,
                "min": self._min if self._count else None,
                "max": self._max if self._count else None,
                "buckets": out,
            }


def _label_key(labels: Optional[Dict[str, str]]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """Get-or-create metric store keyed by ``(name, labels)``.

    Creation is locked; the returned metric objects carry their own locks,
    so hot-path ``inc``/``observe`` calls never contend on the registry.
    A name is bound to ONE kind — asking for ``counter("x")`` after
    ``gauge("x")`` raises instead of silently aliasing.
    """

    def __init__(self) -> None:
        self._metrics: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], object] = {}
        self._help: Dict[str, str] = {}
        self._kinds: Dict[str, str] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, help: str, labels, **kwargs):
        key = (name, _label_key(labels))
        with self._lock:
            existing_kind = self._kinds.get(name)
            if existing_kind is not None and existing_kind != cls.kind:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{existing_kind}, cannot re-register as {cls.kind}"
                )
            metric = self._metrics.get(key)
            if metric is None:
                metric = cls(**kwargs)
                self._metrics[key] = metric
                self._kinds[name] = cls.kind
                if help:
                    self._help[name] = help
            return metric

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._get(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  buckets: Iterable[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, labels, buckets=buckets)

    def forget(self, prefix: str) -> None:
        """Drop every metric whose name starts with ``prefix``: the facts
        of an owner that has been replaced (a process's set-up gauges
        describe its newest engine). A name stays bound to its kind."""
        with self._lock:
            for key in [k for k in self._metrics if k[0].startswith(prefix)]:
                del self._metrics[key]

    def snapshot(self) -> dict:
        """Plain-dict dump: {name: [{"labels": {...}, ...metric fields}]}."""
        with self._lock:
            items = list(self._metrics.items())
        out: Dict[str, List[dict]] = {}
        for (name, lkey), metric in sorted(items, key=lambda kv: kv[0]):
            entry: dict = {"labels": dict(lkey), "kind": metric.kind}
            if isinstance(metric, Histogram):
                entry.update(metric.snapshot())
            else:
                entry["value"] = metric.value
            out.setdefault(name, []).append(entry)
        return out

    def help_text(self, name: str) -> str:
        with self._lock:
            return self._help.get(name, "")


_GLOBAL = MetricsRegistry()


def get_global_registry() -> MetricsRegistry:
    """Process-wide default registry — the sink for modules that have no
    natural owner to receive one (standalone FT machinery in tests, tools).
    Components with a config (engines, servers) use their own
    :class:`~fedtpu.obs.telemetry.Telemetry` registry instead."""
    return _GLOBAL
