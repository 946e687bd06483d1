"""Span tracer: nested round/client/phase spans with monotonic timing.

Spans are recorded as Chrome trace-event "complete" events (``ph: "X"``) so
a dump loads directly in Perfetto / ``chrome://tracing``. Two nesting
signals are emitted:

- **time containment** per thread track (``tid``) — what the viewers render;
- explicit ``args.span_id`` / ``args.parent_id`` links — what the tests
  (and :mod:`tools.metrics_report`) verify, and the only signal that holds
  across threads: a ``decode`` span running in a collect worker thread is
  parented to the main thread's ``round`` span by id, not by track.

Parentage defaults to the innermost open span **on the same thread**
(a thread-local stack); cross-thread children pass ``parent=`` explicitly
(:meth:`SpanTracer.span` / :meth:`SpanTracer.current_id`).

The profiler's clock: every span also enters a
``jax.profiler.TraceAnnotation`` of the same name (:func:`profiler_span`),
so a jax profiler session (``--profile-rounds``) records the framework's
spans in the same ``.xplane.pb``, on the same clock, as the device's
operations — what ``tools/gap_analyze.py`` attributes idle gaps to. Whether
a session is listening is the annotation's own flag test in C++ (about
0.4 us a span when none is); the import is lazy so that this module never
drags in a backend.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Shared no-op span: what ``Telemetry.span`` returns in ``off`` mode.
    ``id`` is None so ``parent=span.id`` chains stay valid."""

    __slots__ = ()
    id = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NullSpan()


def profiler_span(name: str, args: Dict[str, Any]):
    """The span as the jax profiler records it, and nothing else: a
    ``TraceAnnotation`` (a ``StepTraceAnnotation`` when ``args`` carries
    ``step_num``, so that a viewer groups device work by round). What
    ``Telemetry.span`` returns in ``basic`` mode; ``id`` is None like
    :data:`NULL_SPAN`'s, since only a recorded span has one."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    kind = StepTraceAnnotation if "step_num" in args else TraceAnnotation
    span = kind(name, **args)
    span.id = None
    return span


class _Span:
    __slots__ = ("_tracer", "name", "args", "id", "parent", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 parent: Optional[int], args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.parent = parent
        self.id = None
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        tr = self._tracer
        self.id = next(tr._ids)
        if self.parent is None:
            self.parent = tr.current_id()
        stack = tr._stack()
        stack.append(self.id)
        self._ann = profiler_span(self.name, self.args)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        tr = self._tracer
        stack = tr._stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        args = {"span_id": self.id}
        if self.parent is not None:
            args["parent_id"] = self.parent
        args.update(self.args)
        tr._record({
            "name": self.name,
            "ph": "X",
            "ts": round((self._t0 - tr._t0) * 1e6, 3),
            "dur": round((t1 - self._t0) * 1e6, 3),
            "pid": tr._pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args,
        })


class SpanTracer:
    """Collects spans; thread-safe; export via :func:`write_chrome_trace`."""

    def __init__(self):
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self.wall_start = time.time()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._pid = os.getpid()
        # Federation-wide trace identity: random per tracer, OVERWRITTEN on
        # remote clients the moment a propagated context arrives
        # (fedtpu.obs.propagate) so every process in one federation run
        # shares the coordinator's id. Span ids stay process-local;
        # tools/trace_merge.py qualifies them by role when stitching.
        self.trace_id: str = os.urandom(8).hex()
        # Optional per-event hook (e.g. the flight recorder's span feed) —
        # called with the finished Chrome event OUTSIDE the tracer lock.
        # Must never raise into the traced code path.
        self.sink = None

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, event: dict) -> None:
        with self._lock:
            self._events.append(event)
        sink = self.sink
        if sink is not None:
            try:
                sink(event)
            except Exception:
                pass

    # ------------------------------------------------------------------ api
    def span(self, name: str, parent: Optional[int] = None,
             **args: Any) -> _Span:
        """Context manager for one timed span. ``parent`` overrides the
        thread-local nesting (required when the span runs on a different
        thread than its logical parent)."""
        return _Span(self, name, parent, args)

    def current_id(self) -> Optional[int]:
        """Innermost open span id on THIS thread (None outside any span) —
        capture it before handing work to another thread, then pass it as
        that thread's ``parent=``."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


def write_chrome_trace(events: List[dict], path: str,
                       metadata: Optional[Dict[str, Any]] = None) -> None:
    """Write events as a Perfetto/chrome://tracing-loadable JSON object.

    ``metadata`` (ignored by viewers, read by ``tools/trace_merge.py``)
    carries the process identity a multi-process merge needs: the
    federation ``trace_id``, this process's ``role``/``pid``, and
    ``wall_start`` — the wall-clock time of the tracer's monotonic zero,
    which is how per-process relative timestamps align on one timeline.
    """
    doc = {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
    }
    if metadata:
        doc["metadata"] = dict(metadata)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh)
    os.replace(tmp, path)


def load_chrome_trace(path: str) -> List[dict]:
    """Read back a :func:`write_chrome_trace` dump (accepts the bare-array
    form too — both are valid Chrome trace JSON)."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        return doc
    return doc["traceEvents"]
