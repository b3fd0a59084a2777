"""Host-side tracing spans with Chrome/Perfetto export (DESIGN.md §9.2).

``with span("queue.flush", tenant="t0"):`` records one complete ("X")
``trace_event`` into a fixed-capacity ring buffer: wall-clock ``ts`` and
``dur`` in microseconds, the recording thread's id as ``tid`` (so nested
spans on one thread render as a flame graph by timestamp containment),
and any keyword labels as ``args``. ``Tracer.export()`` writes the
``{"traceEvents": [...]}`` JSON that chrome://tracing and ui.perfetto.dev
load directly (``launch/serve.py --trace-out``).

Disabled is the default posture and it must cost ~nothing: ``span()``
then returns a shared no-op context manager after one attribute check —
no allocation, no clock read. When enabled, spans also enter
``jax.profiler.TraceAnnotation`` (best-effort) so device profiles carry
the same names as the host timeline. Recording never touches jax values:
the ring buffer holds only host floats/strings, so no instrumentation
point can introduce a device sync.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

try:  # device-profile annotation is optional; tracer works without jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - jax always present in this repo
    _TraceAnnotation = None

DEFAULT_CAPACITY = 65536


class _NullSpan:
    """Shared do-nothing context manager handed out while disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records an "X" event on exit."""
    __slots__ = ("_tracer", "name", "args", "_t0", "_annot")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._annot = None

    def __enter__(self):
        if _TraceAnnotation is not None:
            try:
                self._annot = _TraceAnnotation(self.name)
                self._annot.__enter__()
            except Exception:
                self._annot = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._annot is not None:
            try:
                self._annot.__exit__(*exc)
            except Exception:
                pass
        self._tracer._record(self.name, self._t0, dur, self.args)
        return False

    def set(self, **args):
        """Add labels known only inside the span (the flush that served a
        request); they land in the event's ``args``, never in its name."""
        self.args.update(args)


class Tracer:
    """Ring-buffered trace-event recorder.

    Events are stored newest-wins in a circular list so a long serving
    run keeps the most recent ``capacity`` spans; ``events()`` returns
    them in chronological order.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._ring: List[Optional[dict]] = []
        self._head = 0
        self._dropped = 0
        self.enabled = False
        self._epoch = time.perf_counter()

    # ------------------------------------------------------------- control
    def enable(self, capacity: Optional[int] = None):
        with self._lock:
            if capacity is not None and capacity != self._capacity:
                self._capacity = int(capacity)
                self._ring = []
                self._head = 0
            self.enabled = True

    def disable(self):
        self.enabled = False

    def clear(self):
        with self._lock:
            self._ring = []
            self._head = 0
            self._dropped = 0
            self._epoch = time.perf_counter()

    # ----------------------------------------------------------- recording
    def span(self, name: str, **args):
        """Context manager timing a span. Near-free when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def _record(self, name: str, t0: float, dur: float,
                args: Dict[str, Any]):
        ev = {
            "name": name, "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,
            "dur": dur * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        self._push(ev)

    def _push(self, ev: dict):
        with self._lock:
            if len(self._ring) < self._capacity:
                self._ring.append(ev)
            else:
                self._ring[self._head] = ev
                self._head = (self._head + 1) % self._capacity
                self._dropped += 1

    # ------------------------------------------------------------- reading
    def events(self) -> List[dict]:
        """Recorded events, oldest first."""
        with self._lock:
            out = self._ring[self._head:] + self._ring[:self._head]
        return sorted(out, key=lambda e: e["ts"])

    @property
    def dropped(self) -> int:
        return self._dropped

    def export(self, path: Optional[str] = None) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON; written to ``path`` when
        given, returned either way."""
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self._dropped},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


TRACER = Tracer()


def span(name: str, **args):
    """Module-level shorthand for ``TRACER.span`` — the one-attribute-check
    fast path every hot instrumentation point uses."""
    if not TRACER.enabled:
        return _NULL_SPAN
    return _Span(TRACER, name, args)

