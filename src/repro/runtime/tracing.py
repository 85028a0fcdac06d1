"""Span tracing: nested wall-clock spans with a Perfetto-loadable export.

The observability layer has three prongs (see :mod:`.diagnostics` for the
other two); this module owns the *where-did-the-time-go* prong:

* :func:`span` — a nested context manager placed at the structural
  boundaries of a run (serve request -> ``driver.run`` -> warm/cold
  launch -> profile stages; the service's submit, poll and launch with
  the launch's pad, complement and stack steps; streaming ticks).
  With no tracer installed it is a no-op costing one attribute read, so
  the instrumentation can live permanently in the hot paths.
* :class:`ChromeTracer` — collects completed spans as Chrome trace
  events (``"ph": "X"`` duration events, microsecond timestamps) and
  writes a ``{"traceEvents": [...]}`` JSON file that loads directly in
  Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
* an opt-in `jax.profiler` hookup — when enabled, every span also opens
  a ``jax.profiler.TraceAnnotation`` carrying the span's attributes, so a
  profile captured with ``jax.profiler.start_trace`` holds the spans on
  the device trace's clock.  ``ChromeTracer`` timestamps come from the
  host's ``perf_counter`` and cannot be overlaid on a device profile.

Spans additionally emit ``span`` telemetry events (name, ``dur_us``,
``depth``) through :mod:`repro.runtime.telemetry` when a sink is active,
so a jsonl capture of a traced run is self-contained.

Selection is a spec string (``REPRO_TRACE`` env var or ``serve --trace``):

* ``chrome:PATH`` — record spans, :meth:`ChromeTracer.save` to PATH;
* ``chrome+jax:PATH`` — same, plus jax profiler annotations;
* ``jax`` — annotations only, nothing recorded host-side;
* ``off``/empty — disabled (:func:`tracer_from_spec` returns ``None``).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from repro.runtime import telemetry

__all__ = [
    "ChromeTracer",
    "JaxTracer",
    "get_tracer",
    "set_tracer",
    "span",
    "tracer_from_spec",
]


class ChromeTracer:
    """Collects spans as Chrome trace events; ``save()`` writes the JSON.

    Thread-safe: spans from service worker threads interleave correctly
    (each records its own ``tid``, so Perfetto renders one track per
    thread).  ``jax_annotations=True`` additionally wraps every span in a
    ``jax.profiler.TraceAnnotation``.
    """

    #: value for the ``cat`` field of every emitted trace event.
    CATEGORY = "repro"

    def __init__(self, path: str, jax_annotations: bool = False):
        self.path = path
        self.jax_annotations = bool(jax_annotations)
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def record(self, name: str, ts_us: int, dur_us: int, tid: int,
               args: Dict[str, Any]) -> None:
        """Append one completed span as a ``ph: "X"`` duration event."""
        event = {
            "name": name,
            "cat": self.CATEGORY,
            "ph": "X",
            "ts": int(ts_us),
            "dur": max(int(dur_us), 1),
            "pid": os.getpid(),
            "tid": int(tid) % 2**31,
        }
        if args:
            event["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            self._events.append(event)

    def save(self, path: Optional[str] = None) -> str:
        """Write the collected spans as Perfetto-loadable JSON; return path."""
        target = path or self.path
        with self._lock:
            doc = {"traceEvents": list(self._events),
                   "displayTimeUnit": "ms"}
        directory = os.path.dirname(os.path.abspath(target))
        os.makedirs(directory, exist_ok=True)
        with open(target, "w") as fh:
            json.dump(doc, fh)
        return target

    def close(self) -> None:
        self.save()


class JaxTracer:
    """``jax`` spec: profiler annotations only, no host-side recording."""

    jax_annotations = True
    path = None

    def __len__(self) -> int:
        return 0

    def record(self, name: str, ts_us: int, dur_us: int, tid: int,
               args: Dict[str, Any]) -> None:
        pass

    def save(self, path: Optional[str] = None) -> Optional[str]:
        return None

    def close(self) -> None:
        pass


def _jsonable(value: Any) -> Any:
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return str(value)


# Process-global tracer, mirroring telemetry's process-global sink: spans
# fire from deep inside the driver where threading a handle through every
# call would contaminate the algorithm API.
_TRACER: Optional[ChromeTracer] = None
_DEPTH = threading.local()


def set_tracer(tracer) -> Optional[ChromeTracer]:
    """Install ``tracer`` (or ``None`` to disable); returns the previous."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def get_tracer():
    """The currently installed tracer, or ``None``."""
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record a nested wall-clock span around the enclosed block.

    No-op (one global read) when no tracer is installed.  ``attrs`` must
    be JSON-scalar-ish; they land in the trace event's ``args``, the
    ``span`` telemetry event's fields and, under a tracer with
    ``jax_annotations``, the profiler event's stats.
    """
    tracer = _TRACER
    if tracer is None:
        yield
        return
    depth = getattr(_DEPTH, "value", 0)
    _DEPTH.value = depth + 1
    annotation = None
    if tracer.jax_annotations:
        try:
            from jax.profiler import TraceAnnotation
            annotation = TraceAnnotation(
                name, **{k: _jsonable(v) for k, v in attrs.items()})
            annotation.__enter__()
        except Exception:  # profiler unavailable: spans still record
            annotation = None
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        dur_us = (time.perf_counter_ns() - t0) // 1000
        if annotation is not None:
            annotation.__exit__(None, None, None)
        _DEPTH.value = depth
        tracer.record(name, t0 // 1000, dur_us, threading.get_ident(), attrs)
        telemetry.emit("span", name=name, dur_us=int(dur_us), depth=depth,
                       **attrs)


def tracer_from_spec(spec: Optional[str]):
    """Build a tracer from a ``REPRO_TRACE`` / ``--trace`` spec string.

    ``chrome:PATH`` | ``chrome+jax:PATH`` | ``jax`` | ``off``/``none``/
    empty/``None`` (returns ``None``).  Raises ``ValueError`` otherwise.
    """
    if spec is None:
        return None
    value = spec.strip()
    if value.lower() in ("", "0", "off", "none", "null", "false"):
        return None
    if value.lower() == "jax":
        return JaxTracer()
    for prefix, jax_on in (("chrome+jax:", True), ("chrome:", False)):
        if value.lower().startswith(prefix):
            path = value[len(prefix):]
            if not path:
                raise ValueError(
                    f"trace spec {spec!r} needs a file path after "
                    f"'{prefix}'")
            return ChromeTracer(path, jax_annotations=jax_on)
    raise ValueError(
        f"unknown trace spec {spec!r} (expected 'chrome:PATH', "
        "'chrome+jax:PATH', 'jax', or 'off')")
