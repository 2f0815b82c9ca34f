"""Ring-buffer span tracer with Chrome-trace-event / Perfetto JSON export.

Contract (docs/observability.md §Overhead contract):

* **Monotonic clock** — timestamps come from ``time.perf_counter_ns``
  (never wall-clock), taken once on span entry and once on exit. All
  exported timestamps are microseconds relative to the tracer's birth.
* **Bounded memory** — events land in a fixed-capacity ring buffer; once
  full, the oldest event is overwritten and ``dropped`` counts how many
  were lost (the export records the drop count, so a truncated trace can
  never silently masquerade as a complete one).
* **Thread-safe** — the ring push takes a lock; spans themselves carry no
  shared state, so concurrently open spans from different threads are
  fine. The exported events carry the OS thread id, so Perfetto renders
  one track per thread.
* **Disabled = no-op** — unless a profiler session is capturing, a
  disabled tracer's ``span()`` returns a single module-level
  ``_NULL_SPAN`` object (no allocation, no clock read, no lock) and
  ``instant()`` returns immediately. The decode hot path can therefore
  keep its instrumentation calls unconditionally; with telemetry off they
  cost one attribute load, one branch and one ``is_enabled()`` check
  (negative-tested in tests/test_telemetry.py).
* **Profiler sink** — while a JAX profiler session is capturing
  (``jax.profiler.trace`` / ``start_trace``), every span also opens a
  ``jax.profiler.TraceAnnotation`` carrying its args, and every instant
  writes a zero-length one (a *mark*), enabled tracer or not. They land
  on the host plane of the profiler's trace, on the same clock as the
  device operations. Arg values are encoded by ``encode_arg``: no ``,``,
  ``#`` or ``=`` (the annotation format's separators), lists joined with
  spaces.

Export is the Chrome trace-event JSON array format (``{"traceEvents":
[...]}``) that both ``chrome://tracing`` and https://ui.perfetto.dev load
directly: ``"X"`` (complete) events for spans, ``"i"`` (instant) events
for point markers, ``"M"`` metadata records for track names.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

HOST_PID = 0            # pid of the host-side scheduler/engine/trainer track

# True while a profiler session is capturing (one C++ call)
capturing = TraceAnnotation.is_enabled

_ARG_SEPARATORS = str.maketrans({",": ";", "#": ";", "=": ":"})


def encode_arg(value) -> str:
    """An annotation arg value: lists and tuples joined with spaces, the
    annotation format's separators (`,` `#` `=`) replaced."""
    s = (" ".join(map(str, value)) if isinstance(value, (list, tuple))
         else str(value))
    if "," in s or "#" in s or "=" in s:
        return s.translate(_ARG_SEPARATORS)
    return s


def _annotation(name: str, args) -> TraceAnnotation:
    return TraceAnnotation(name, **{k: encode_arg(v)
                                    for k, v in (args or {}).items()})


def mark(name: str, **args) -> None:
    """A zero-length annotation in the profiler's trace; call it only
    while a session is `capturing()`."""
    with _annotation(name, args):
        pass


class _NullSpan:
    """The disabled-tracer span: a process-wide singleton whose context
    protocol does nothing. `annotate` swallows late args the same way."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **args):
        return self


_NULL_SPAN = _NullSpan()


class _ProfilerSpan:
    """A disabled tracer's span while a profiler session is capturing:
    only the profiler annotation."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, args):
        self._ann = _annotation(name, args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def annotate(self, **args):
        self._ann.set_metadata(**{k: encode_arg(v) for k, v in args.items()})
        return self


class _Span:
    """An open span: records [enter, exit) as one complete event, and is
    a profiler annotation too while a session is capturing."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._ann = _ProfilerSpan(name, args) if capturing() else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._tracer._now_us()
        return self

    def __exit__(self, *exc):
        t1 = self._tracer._now_us()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._push(("X", self._name, self._cat, self._t0,
                            t1 - self._t0, threading.get_ident(),
                            self._args or None))
        return False

    def annotate(self, **args):
        """Attach (or override) args after entry — e.g. a row count only
        known once the work inside the span finished."""
        if self._args is None:
            self._args = {}
        self._args.update(args)
        if self._ann is not None:
            self._ann.annotate(**args)
        return self


class Tracer:
    """Low-overhead span/instant recorder. See the module docstring for
    the clock/memory/threading/disabled contract."""

    def __init__(self, enabled: bool = True, capacity: int = 1 << 16,
                 clock: Callable[[], int] = time.perf_counter_ns):
        self.enabled = enabled
        self.capacity = int(capacity)
        self.dropped = 0
        self._clock = clock
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        self._next = 0                      # overwrite cursor once full
        self._t0_ns = clock() if enabled else 0

    # -- recording ---------------------------------------------------------

    def _now_us(self) -> float:
        return (self._clock() - self._t0_ns) / 1e3

    def _push(self, ev: tuple) -> None:
        with self._lock:
            if len(self._events) < self.capacity:
                self._events.append(ev)
            else:
                self._events[self._next] = ev
                self._next = (self._next + 1) % self.capacity
                self.dropped += 1

    def span(self, name: str, cat: str = "span", **args):
        """Context manager timing a host-side region. Disabled tracers
        return the no-op singleton — zero allocation on the hot path —
        unless a profiler session is capturing."""
        if not self.enabled:
            return _ProfilerSpan(name, args) if capturing() else _NULL_SPAN
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "event", **args) -> None:
        """A point-in-time marker (rendered as an arrow/flag in Perfetto,
        and a mark in the profiler's trace while a session captures)."""
        if capturing():
            mark(name, **args)
        if not self.enabled:
            return
        self._push(("i", name, cat, self._now_us(), 0,
                    threading.get_ident(), args or None))

    # -- export ------------------------------------------------------------

    def events(self) -> List[tuple]:
        """Recorded events, oldest first (unwrapping the ring)."""
        with self._lock:
            if len(self._events) < self.capacity:
                return list(self._events)
            return self._events[self._next:] + self._events[:self._next]

    def chrome_events(self) -> List[Dict]:
        """Events as Chrome trace-event dicts (host pid, per-thread tids)."""
        out = []
        for ph, name, cat, ts, dur, tid, args in self.events():
            ev = {"ph": ph, "name": name, "cat": cat, "ts": round(ts, 3),
                  "pid": HOST_PID, "tid": tid}
            if ph == "X":
                ev["dur"] = round(dur, 3)
            if ph == "i":
                ev["s"] = "t"               # thread-scoped instant
            if args:
                ev["args"] = args
            out.append(ev)
        return out


def write_chrome_trace(path: str, events: List[Dict],
                       metadata: Optional[Dict] = None) -> str:
    """Write a Chrome-trace/Perfetto JSON object file. `events` are
    trace-event dicts (from `Tracer.chrome_events` plus any synthesized
    track events); `metadata` lands under the top-level "metadata" key."""
    payload = {
        "traceEvents": sorted(events, key=lambda e: e.get("ts", 0.0)),
        "displayTimeUnit": "ms",
    }
    if metadata:
        payload["metadata"] = metadata
    with open(path, "w") as f:
        json.dump(payload, f)
        f.write("\n")
    return path
