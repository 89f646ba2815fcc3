"""TraceRecorder: nestable spans + point events for one request lifecycle.

One recorder is threaded through the whole serving stack (engine → planner →
tier stack → prefetcher → admission → serving loop) and emits a single
structured event stream: queue wait → admission decision (launch reason) →
per-round plan (site, THRESHOLD/TWO-PRONG choice, predicted vs observed
io_time) → tier/peer/prefetch fetch outcomes → device transfer →
cut/satisfy.  ``tools/trace_report.py`` reconstructs per-request critical
paths from the exported JSONL without touching live engine state.

Design contract
---------------
* **Injectable clock** — ``clock()`` is read exactly twice per span (enter /
  exit) and once per event; tests inject counting or simulated clocks.
* **Deterministic ids** — span/event ids come from one monotonic counter,
  so identical runs produce identical streams (modulo timestamps).
* **Ring buffer** — the event deque is bounded by ``max_events``; overflow
  evicts the oldest events and counts them in ``dropped`` (never silent).
* **Disabled is free** — a recorder built with ``enabled=False`` (and every
  call site guarded by ``obs is not None``) performs **zero clock reads and
  zero per-event allocations**: :meth:`TraceRecorder.span` returns one
  shared no-op context manager and :meth:`TraceRecorder.event` returns
  before touching the clock or the buffer.  The byte-identity oracles run
  unchanged with tracing on or off — tracing observes, never steers.
* **Single-threaded** — the recorder is wired on the serving thread only;
  the async prefetch worker never emits (its results are traced at drain).
* **One clock with the device trace** — a live span also holds a
  ``jax.profiler.TraceAnnotation`` of its name for its duration, so under
  ``jax.profiler`` every span lies in the profiler's host plane, on the
  device trace's own clock (a no-op while no profile is being taken).

:func:`span` is the one way a call site opens a span when its recorder may
be ``None``: it hands back :data:`NULL_SPAN` then, so a traced site needs no
untraced twin of its body.

Span nesting is tracked with an explicit stack: a span opened while another
is active records it as its parent, so one serving tick yields a tree
(``serve.tick`` → ``wave.round`` → fetch events) the report renders as a
per-request timeline.
"""
from __future__ import annotations

import itertools
import json
import time
from collections import deque

from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry


class _NullSpan:
    """The shared no-op span: one instance, no state, no clock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class _Span:
    """A live span: times itself on enter/exit, mirrors itself into the
    profiler's host trace, and emits one record."""

    __slots__ = ("rec", "name", "attrs", "sid", "parent", "t0", "ann")

    def __init__(self, rec: "TraceRecorder", name: str, attrs: dict):
        self.rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        rec = self.rec
        self.sid = next(rec._ids)
        self.parent = rec._stack[-1] if rec._stack else 0
        rec._stack.append(self.sid)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = rec.clock()
        return self

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. the round's observed
        io_time, known only after the fetch)."""
        self.attrs.update(attrs)
        return self

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        t1 = rec.clock()
        self.ann.__exit__(*exc)
        rec._stack.pop()
        e = {"kind": "span", "name": self.name, "id": self.sid,
             "parent": self.parent, "t0": self.t0, "t1": t1}
        if self.attrs:
            e["attrs"] = self.attrs
        rec._emit(e)
        return False


class TraceRecorder:
    """Bounded structured trace + its :class:`MetricsRegistry`.

    The recorder doubles as the ``obs`` facade every subsystem accepts: it
    carries the metrics registry (``rec.metrics``) so one object wires both
    the event stream and the counter/histogram plane.
    """

    def __init__(self, clock=time.perf_counter, max_events: int = 65536,
                 metrics: MetricsRegistry | None = None, enabled: bool = True):
        if max_events < 1:
            raise ValueError("max_events must be >= 1")
        self.clock = clock
        self.enabled = bool(enabled)
        self.events: deque = deque(maxlen=int(max_events))
        self.dropped = 0
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._ids = itertools.count(1)
        self._stack: list[int] = []

    # ------------------------------------------------------------------ emit
    def _emit(self, e: dict) -> None:
        ev = self.events
        if len(ev) == ev.maxlen:
            self.dropped += 1
        ev.append(e)

    def span(self, name: str, **attrs):
        """Context manager timing a nested span.  Disabled recorders return
        the shared :data:`NULL_SPAN` — no allocation, no clock read."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        """One point-in-time record, parented under the active span."""
        if not self.enabled:
            return
        t = self.clock()
        e = {"kind": "event", "name": name, "id": next(self._ids),
             "parent": self._stack[-1] if self._stack else 0, "t": t}
        if attrs:
            e["attrs"] = attrs
        self._emit(e)

    # ---------------------------------------------------------------- export
    def to_events(self) -> list[dict]:
        """The buffered events, oldest first (a copy; safe to mutate)."""
        return list(self.events)

    def export_jsonl(self, path: str) -> str:
        """Write the buffer as JSONL (one event per line, sorted keys —
        identical runs produce identical bytes modulo timestamps)."""
        with open(path, "w") as f:
            for e in self.events:
                f.write(json.dumps(e, sort_keys=True) + "\n")
        return str(path)


def span(obs: "TraceRecorder | None", name: str, **attrs):
    """``obs.span(name, **attrs)``, or the shared :data:`NULL_SPAN` when
    ``obs`` is ``None``: no clock read and no record.  Attributes that cost
    work to compute belong in a ``set`` under ``if obs is not None``."""
    if obs is None:
        return NULL_SPAN
    return obs.span(name, **attrs)
