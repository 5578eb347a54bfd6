"""Trace spans → Chrome-trace/Perfetto timeline (ref: ``paddle.profiler``
RecordEvent + chrome-trace export; host-side complement to XLA's own
``jax.profiler`` device timeline).

:func:`span` is a context manager AND a decorator::

    with span("engine.step", tick=3):
        ...

    @span("ckpt.save")
    def save(...): ...

Spans clock with ``time.monotonic_ns`` (never wall clock — a stepped
NTP correction inside a span would report negative durations; on Linux
it is ``CLOCK_MONOTONIC``, the clock ``time.perf_counter`` reads, so a
span's ``ts`` lies on the axis of a benchmark's ``perf_counter``
stamps), record their thread id, and nest: every span event carries an
``id`` and the ``parent`` id of the span that was open on its thread
when it was entered (``None`` at the top), so a reader rebuilds the
tree without guessing from containment.

One span, two sinks, one switch. The global :data:`TRACER` starts
DISABLED. A span records iff, when it is ENTERED (not when it is
created, so a ``@span(...)`` decorator applied at import time starts
tracing the moment tracing is turned on), the tracer is enabled **or a
``jax.profiler`` trace is running**. A recording span does both: it
appends its event to the in-memory buffer, and it opens a
``jax.profiler.TraceAnnotation`` of the same name, which puts it into
the profiler's ``.xplane.pb`` host plane on the profiler's clock,
beside the device operations (a no-op without a running profile). So
``jax.profiler.start_trace`` alone turns the program's spans on, and no
call site opens an annotation of its own. A span entered while both
are off is one object and two flag reads, no clock read and no buffer
write. ``cat="device_wait"`` marks a span in which the host waits for
the device. :func:`instant` emits zero-duration "i" events — fault
injections use it so a chaos run's timeline shows exactly where each
fault landed.

The collector's pauses are on the same timeline: one ``gc.callbacks``
entry, installed when this module is imported, marks every collection as
a span ``host.gc`` (``generation``, ``collected``) under whatever span is
open on the thread the collection interrupts, recorded iff spans record;
and always adds the pause, from its own two clock reads, to the counter
``python_gc_seconds_total{generation}``.

Cross-thread/cross-replica stitching (ISSUE 9): :meth:`Tracer.flow`
emits Chrome-trace flow events — ``ph`` "s" (start) / "t" (step) /
"f" (end) sharing an ``id`` draw as one connected arrow across
threads, which is how one request's hops over prefill and decode
replicas become a single timeline in Perfetto. :meth:`Tracer.track_tid`
assigns a stable synthetic tid to a named logical track (e.g. a
replica name) and labels it with a "M" ``thread_name`` metadata event
prepended at export, so events can be pinned to a lane that is not a
real OS thread.
"""
from __future__ import annotations

import functools
import gc
import itertools
import json
import os
import sys
import threading
import time

from paddle_tpu.observability.metrics import METRICS

__all__ = ["TRACER", "Tracer", "span", "instant", "export_chrome_trace"]


_IDS = itertools.count(1)          # next() is atomic under the GIL
_OPEN = threading.local()          # .stack: ids of this thread's open spans
_ANNOTATION = None                 # jax.profiler.TraceAnnotation, once seen


def _annotation():
    """``jax.profiler.TraceAnnotation`` if the process has imported
    ``jax.profiler`` (no profile can run before that), else None. Never
    imports jax: this module stays importable without a backend."""
    global _ANNOTATION
    if _ANNOTATION is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None
        _ANNOTATION = mod.TraceAnnotation
    return _ANNOTATION


class _Span:
    """One span site. Create fresh per use (``with span(...):``); the
    decorator form re-opens a fresh span per call, so one decoration is
    safe under recursion and concurrent threads."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_id", "_parent",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict,
                 cat: str = "host"):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None

    def begin(self, t_ns: int = None):
        """Enter. A caller that has just read ``time.monotonic_ns()`` for
        its own accounting hands the reading in, so that the span and
        that accounting share one clock read per edge."""
        ann = _annotation()
        if not (self._tracer._enabled
                or (ann is not None and ann.is_enabled())):
            self._t0 = None              # off at entry: nothing recorded
            return self
        self._t0 = time.monotonic_ns() if t_ns is None else t_ns
        stack = _OPEN.__dict__.setdefault("stack", [])
        self._parent = stack[-1] if stack else None
        self._id = next(_IDS)
        stack.append(self._id)
        self._ann = None
        if ann is not None:
            self._ann = ann(self.name, **self.args)
            self._ann.__enter__()
        return self

    @property
    def recording(self) -> bool:
        """True between a recording entry and its exit: guard work done
        only to fill :meth:`set`."""
        return self._t0 is not None

    def set(self, **args):
        """Counts known only inside the span (tokens emitted, requests
        admitted). Dropped, like the span, when it does not record."""
        if self._t0 is not None:
            self.args = {**self.args, **args}
            if self._ann is not None:
                self._ann.set_metadata(**args)

    def end(self, t_ns: int = None):
        if self._t0 is None:             # tracing was off at entry
            return
        t1 = time.monotonic_ns() if t_ns is None else t_ns
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _OPEN.stack
        if stack and stack[-1] == self._id:
            stack.pop()
        elif self._id in stack:          # exited out of order
            stack.remove(self._id)
        self._tracer._emit({
            "name": self.name, "ph": "X", "cat": self.cat,
            "ts": self._t0 / 1e3, "dur": (t1 - self._t0) / 1e3,
            "pid": self._tracer._pid, "tid": threading.get_ident(),
            "id": self._id, "parent": self._parent,
            **({"args": self.args} if self.args else {}),
        })
        self._t0 = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        tracer, name, args, cat = self._tracer, self.name, self.args, self.cat

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with _Span(tracer, name, args, cat):
                return fn(*a, **kw)
        return wrapped


class Tracer:
    """Event buffer + export. ``max_events`` bounds memory: the buffer
    drops NEW events past the cap (and counts the drops) instead of
    growing without bound during a long traced run."""

    _TRACK_TID_BASE = 1 << 22       # clear of real OS thread ids' low range

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self._events: list[dict] = []
        # re-entrant: a collection that starts while this thread holds the
        # lock (``export`` allocates under it) ends in ``_emit``
        self._lock = threading.RLock()
        self._enabled = False
        self._pid = os.getpid()
        self._tracks: dict = {}      # label -> synthetic tid (survives clear)
        self.dropped = 0

    # ------------------------------------------------------------ admin
    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def clear(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def __enter__(self):                 # `with TRACER:` traces a block
        self.enable()
        return self

    def __exit__(self, *exc):
        self.disable()
        return False

    # ----------------------------------------------------------- record
    def span(self, name: str, *, cat: str = "host", **args) -> _Span:
        return _Span(self, name, args, cat)

    def instant(self, name: str, **args):
        """Zero-duration marker ("i" event) — fault injections, restarts."""
        if not self._enabled:
            return
        self._emit({
            "name": name, "ph": "i", "cat": "host", "s": "t",
            "ts": time.monotonic_ns() / 1e3,
            "pid": self._pid, "tid": threading.get_ident(),
            **({"args": args} if args else {}),
        })

    def counter(self, name: str, **values):
        """Chrome-trace counter event ("C"): Perfetto renders the
        series in ``values`` as one stacked counter track, so e.g. KV
        pool occupancy-by-state draws as an area chart over time next
        to the span timeline."""
        if not self._enabled:
            return
        self._emit({
            "name": name, "ph": "C", "cat": "host",
            "ts": time.monotonic_ns() / 1e3,
            "pid": self._pid, "tid": threading.get_ident(),
            "args": {k: float(v) for k, v in values.items()},
        })

    def track_tid(self, label: str) -> int:
        """Stable synthetic tid for a named logical track. Registration
        survives :meth:`clear` — the label registry is metadata, not
        events — and export prepends a ``thread_name`` "M" event per
        track so Perfetto shows the label instead of a bare number."""
        with self._lock:
            tid = self._tracks.get(label)
            if tid is None:
                tid = self._TRACK_TID_BASE + len(self._tracks)
                self._tracks[label] = tid
            return tid

    def flow(self, name: str, flow_id: int, phase: str,
             track: str = None, **args):
        """One flow event. ``phase`` is "s" (start), "t" (step) or "f"
        (end); events sharing ``flow_id`` stitch into one arrow across
        threads. ``track`` pins the event onto a named synthetic track
        (see :meth:`track_tid`) instead of the calling thread's lane."""
        if not self._enabled:
            return
        if phase not in ("s", "t", "f"):
            raise ValueError(f"flow phase must be s/t/f, got {phase!r}")
        tid = self.track_tid(track) if track else threading.get_ident()
        ev = {
            "name": name, "ph": phase, "cat": "flow", "id": int(flow_id),
            "ts": time.monotonic_ns() / 1e3, "pid": self._pid, "tid": tid,
            **({"args": args} if args else {}),
        }
        if phase == "f":
            ev["bp"] = "e"           # bind to enclosing slice
        self._emit(ev)

    def _emit(self, ev: dict):
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    # ----------------------------------------------------------- export
    def export(self) -> dict:
        """Chrome-trace JSON object (load at chrome://tracing or
        ui.perfetto.dev)."""
        with self._lock:
            meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                     "tid": tid, "args": {"name": label}}
                    for label, tid in self._tracks.items()]
            events = meta + list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"producer": "paddle_tpu.observability",
                              "dropped_events": self.dropped}}

    def export_chrome_trace(self, path: str = None) -> str:
        """Serialise the timeline; write to ``path`` when given. Returns
        the JSON string either way."""
        s = json.dumps(self.export(), separators=(",", ":"))
        if path is not None:
            with open(path, "w") as f:
                f.write(s)
        return s


TRACER = Tracer()


def span(name: str, *, cat: str = "host", **args) -> _Span:
    """Module-level sugar over the global tracer: the one way the
    program marks an interval."""
    return _Span(TRACER, name, args, cat)


def instant(name: str, **args):
    return TRACER.instant(name, **args)


def export_chrome_trace(path: str = None) -> str:
    return TRACER.export_chrome_trace(path)


# ------------------------------------------------------------ the collector
_GC_SECONDS = METRICS.counter(
    "python_gc_seconds_total",
    "seconds the thread that tripped it stood in Python's cyclic garbage "
    "collector, by generation collected (a tail of gaps between tokens "
    "that follows this counter is the collector's)",
    labelnames=("generation",))
_GC_BY_GEN = tuple(_GC_SECONDS.labels(generation=g) for g in range(3))
_GC_OPEN = []                      # (the pass in hand's span, its start)


def _on_gc(phase: str, info: dict):
    """The ``gc.callbacks`` entry. Collections do not nest and a pass
    ends on the thread it began on, so one slot holds the pass in hand."""
    t = time.monotonic_ns()
    if phase == "start":
        _GC_OPEN[:] = [(span("host.gc",
                             generation=info["generation"]).begin(t), t)]
    elif _GC_OPEN:
        sp, t0 = _GC_OPEN.pop()
        _GC_BY_GEN[info["generation"]].inc((t - t0) * 1e-9)
        sp.set(collected=info["collected"])
        sp.end(t)


gc.callbacks.append(_on_gc)
