"""Trace recorder for the serving path.

``TraceRecorder`` collects **spans** (begin/end pairs), **instant
events** and **counter samples** in a bounded ring buffer — when it
overflows the oldest events are dropped and the drop is accounted
(``dropped_events``), never silently. Every event lives on one of two
clocks, and the two never share an axis:

* **modeled** — the ``M2CacheEngine`` transfer clock, in seconds: the
  scheduler's spans, instants and counters (``span``, ``instant``,
  ``counter``, ``span_begin``/``span_end``).
* **host** — ``time.perf_counter()`` seconds: ``host_span`` times the
  served path's layer boundaries (scheduler iteration, engine step, M2
  neuron-cache bookkeeping, KV tier moves) as the host ran them. While
  one is open it also holds a ``jax.profiler.TraceAnnotation`` named
  ``m2c:<track>.<name>``, so under a running profiler trace the span
  lands in the ``.xplane.pb`` on the device trace's clock.

Two invariants keep instrumentation safe to leave on:

* Recording NEVER advances the modeled clock — emitters pass the
  current engine time (or the recorder reads it through an attached
  ``clock`` callable); the recorder only stores floats. Modeled tok/s
  with tracing on is therefore *identical* to tracing off, which
  ``benchmarks/serving_obs.py`` asserts.
* Recording never touches RNG or model state, so generated tokens are
  byte-identical with tracing on/off.

``export_chrome`` writes Chrome ``trace_event`` JSON (the
``{"traceEvents": [...]}`` envelope) that loads directly in Perfetto /
``chrome://tracing``: spans as ``ph="X"`` complete events, instants as
``ph="i"``, counters as ``ph="C"``. Modeled events form one process and
host spans a second one (``otherData.host_pid``); seconds map to trace
microseconds and each track becomes a named thread via ``ph="M"``
``thread_name`` metadata — see ``docs/OBSERVABILITY.md``.
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

#: event kinds stored in the ring buffer
SPAN = "span"          # completed span: t .. t + dur
INSTANT = "instant"
COUNTER = "counter"

#: the clock an event's ``t`` and ``dur`` are on
MODELED = "modeled"    # engine transfer clock (s)
HOST = "host"          # time.perf_counter() (s)

#: Chrome-export process ids, one per clock
MODELED_PID = 1
HOST_PID = 2

#: prefix of the profiler annotations host spans hold while open
ANNOTATION_PREFIX = "m2c:"

DEFAULT_CAPACITY = 200_000


@dataclasses.dataclass
class TraceEvent:
    kind: str                 # SPAN | INSTANT | COUNTER
    track: str                # display track (Chrome "thread")
    name: str
    t: float                  # seconds on ``clock``
    dur: float = 0.0          # seconds on ``clock``; spans only
    args: Optional[Dict[str, Any]] = None
    clock: str = MODELED      # MODELED | HOST


class _OpenSpan:
    __slots__ = ("track", "name", "t0", "args")

    def __init__(self, track, name, t0, args):
        self.track, self.name = track, name
        self.t0, self.args = t0, args


class HostSpan:
    """One host-clock span; a context manager from
    :meth:`TraceRecorder.host_span`. :meth:`note` adds args that are
    known only once the work is done (bytes moved, rows served)."""

    __slots__ = ("_rec", "label", "track", "name", "args", "_t0", "_ann")

    def __init__(self, rec: "TraceRecorder", track: str, name: str,
                 args: Dict[str, Any]):
        self._rec = rec
        self.track, self.name = track, name
        self.label = f"{track}.{name}"
        self.args = args

    def note(self, **args):
        self.args.update(args)

    def __enter__(self) -> "HostSpan":
        from jax.profiler import TraceAnnotation
        stack = self._rec._host_stack
        if stack:
            self.args["parent"] = stack[-1].label
        stack.append(self)
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + self.label)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        stack = self._rec._host_stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self._rec._push(TraceEvent(SPAN, self.track, self.name, self._t0,
                                   dur=t1 - self._t0,
                                   args=self.args or None, clock=HOST))
        return False


class _NoSpan:
    """Stands in for a host span when no recorder is attached."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args):
        pass


NO_SPAN = _NoSpan()


def host_span(recorder: Optional["TraceRecorder"], track: str, name: str,
              rid: Optional[int] = None, **args):
    """``recorder.host_span(...)``, or a shared no-op when ``recorder``
    is None — with tracing off a call site costs that one test."""
    if recorder is None:
        return NO_SPAN
    return recorder.host_span(track, name, rid, **args)


class TraceRecorder:
    """Bounded-ring trace recorder on the modeled and host clocks.

    ``clock`` (optional) is a zero-arg callable returning the current
    modeled time; emitters that do not pass an explicit ``t`` fall back
    to it. All modeled timestamps are *raw* engine-clock seconds —
    consumers work in differences (TTFT = first_token − queued start)
    so the origin never matters.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Optional[Callable[[], float]] = None):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = int(capacity)
        self._events: deque = deque(maxlen=self.capacity)
        self._clock = clock
        self._open: Dict[int, _OpenSpan] = {}
        self._next_sid = 0
        self._host_stack: List[HostSpan] = []
        self.total_events = 0      # lifetime emits (incl. dropped)
        self.dropped_events = 0    # evicted by ring overflow

    # -- clock ---------------------------------------------------------
    def set_default_clock(self, clock: Optional[Callable[[], float]]):
        self._clock = clock

    def _now(self, t: Optional[float]) -> float:
        if t is not None:
            return float(t)
        if self._clock is not None:
            return float(self._clock())
        return 0.0

    # -- emission ------------------------------------------------------
    def _push(self, ev: TraceEvent):
        self.total_events += 1
        if len(self._events) == self.capacity:
            self.dropped_events += 1
        self._events.append(ev)

    def span_begin(self, track: str, name: str,
                   t: Optional[float] = None, **args) -> int:
        """Open a span; returns a span id for :meth:`span_end`."""
        sid = self._next_sid
        self._next_sid += 1
        self._open[sid] = _OpenSpan(track, name, self._now(t),
                                    dict(args) or None)
        return sid

    def span_end(self, sid: int, t: Optional[float] = None, **args):
        """Close span ``sid``; extra ``args`` merge into the span's."""
        op = self._open.pop(sid, None)
        if op is None:
            return
        t1 = self._now(t)
        merged = dict(op.args or {})
        merged.update(args)
        self._push(TraceEvent(SPAN, op.track, op.name, op.t0,
                              dur=max(0.0, t1 - op.t0),
                              args=merged or None))

    def span(self, track: str, name: str, t0: float, t1: float, **args):
        """Emit an already-complete span in one call."""
        self._push(TraceEvent(SPAN, track, name, float(t0),
                              dur=max(0.0, float(t1) - float(t0)),
                              args=dict(args) or None))

    def instant(self, track: str, name: str,
                t: Optional[float] = None, **args):
        self._push(TraceEvent(INSTANT, track, name, self._now(t),
                              args=dict(args) or None))

    def counter(self, track: str, name: str,
                t: Optional[float] = None, **values):
        """Counter sample; ``values`` are the series of the counter."""
        self._push(TraceEvent(COUNTER, track, name, self._now(t),
                              args={k: float(v) for k, v in values.items()}))

    def host_span(self, track: str, name: str, rid: Optional[int] = None,
                  **args) -> HostSpan:
        """A host-clock span over a ``with`` block: ``perf_counter`` on
        enter and exit, the enclosing host span's ``track.name`` as
        ``parent``, ``rid`` when the work belongs to one request, and a
        ``jax.profiler.TraceAnnotation("m2c:<track>.<name>")`` held
        while it is open. The span goes into the same ring as every
        other event when the block exits."""
        if rid is not None:
            args["rid"] = rid
        return HostSpan(self, track, name, args)

    # -- access --------------------------------------------------------
    def events(self) -> List[TraceEvent]:
        """Events currently in the ring, oldest first."""
        return list(self._events)

    def open_spans(self) -> int:
        return len(self._open) + len(self._host_stack)

    def stats(self) -> Dict[str, int]:
        return {"trace_events": len(self._events),
                "trace_total_events": self.total_events,
                "trace_dropped_events": self.dropped_events,
                "trace_open_spans": self.open_spans()}

    # -- export --------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome ``trace_event`` JSON object (Perfetto-loadable):
        modeled events in process ``MODELED_PID``, host spans in
        ``HOST_PID``; thread ids are unique across both."""
        tids: Dict[tuple, int] = {}
        out: List[Dict[str, Any]] = []
        for ev in self._events:
            pid = HOST_PID if ev.clock == HOST else MODELED_PID
            tid = tids.get((pid, ev.track))
            if tid is None:
                tid = tids[(pid, ev.track)] = len(tids) + 1
            rec = {"name": ev.name, "pid": pid, "tid": tid,
                   "ts": ev.t * 1e6}
            args = dict(ev.args or {})
            if ev.kind == SPAN:
                rec.update(ph="X", dur=ev.dur * 1e6, args=args)
            elif ev.kind == INSTANT:
                rec.update(ph="i", s="t", args=args)
            else:  # COUNTER — args ARE the series
                rec.update(ph="C", args=args)
            out.append(rec)
        pids = sorted({pid for pid, _ in tids} | {MODELED_PID})
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": "host clock" if pid == HOST_PID
                          else "modeled clock"}} for pid in pids]
        meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                  "args": {"name": track}}
                 for (pid, track), tid in tids.items()]
        return {"traceEvents": meta + out,
                "displayTimeUnit": "ms",
                "otherData": {"clock": "modeled_seconds",
                              "host_pid": HOST_PID,
                              "host_clock": "perf_counter_seconds",
                              "dropped_events": self.dropped_events,
                              "total_events": self.total_events}}

    def export_chrome(self, path) -> str:
        obj = self.to_chrome()
        with open(path, "w") as f:
            json.dump(obj, f)
        return str(path)
