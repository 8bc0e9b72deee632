"""Host-side span tracing — Chrome-trace-event output with a clock anchor
(``docs/observability.md``).

The reference repo's timing story is two ``time.time()`` reads around the
epoch loop printed on rank 0; ``jax.profiler`` captures the DEVICE side but
says nothing about the host work that starves it (checkpoint serialization,
loader waits, eval loops). This module records **host spans** on a
monotonic clock (``time.perf_counter``) and emits them in the Chrome
trace-event format, so one file loads in Perfetto / ``chrome://tracing``
and shows the host timeline. :func:`clock_anchor` ties that clock to Unix
time, which is how the spans are laid beside a device capture. (A
context-manager span also enters a ``jax.profiler.TraceAnnotation``, but
that reaches a capture only with the profiler's host tracer on, which the
TPU loop cannot afford; :func:`add_event`/:func:`add_timed` records never
do.)

Contract (audited by TD106): arming the recorder changes NOTHING inside
the traced train step — spans wrap host code only, and a disabled
recorder's :func:`span` returns a shared no-op context (one global read,
no allocation). Nesting needs no explicit stack: complete (``"ph": "X"``)
events on the same thread nest by interval containment, which is exactly
how the viewers render them.

Usage::

    spans.enable()
    with spans.span("ckpt/save", epoch=3):
        ...
    spans.export_chrome_trace("trace.json")   # or drain() into history
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional, Tuple

from tpu_dist.obs import counters

#: Cap on buffered events: a week-long run must not grow host memory
#: without bound. Overflow drops new events and counts them (the count is
#: surfaced in the exported trace metadata, never silently).
MAX_EVENTS = 200_000

_LOCK = threading.Lock()
_ENABLED = False
_EVENTS: List[dict] = []
_DROPPED = 0
_PID = 0
# One clock zero for every event in the process, set at import and reset by
# enable(): perf_counter is monotonic and sub-microsecond, and a common
# origin keeps cross-thread spans comparable in the viewer.
_T0 = time.perf_counter()
# (perf_counter, time_ns) read back to back by enable(): the pair that ties
# every event's ``ts`` to the wall clock (see clock_anchor)
_ANCHOR: Optional[Tuple[float, int]] = None
_ANNOTATION = None  # cached jax.profiler.TraceAnnotation (resolved lazily)
# Span-OPEN listener (the flight recorder's tap, docs/observability.md
# "Crash forensics"): called with (name, args) the moment a span opens,
# INDEPENDENT of the recorder being enabled — crash forensics runs on
# every rank, while span buffering stays rank-0-only. The listener must
# never raise (FlightRecorder.record is never-raise by contract).
_OPEN_LISTENER = None


class _NullSpan:
    """Shared do-nothing context for the disabled recorder."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "_t0", "_ann")

    def __init__(self, name: str, args: Dict[str, object]):
        self.name = name
        self.args = args
        self._ann = None

    def __enter__(self):
        lis = _OPEN_LISTENER
        if lis is not None:
            lis(self.name, self.args)
        ann = _ANNOTATION
        if ann is not None and _ENABLED:
            # bridge: while this host span is open, the XLA profiler (when
            # capturing) tags device activity with the same name
            self._ann = ann(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        add_event(self.name, self._t0, end - self._t0, **self.args)
        return False


def span(name: str, **args):
    """Context manager timing a host region. Free when disabled (a real
    span is still constructed — without buffering — when only the crash-
    forensics open listener is set, so span opens reach the flight ring
    on every rank)."""
    if not _ENABLED and _OPEN_LISTENER is None:
        return _NULL
    return _Span(name, args)


def set_open_listener(fn) -> None:
    """Arm the span-open tap (one per process; the trainer points it at
    its :class:`~tpu_dist.obs.flight.FlightRecorder`). ``fn(name, args)``
    is called at every span open, enabled or not."""
    global _OPEN_LISTENER
    _OPEN_LISTENER = fn


def clear_open_listener() -> None:
    global _OPEN_LISTENER
    _OPEN_LISTENER = None


def add_event(name: str, t_start: float, duration: float, **args) -> None:
    """Record an already-timed region (``t_start`` from
    ``time.perf_counter()``). Lets call sites that measure phases anyway
    (the trainer's step-phase split) emit spans without double-timing."""
    global _DROPPED
    if not _ENABLED:
        return
    evt = {
        "name": name,
        "ph": "X",
        "ts": round((t_start - _T0) * 1e6, 1),  # Chrome traces are in us
        "dur": round(duration * 1e6, 1),
        "pid": _PID,
        "tid": threading.get_ident() & 0x7FFFFFFF,
    }
    if args:
        evt["args"] = args
    with _LOCK:
        if len(_EVENTS) >= MAX_EVENTS:
            _DROPPED += 1
            return
        _EVENTS.append(evt)


def add_timed(name: str, counter: Optional[str], t_start: float, **args) -> float:
    """One clock read per boundary: close the region that began at
    ``t_start`` NOW, add its seconds to the always-on ``counter`` (if
    given) and, recorder on, record the span. Returns the end clock, so
    the next region starts where this one stopped."""
    end = time.perf_counter()
    if counter is not None:
        counters.add_seconds(counter, end - t_start)
    add_event(name, t_start, end - t_start, **args)
    return end


def enable(fresh: bool = True) -> None:
    """Arm the recorder (fresh buffer, clock re-zeroed). Rank-agnostic:
    every process MAY record; the trainer only enables (and exports) on
    rank 0, keeping the rank-0 output discipline.

    ``fresh=False`` re-arms WITHOUT clearing the buffer or moving the
    clock origin — for tooling (the TD106 audit) that must not destroy a
    live recorder's undrained events or shift later timestamps."""
    global _ENABLED, _DROPPED, _T0, _ANCHOR, _PID, _ANNOTATION
    if fresh:
        with _LOCK:
            _EVENTS.clear()
            _DROPPED = 0
        _T0 = time.perf_counter()
    if fresh or _ANCHOR is None:
        _ANCHOR = (time.perf_counter(), time.time_ns())
    try:  # resolve the bridge + process id once, not per span
        import jax  # noqa: PLC0415

        _ANNOTATION = jax.profiler.TraceAnnotation
        _PID = jax.process_index()
    except Exception:  # jax absent/uninitialized: host-only tracing still works
        _ANNOTATION = None
        _PID = 0
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def clock_anchor() -> Optional[dict]:
    """What turns an event's ``ts`` (us since the recorder's origin) into
    Unix time: ``unix_ns = unix_ns_at_origin + ts * 1000``. Taken from one
    ``perf_counter``/``time_ns`` pair read back to back by the last fresh
    :func:`enable`; None before the first."""
    if _ANCHOR is None:
        return None
    pc, ns = _ANCHOR
    return {
        "perf_counter_s": pc,
        "unix_ns": ns,
        "unix_ns_at_origin": ns - int(round((pc - _T0) * 1e9)),
    }


def events() -> List[dict]:
    """Copy of the buffered events (oldest first)."""
    with _LOCK:
        return list(_EVENTS)


def dropped() -> int:
    with _LOCK:
        return _DROPPED


def drain() -> List[dict]:
    """Return AND clear the buffer — the trainer calls this at epoch ends
    to move spans into the JSONL history incrementally (bounded memory)."""
    with _LOCK:
        out = list(_EVENTS)
        _EVENTS.clear()
        return out


def to_chrome_trace(extra_events: Optional[List[dict]] = None) -> dict:
    """The Perfetto/chrome://tracing JSON object for the buffered (plus any
    caller-supplied) events."""
    evts = events()
    if extra_events:
        evts = extra_events + evts
    out = {"traceEvents": evts, "displayTimeUnit": "ms"}
    meta: Dict[str, object] = {}
    anchor = clock_anchor()
    if anchor is not None:
        meta["tpu_dist_clock_anchor"] = anchor
    d = dropped()
    if d:
        meta["tpu_dist_dropped_events"] = d
    if meta:
        out["metadata"] = meta
    return out


def export_chrome_trace(path: str, extra_events: Optional[List[dict]] = None) -> str:
    """Write the Chrome trace JSON to ``path``; returns the path. Caller
    owns the rank-0 guard (the trainer exports on the primary only)."""
    # tpu-dist: ignore[TD002] — the trainer calls this under its rank-0
    # telemetry guard; standalone users own their own process discipline
    with open(path, "w") as f:
        json.dump(to_chrome_trace(extra_events), f)
    return path
