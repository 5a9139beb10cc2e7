"""Low-overhead span recorder: ONE host-side timeline for the whole
stack (DESIGN-OBSERVABILITY.md).

Every layer used to keep its own ad-hoc timing — ``AutoFoldTuner``
calibration numbers died inside ``framework/dispatch.py``, serving
latency lived in private dicts, bench rounds hand-rolled JSON.  This
module is the single sink: training dispatches, serving request
lifecycles, checkpoint IO and user ``RecordEvent`` annotations all
record into one process-wide monotonic-clock ring buffer, so one
export answers "where did this step/request spend its time".

The one rule (PR 36): **a program span is a ring record when armed
and a profiler annotation always.**  ``span(name, args)`` opens a
``jax.profiler.TraceAnnotation`` of the same name, so every span of
the program (``mesh.*``, ``pp.*``, ``dispatch.group``, serving,
checkpoint IO, ``host.gc``, user ``RecordEvent``s) stands in any
``jax.profiler`` trace on the profiler's clock, beside the device's
planes, with no arming and no second system.  The ring is the
operator's export (``PADDLE_TPU_TRACE=1``); the annotation is what a
device trace is read against.

Design constraints (the fold=8 microbench is the referee):

- **~zero cost when disabled.**  ``span(name)`` returns the bare
  annotation: a flag test in C++ while no profiler session runs, no
  ring record, nothing kept.  Arm the ring with ``PADDLE_TPU_TRACE=1``
  (read when ``paddle_tpu.observability`` imports) or :func:`enable`.
- **No host↔device syncs.**  The recorder touches ``time``, a deque
  and the profiler's host-side annotation — never a device value.
  ``scripts/check_host_sync.py`` guards this module like the hot
  loops it instruments.
- **Bounded memory.**  Events land in a ``deque(maxlen=capacity)``
  ring (default 64K events, ``PADDLE_TPU_TRACE_CAPACITY``): a
  week-long serving process keeps the most recent window instead of
  growing without bound.
- **Thread-aware.**  Events carry their OS thread ident; per-thread
  *live* span stacks let the hang watchdog name the phase a wedged
  dispatch died in (:func:`live_spans`).

Clock: ``time.monotonic_ns()`` everywhere — the same clock the
serving ``RequestStats`` milestones use, so retroactive request
lifecycle spans (:func:`add_span`) land on the same timeline as live
``span()`` records.

Exporters: :func:`to_chrome_trace` / :func:`dump_chrome_trace` emit
Chrome/Perfetto ``trace_event`` JSON (``X`` complete events; nesting
is by containment per track); :func:`summary` aggregates per-name
count/total/avg/max for a compact run report.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

__all__ = [
    "enable", "disable", "enabled", "span", "instant",
    "add_span", "live_spans", "events", "clear", "to_chrome_trace",
    "dump_chrome_trace", "summary", "set_track_name",
]

_DEFAULT_CAPACITY = 1 << 16

# module state — plain globals so the disabled fast path is one
# LOAD_GLOBAL + truth test
_enabled: bool = False
_ring: deque = deque(maxlen=_DEFAULT_CAPACITY)
_epoch_ns: int = time.monotonic_ns()
# wall-clock anchor of the monotonic epoch, captured back-to-back with
# it: exported so a multi-rank merge (aggregate.merge_traces) can
# shift each process's relative timestamps onto ONE fleet timeline
_epoch_unix_ns: int = time.time_ns()
# tid -> list[(name, t0_ns)] — the LIVE stack per thread, read by the
# hang watchdog; list append/pop are atomic under the GIL
_live: Dict[int, List] = {}
# explicit display names for synthetic tracks (serving slot lanes)
_track_names: Dict[int, str] = {}
_lock = threading.Lock()


# -- record shapes ----------------------------------------------------------
# ("X", name, tid, t0_ns, dur_ns, args)     complete span
# ("i", name, tid, t_ns, None, args)        instant event


class _Span:
    """A live span: records on ``__exit__``, with the profiler's
    annotation of the same name open beside it.  Only allocated while
    tracing is enabled — the disabled path returns the bare annotation.
    """

    __slots__ = ("_name", "_args", "_tid", "_t0", "_stack", "_entry",
                 "_annotation")

    def __init__(self, name: str, args):
        self._name = name
        self._args = args
        self._annotation = _Annotation(name, **args) if args \
            else _Annotation(name)
        self._tid = threading.get_ident()
        stack = _live.get(self._tid)
        if stack is None:
            stack = _live.setdefault(self._tid, [])
        self._stack = stack
        self._t0 = time.monotonic_ns()
        self._entry = (name, self._t0)
        stack.append(self._entry)

    def __enter__(self):
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(None, None, None)
        t1 = time.monotonic_ns()
        stack = self._stack
        if stack and stack[-1] is self._entry:
            stack.pop()
        else:
            # non-LIFO exit (explicit begin()/end() APIs may overlap):
            # remove THIS span's own entry wherever it sits, so the
            # live stack never strands a phantom open phase
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] is self._entry:
                    del stack[i]
                    break
        _ring.append(("X", self._name, self._tid, self._t0,
                      t1 - self._t0, self._args))
        return False


# -- recording API ----------------------------------------------------------


def span(name: str, args: Optional[Dict[str, Any]] = None):
    """Context manager of one complete span: a profiler annotation
    always, a ring record too when armed.  Disarmed this returns the
    bare ``TraceAnnotation`` — the hot loops call it unconditionally
    and pay a flag test in C++ while no profiler session runs.
    ``args`` (optional dict of plain values) rides into the Chrome
    trace event and into the annotation's stats (``step=12``); hot
    sites that build an args dict should do so per *dispatch*, not
    per step."""
    if not _enabled:
        return _Annotation(name, **args) if args else _Annotation(name)
    return _Span(name, args)


def instant(name: str, args: Optional[Dict[str, Any]] = None):
    """Zero-duration marker (Chrome ``i`` event)."""
    if not _enabled:
        return
    _ring.append(("i", name, threading.get_ident(),
                  time.monotonic_ns(), None, args))


def add_span(name: str, t0_s: float, t1_s: float,
             tid: Optional[int] = None,
             args: Optional[Dict[str, Any]] = None):
    """Record a span RETROACTIVELY from ``time.monotonic()`` second
    timestamps — the serving engine reconstructs each request's
    queued→prefill→decode lifecycle from its ``RequestStats``
    milestones at finalize time, on a synthetic per-slot track
    (``tid``).  Same clock as ``span()``, so both interleave correctly
    on one timeline.  The ring only: a span that is already over
    cannot be bridged to the profiler, whose annotations are taken as
    they open and close."""
    if not _enabled or t1_s < t0_s:
        return
    _ring.append(("X", name,
                  tid if tid is not None else threading.get_ident(),
                  int(t0_s * 1e9), int((t1_s - t0_s) * 1e9), args))


def set_track_name(tid: int, name: str):
    """Display name for a synthetic track (Perfetto thread_name
    metadata) — the serving engine labels slot lanes this way."""
    with _lock:
        _track_names[int(tid)] = str(name)


# -- lifecycle --------------------------------------------------------------


def enable(capacity: Optional[int] = None):
    """Arm the recorder (idempotent).  ``capacity`` resizes the ring
    (drops recorded events); default keeps the current ring."""
    global _enabled, _ring
    with _lock:
        if capacity is not None and capacity != _ring.maxlen:
            _ring = deque(maxlen=int(capacity))
        _enabled = True


def disable():
    """Stop recording.  The ring is kept for export; :func:`clear`
    empties it."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def clear():
    _ring.clear()
    _live.clear()
    with _lock:
        _track_names.clear()


def events() -> List[tuple]:
    """Snapshot of the raw ring (oldest first)."""
    return list(_ring)


def live_spans() -> Dict[str, List[str]]:
    """The CURRENTLY-OPEN span stack of every traced thread,
    outermost first — the hang watchdog's phase attribution.  Keys are
    ``"<thread name> (<ident>)"``."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: Dict[str, List[str]] = {}
    for tid, stack in list(_live.items()):
        if not stack:
            continue
        label = f"{names.get(tid, '?')} ({tid})"
        out[label] = [name for name, _t0 in list(stack)]
    return out


# -- export -----------------------------------------------------------------


def to_chrome_trace() -> Dict[str, Any]:
    """Chrome/Perfetto ``trace_event`` JSON object: ``X`` complete
    events with microsecond timestamps relative to the recorder epoch,
    plus ``M`` thread-name metadata so tracks read as phases, not
    idents.  Load via chrome://tracing or ui.perfetto.dev."""
    pid = os.getpid()
    trace_events: List[Dict[str, Any]] = []
    tids = set()
    for rec in list(_ring):
        kind, name, tid, t_ns, extra, args = rec
        tids.add(tid)
        ev: Dict[str, Any] = {
            "name": name, "pid": pid, "tid": tid, "cat": "paddle_tpu",
            "ts": (t_ns - _epoch_ns) / 1e3,
        }
        if kind == "X":
            ev["ph"] = "X"
            ev["dur"] = extra / 1e3
            if args:
                ev["args"] = args
        else:                                     # "i"
            ev["ph"] = "i"
            ev["s"] = "t"
            if args:
                ev["args"] = args
        trace_events.append(ev)
    thread_names = {t.ident: t.name for t in threading.enumerate()}
    with _lock:
        thread_names.update(_track_names)
    for tid in sorted(tids):
        trace_events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": thread_names.get(tid, f"thread-{tid}")},
        })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms",
            # wall-clock anchor of ts=0 (extra top-level keys are
            # ignored by chrome://tracing and Perfetto; the multi-rank
            # merge uses it to align per-process timelines)
            "epochUnixNs": _epoch_unix_ns}


def dump_chrome_trace(path: str) -> str:
    """Write the timeline as Chrome-trace JSON; returns ``path``."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(to_chrome_trace(), f)
    return path


def summary() -> Dict[str, Dict[str, float]]:
    """Per-name aggregate over the recorded spans: count, total/avg/
    max milliseconds — the compact run report (``Profiler.summary``
    renders this)."""
    stats: Dict[str, Dict[str, float]] = {}
    for rec in list(_ring):
        if rec[0] != "X":
            continue
        _kind, name, _tid, _t0, dur_ns, _args = rec
        s = stats.setdefault(name, {"count": 0, "total": 0.0,
                                    "max": 0.0})
        ms = dur_ns / 1e6
        s["count"] += 1
        s["total"] += ms
        if ms > s["max"]:
            s["max"] = ms
    for s in stats.values():
        s["avg"] = s["total"] / s["count"]
    return stats
