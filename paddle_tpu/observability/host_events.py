"""What the host does beside the steps, counted where it happens:
everything jax builds for the program, and the collector's pauses
(DESIGN-OBSERVABILITY.md §Host events).

Both are installed once, by ``paddle_tpu.observability`` at import, and
are always on.  Neither costs a steady step anything: jax's events
fire only when jax traces, lowers, compiles or reads its cache, and
the collector's hook runs only when the collector does.

**What jax builds.**  ``jax.monitoring`` says every trace, lowering
and backend compile with the function's name (``fun_name``; a trace is
``step``, its lowering and compile ``jit(step)``), and the persistent
cache says its hits, misses and retrieval times without one: those
fire inside the backend compile of the function they belong to, on
its thread, so they wait in a thread-local until that compile's own
event names them.  Registry counters:

- ``jax_compile_seconds_total{phase, fun}``, phase ``trace``,
  ``lower``, ``backend_compile`` (the cache's read included, as jax
  times it) or ``cache_retrieval``;
- ``jax_compile_events_total{phase, fun}``, the same phases (a
  ``cache_retrieval`` is a hit) and ``cache_miss``.

``fun`` is the function's name for the programs a caller has
registered as its own (:func:`register_fun`: the runner's ``step``,
its folded entry, its ``predict_step``) and ``other`` for the rest, so
the label set is bounded.  A function traced inside another's trace
(a jitted helper) fires its own trace event under ``other``, and its
seconds lie inside the outer function's too.

With the span recorder armed the same events are laid into the ring
(``jax.trace:<name>``, ``jax.lower:<name>``,
``jax.backend_compile:<name>``, under the name jax gives), from the
event's own start and end, so an operator's ``PADDLE_TPU_TRACE=1``
export shows set-up too.  They are retroactive, so the ring only
(``trace.add_span``).

**The collector.**  One ``gc.callbacks`` hook: a collection's start
and stop become ``host_gc_pause_s{generation}`` and, through
``trace.span``, a span ``host.gc`` on the thread it ran on: in any
``jax.profiler`` trace, and in the ring when armed.  Two clock reads a
collection; the hook walks no object.  It runs inside whatever the
thread was doing, a registry call included, which is why the
registry's locks are reentrant.
"""

from __future__ import annotations

import gc
import re
import threading
import time
from typing import Optional

from . import trace
from .metrics import registry

__all__ = ["install", "register_fun", "OTHER", "argument_signature",
           "signature_change", "REASONS"]

OTHER = "other"
_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
# a hit is followed by its retrieval time, so the hits need no listener
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# "jit(step)", "pmap(step)": the name a lowering and a compile carry
_WRAPPED = re.compile(r"\w+\((.*)\)\Z")

_own_funs = set()
_pending = threading.local()      # .cache: [(phase, seconds or None)]
_installed = False
_gc_open: Optional[tuple] = None  # (perf_counter at start, the open span)


def register_fun(*names: str):
    """Count what jax builds for the functions called so under their
    own names and not under ``other``."""
    _own_funs.update(names)


def _plain(fun_name: str) -> str:
    m = _WRAPPED.match(fun_name)
    return m.group(1) if m else fun_name


def _count(phase: str, fun: str, seconds: Optional[float]):
    reg = registry()
    labels = {"phase": phase, "fun": fun}
    reg.counter("jax_compile_events_total",
                "traces, lowerings, backend compiles, persistent-cache "
                "retrievals and misses, by the function jax built for",
                labels=labels).inc()
    if seconds is not None:
        reg.counter("jax_compile_seconds_total",
                    "host seconds jax spent tracing, lowering, compiling "
                    "and reading its persistent cache, by function",
                    labels=labels).inc(seconds)


def _on_time_span(event: str, start: float, end: float, **kw):
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    name = _plain(str(kw.get("fun_name", "")))
    fun = name if name in _own_funs else OTHER
    _count(phase, fun, end - start)
    if phase == "backend_compile":
        # the cache spoke inside this compile, before it had a name
        waiting = _waiting()
        for cache_phase, seconds in waiting:
            _count(cache_phase, fun, seconds)
        waiting.clear()
    if trace.enabled():
        # jax's clock is time.time(); the ring's is monotonic
        to_ring = time.monotonic() - time.time()
        trace.add_span(f"jax.{phase}:{name}", start + to_ring,
                       end + to_ring)


def _on_duration(event: str, duration: float, **kw):
    if event == _CACHE_RETRIEVAL:
        _waiting().append(("cache_retrieval", float(duration)))


def _on_event(event: str, **kw):
    if event == _CACHE_MISS:
        _waiting().append(("cache_miss", None))


def _waiting() -> list:
    cache = getattr(_pending, "cache", None)
    if cache is None:
        cache = _pending.cache = []
    return cache


def _on_gc(phase: str, info: dict):
    global _gc_open
    if phase == "start":
        span = trace.span("host.gc", {"generation": info["generation"]})
        span.__enter__()
        _gc_open = (time.perf_counter(), span)
    elif _gc_open is not None:
        (t0, span), _gc_open = _gc_open, None
        seconds = time.perf_counter() - t0
        span.__exit__(None, None, None)
        registry().histogram(
            "host_gc_pause_s",
            "seconds one collection of the cyclic collector held its "
            "thread (and the interpreter lock)",
            labels={"generation": str(info["generation"])},
        ).observe(seconds)


# -- why a jitted function built another executable ------------------------
# what of an argument the C++ fast path of ``jax.jit`` keys an
# executable on, in the order a difference is reported under
REASONS = ("shape", "dtype", "weak_type", "sharding", "committed",
           "layout")


def argument_signature(args) -> dict:
    """``{path of the leaf: (shape, dtype, weak type, sharding,
    committed, layout)}`` of a call's arguments, in :data:`REASONS`'
    order.  Reads no value, so donated (deleted) arrays are fine; of
    those the layout is no longer known and reads None."""
    import jax
    import numpy as np
    leaves, _ = jax.tree_util.tree_flatten_with_path(args)
    return {
        jax.tree_util.keystr(path): (
            tuple(np.shape(x)), str(getattr(x, "dtype", type(x).__name__)),
            bool(getattr(x, "weak_type", False)),
            getattr(x, "sharding", None),
            bool(getattr(x, "committed", False)),
            getattr(getattr(x, "format", None), "layout", None))
        for path, x in leaves}


def signature_change(before: Optional[dict], now: dict, most: int = 4):
    """``(reason, the first few leaves that differ, as text)``.  The
    reason is ``first`` where there was no executable before, the first
    of :data:`REASONS` in which any leaf differs (a leaf that came or
    went counts under ``shape``; a layout no longer known differs from
    none), or ``unknown``: something outside the arguments, such as a
    context jax keys its executables on."""
    if before is None:
        return "first", []
    if before.keys() != now.keys():
        changed = sorted(before.keys() ^ now.keys())
        return "shape", [f"{k}: {'gone' if k in before else 'new'}"
                         for k in changed[:most]]
    for i, reason in enumerate(REASONS):
        differing = [
            f"{k}: {_brief(before[k][i])} -> {_brief(now[k][i])}"
            for k in now
            if before[k][i] != now[k][i] and not (
                reason == "layout" and None in (before[k][i], now[k][i]))]
        if differing:
            more = len(differing) - most
            return reason, differing[:most] + (
                [f"and {more} more"] if more > 0 else [])
    return "unknown", []


def _brief(value) -> str:
    """A sharding over a mesh by its axes' sizes, spec and memory kind:
    the repr of a ``NamedSharding`` spells the mesh out at length."""
    mesh = getattr(value, "mesh", None)
    if mesh is None:
        return str(value)
    return (f"{type(value).__name__}({dict(mesh.shape)}, "
            f"{getattr(value, 'spec', None)}, "
            f"{getattr(value, 'memory_kind', None)})")


def install():
    """Register the listeners and the hook, once a process."""
    global _installed
    if _installed:
        return
    _installed = True
    import jax.monitoring as monitoring
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)
