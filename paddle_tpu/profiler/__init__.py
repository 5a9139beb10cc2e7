"""paddle.profiler facade (parity: python/paddle/profiler/ —
SURVEY.md §5.1), re-backed onto the unified observability recorder
(DESIGN-OBSERVABILITY.md).

Device side: jax.profiler → XPlane/TensorBoard (replacing CUPTI).
Host side: ``Profiler`` start/stop arm :mod:`paddle_tpu.observability
.trace` — the SAME ring buffer the dispatch engine, fit loop, mesh
runner, serving engine and checkpoint IO record into — so a profiled
run exports ONE timeline carrying both user ``RecordEvent``
annotations and the framework's own spans.  ``export_chrome_tracing``
dumps that unified timeline.  ``RecordEvent`` additionally feeds the
native C++ tracer (paddle_tpu/native/src/host_tracer.cc) when it is
armed, keeping the pre-existing native export path alive."""

from __future__ import annotations

import contextlib
import enum
import os
import time
from typing import Callable, Iterable, Optional

import jax

from ..native import host_tracer as _host_tracer
from ..observability import trace as _obs_trace


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable:
    total = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler writing the UNIFIED chrome trace — the
    observability recorder's timeline, which carries the profiled
    run's ``RecordEvent`` annotations alongside the framework's own
    dispatch/fit/serving/checkpoint spans on one clock."""
    def handler(prof):
        prof._log_dir = dir_name
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        _obs_trace.dump_chrome_trace(
            os.path.join(dir_name, f"{name}.json"))
    return handler


class Profiler:
    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, timer_only: bool = False,
                 record_shapes: bool = False, profile_memory=False,
                 with_flops: bool = False):
        self._timer_only = timer_only
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._log_dir = os.environ.get("PADDLE_PROFILER_LOGDIR",
                                       "./profiler_log")
        self._step = 0
        self._active = False
        self._step_times = []
        self._last_ts = None
        self._armed_recorder = False

    def start(self):
        if not self._timer_only:
            # delegate the host timeline to the unified recorder: the
            # profiled window records into the SAME ring as the
            # framework's own instrumentation (one timeline, ISSUE 8).
            # Remember whether WE armed it so stop() doesn't disable a
            # recorder the user armed via PADDLE_TPU_TRACE.
            self._armed_recorder = not _obs_trace.enabled()
            if self._armed_recorder:
                # fresh window when WE arm: back-to-back profiler
                # sessions must not leak spans into each other's
                # export (parity with the native tracer, which
                # cleared its buffer on every enable)
                _obs_trace.clear()
            _obs_trace.enable()
            _host_tracer.enable()
            try:
                jax.profiler.start_trace(self._log_dir)
                self._active = True
            except Exception:
                self._active = False
        self._last_ts = time.perf_counter()

    def stop(self):
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        _host_tracer.disable()
        if self._armed_recorder:
            # stop recording but KEEP the ring: export and summary()
            # read the profiled window after stop()
            _obs_trace.disable()
            self._armed_recorder = False

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_ts is not None:
            self._step_times.append(now - self._last_ts)
        if _obs_trace.enabled():
            _obs_trace.instant("profiler.step",
                               args={"step": self._step})
        self._last_ts = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        avg = sum(self._step_times) / len(self._step_times)
        return f"avg step time {avg * 1000:.2f} ms"

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Print step timing + host-span table (upstream: op/kernel
        summary tables) aggregated from the unified recorder's
        timeline, merged with any spans the native tracer still
        holds."""
        print(self.step_info())
        stats = dict(_obs_trace.summary())
        for name, s in host_span_stats().items():
            if name not in stats:
                stats[name] = s
        if not stats:
            return
        name_w = max(len(n) for n in stats) + 2
        print(f"{'Name':<{name_w}}{'Calls':>8}{'Total(ms)':>12}"
              f"{'Avg(ms)':>10}{'Max(ms)':>10}{'Ratio%':>8}")
        total_all = sum(s['total'] for s in stats.values()) or 1.0
        order = sorted(stats.items(), key=lambda kv: -kv[1]["total"])
        for name, s in order:
            print(f"{name:<{name_w}}{s['count']:>8}"
                  f"{s['total']:>12.3f}{s['avg']:>10.3f}"
                  f"{s['max']:>10.3f}"
                  f"{100.0 * s['total'] / total_all:>8.1f}")

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def host_span_stats():
    """Aggregate the native tracer's span buffer into per-name stats
    (count/total/avg/max in ms)."""
    import json
    import tempfile
    if _host_tracer.count() == 0:
        return {}
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    try:
        if not _host_tracer.dump(path):
            return {}
        with open(path) as fh:
            events = json.load(fh)["traceEvents"]
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass
    stats = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        s = stats.setdefault(e["name"],
                             {"count": 0, "total": 0.0, "max": 0.0})
        dur_ms = e["dur"] / 1000.0
        s["count"] += 1
        s["total"] += dur_ms
        s["max"] = max(s["max"], dur_ms)
    for s in stats.values():
        s["avg"] = s["total"] / s["count"]
    return stats


class RecordEvent:
    """Host-side trace annotation: one span of the unified
    observability recorder, which is a ``jax.profiler`` annotation
    always (XPlane correlation) and a record of the ONE timeline when
    armed (``observability/trace.py``), and the native host tracer
    when enabled."""

    def __init__(self, name: str, event_type=None):
        self._name = name
        self._native = False
        self._uspan = None

    def begin(self):
        # begin() twice without end() would overwrite (and leak) the
        # previous span — close it first
        if self._uspan is not None:
            self.end()
        self._uspan = _obs_trace.span(self._name)
        self._uspan.__enter__()
        if _host_tracer.enabled():
            _host_tracer.begin(self._name)
            self._native = True

    def end(self):
        if self._native:
            _host_tracer.end()
            self._native = False
        if self._uspan is not None:
            self._uspan.__exit__(None, None, None)
            self._uspan = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def load_profiler_result(path):
    raise NotImplementedError("load_profiler_result: use TensorBoard on "
                              "the XPlane trace directory")
