"""The host half of a traced window, from inside the program.

``trace_reduce.py`` lays a device's idle gaps to the harness's own spans
around its calls (``bench.dispatch``, ``bench.sync``): the host half
from outside.  Since PR 36 every span of the program is a
``jax.profiler.TraceAnnotation`` too (``observability/trace.py``), so
the trace of a run holds, on the thread that dispatches and on the
device's clock:

- ``mesh.dispatch`` around one ``DistributedRunner.train_step``, with
  the stat ``step``, and inside it, once each and in this order,
  ``mesh.stage`` (the batch's transfer), ``mesh.scalars`` (``lr`` and
  the step counter made device scalars), ``mesh.val_cache``,
  ``mesh.launch`` (the call of the jitted step and nothing else) and
  ``mesh.commit`` (rebinding what it returned, the resilience hooks);
- ``host.gc`` around every collection of the cyclic collector, with
  the stat ``generation``, on whichever thread it ran.

This module reads those from the host planes of the run's
``.xplane.pb`` (``jax.profiler.ProfileData`` only), inside the window
that the ``bench.*`` spans mark, and gives:

1. *A step's phases*: of every name the median, the mean and the
   longest over the window's steps; ``own`` is ``mesh.dispatch`` less
   ``mesh.launch``, what the runner's Python takes beside jax's launch.
2. *The idlest device's idle seconds by the phase the host was in*:
   the gaps are ``trace_reduce``'s (the window less the union of ``XLA
   Ops``); each stretch of a gap goes to the innermost of the program's
   spans open on the dispatching thread meanwhile, ``mesh.dispatch``
   for its own lines between the phases, and ``outside the program``
   for the rest.  One partition: it sums to the harness's ``idle_gaps``.
3. *The device programs a step*: the runs on one device's ``XLA
   Modules`` that a ``mesh.dispatch`` launched, by name.  The device
   runs a program long after the host launched it, so the two are
   joined by order: the trace starts and ends on a drained device, the
   runtime writes one ``PJRT_LoadedExecutable_Execute linkage`` event
   on the launching thread for every launch, and a device runs its
   programs in the order they were launched.  Where the counts differ
   the join is not made, every run inside the window counts, and the
   table says so.

A trace taken on a CPU has the host plane and no device: (1) and the
collector's pauses are read, (2) and (3) are not.  A program from
before PR 36 has none of the spans: the reader says so in one line and
returns None.  It never raises: whatever goes wrong in here, the
existing metrics are read as before.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from . import trace_reduce

DISPATCH = "mesh.dispatch"
LAUNCH = "mesh.launch"
PHASES = ("mesh.stage", "mesh.scalars", "mesh.val_cache", LAUNCH,
          "mesh.commit")
OWN = "own"                      # mesh.dispatch less mesh.launch
GC = "host.gc"
OUTSIDE = "outside the program"
HARNESS_DISPATCH = trace_reduce.SPAN_PREFIX + "dispatch"
LAUNCHED = "PJRT_LoadedExecutable_Execute linkage"
MODULES_LINE = "XLA Modules"
SUM_TOLERANCE = 0.02             # idle by phase against the harness's

Interval = trace_reduce.Interval
Span = Tuple[float, float, str]   # start, end, name: seconds


@dataclass
class HostHalf:
    window: Interval
    thread: str                       # the line that dispatches
    # one entry a mesh.dispatch span of the window, in order: seconds
    # by name (DISPATCH, the PHASES found inside it, OWN, and
    # HARNESS_DISPATCH where a bench.dispatch span holds it)
    steps: List[Dict[str, float]]
    step_ids: List[Optional[int]]     # the spans' ``step`` stat
    gc: List[Tuple[float, int]]       # (seconds, generation), any thread
    # idle seconds of the idlest device over the window by the innermost
    # program span the dispatching thread was in; None: no device
    idle: Optional[Dict[str, float]] = None
    idle_device: Optional[int] = None
    # of it, inside a mesh.dispatch and outside its mesh.launch
    idle_own_s: float = 0.0
    # runs a step on one device's XLA Modules that a mesh.dispatch
    # launched, by the program's name without its hash; None: no device
    programs: Optional[Dict[str, float]] = None
    programs_outside: Dict[str, float] = field(default_factory=dict)
    joined: bool = False              # False: every run of the window

    # -- what the metric files read ------------------------------------
    def seconds(self, name: str) -> List[float]:
        return [s[name] for s in self.steps if name in s]

    def median_ms(self, name: str) -> Optional[float]:
        values = self.seconds(name)
        return 1e3 * statistics.median(values) if values else None

    def idle_own_ms_per_step(self) -> Optional[float]:
        """Idle while the host was inside ``mesh.dispatch`` and outside
        ``mesh.launch``: what the runner's Python could give back."""
        if self.idle is None:
            return None
        return 1e3 * self.idle_own_s / len(self.steps)

    def programs_per_step(self) -> Optional[float]:
        return None if self.programs is None else sum(
            self.programs.values())

    def gc_ms_per_step(self) -> float:
        return 1e3 * sum(s for s, _ in self.gc) / len(self.steps)

    # -- the table -----------------------------------------------------
    def lines(self, harness_idle_s: Optional[float] = None) -> List[str]:
        out = [f"the host half from inside: {len(self.steps)} steps "
               f"({_ids(self.step_ids)}) on thread {self.thread!r}, ms a "
               "step: median, mean, longest"]
        for name in (DISPATCH, *PHASES, OWN, HARNESS_DISPATCH):
            values = self.seconds(name)
            if values:
                out.append(
                    f"  {name}: {1e3 * statistics.median(values):.3f}, "
                    f"{1e3 * statistics.fmean(values):.3f}, "
                    f"{1e3 * max(values):.3f}")
        if self.gc:
            longest = max(self.gc)
            out.append(
                f"  {GC}: {len(self.gc)} collections, "
                f"{1e3 * sum(s for s, _ in self.gc):.3f} ms in the window, "
                f"the longest {1e3 * longest[0]:.3f} ms (generation "
                f"{longest[1]})")
        else:
            out.append(f"  {GC}: no collection in the window")
        if self.idle is not None:
            total = sum(self.idle.values())
            against = "" if harness_idle_s is None else (
                f" (the harness's idle_gaps: {1e3 * harness_idle_s:.3f})")
            out.append(
                f"  idle of device {self.idle_device} by the phase the "
                f"host was in, ms in the window: " + ", ".join(
                    f"{k} {1e3 * v:.3f}" for k, v in sorted(
                        self.idle.items(), key=lambda kv: -kv[1]))
                + f"; in all {1e3 * total:.3f}" + against)
        if self.programs is not None:
            how = ("launched under mesh.dispatch" if self.joined else
                   "inside the window (launches and runs differ in "
                   "number: not joined)")
            out.append(
                "  device programs a step, " + how + ": " + (", ".join(
                    f"{k} {v:g}" for k, v in sorted(
                        self.programs.items(), key=lambda kv: -kv[1]))
                    or "none")
                + ("; launched outside it: " + ", ".join(
                    f"{k} {v:g}" for k, v in self.programs_outside.items())
                   if self.programs_outside else ""))
        return out


def _ids(ids: List[Optional[int]]) -> str:
    known = [i for i in ids if i is not None]
    return f"step {min(known)} to {max(known)}" if known else "no step ids"


def innermost(spans: List[Span]) -> List[Span]:
    """The stretches in which each span is the innermost one open:
    nested spans of one thread cut into one partition of their union."""
    out: List[Span] = []
    open_: List[Tuple[float, str]] = []      # (end, name), outermost first
    at = 0.0                                 # cut up to here

    def cut(until: float):
        nonlocal at
        if open_ and until > at:
            out.append((at, until, open_[-1][1]))
        at = max(at, until)

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while open_ and open_[-1][0] <= start:
            cut(open_[-1][0])
            open_.pop()
        cut(start)
        open_.append((end, name))
    while open_:
        cut(open_[-1][0])
        open_.pop()
    return out


def summarize(data, chips: Optional[int] = None, device_trace=None,
              say: Callable[[str], None] = print) -> Optional[HostHalf]:
    """The host half of a ``ProfileData``, or None with one line said.
    ``device_trace``: the run's ``trace_reduce.TraceSummary`` where one
    is at hand, else it is made here (None on a CPU's trace)."""
    try:
        return _summarize(data, chips, device_trace, say)
    except Exception as e:
        return _refused(say, e)


def _refused(say, e: Exception) -> None:
    at = traceback.extract_tb(e.__traceback__)[-1]
    say(f"no host half from inside: {type(e).__name__}: {e} "
        f"({os.path.basename(at.filename)}:{at.lineno})")


def _summarize(data, chips, device_trace, say) -> Optional[HostHalf]:
    wanted = {DISPATCH, *PHASES, GC, LAUNCHED}
    # (plane, line) -> events as (start, end, name, stats)
    by_thread: Dict[Tuple[str, str],
                    List[Tuple[float, float, str, dict]]] = {}
    bench: List[Interval] = []
    modules: Dict[int, List[Tuple[float, str]]] = {}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == MODULES_LINE:
                    modules[int(m.group(1))] = sorted(
                        (ev.start_ns * 1e-9, ev.name.partition("(")[0])
                        for ev in line.events)
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if ev.name.startswith(trace_reduce.SPAN_PREFIX):
                    bench.append((start, end))
                    if ev.name != HARNESS_DISPATCH:
                        continue
                elif ev.name not in wanted:
                    continue
                by_thread.setdefault((plane.name, line.name), []).append(
                    (start, end, ev.name,
                     dict(ev.stats) if ev.name in (DISPATCH, GC) else {}))
    if not bench:
        say("no host half from inside: the trace holds no span of the "
            "harness to mark the window")
        return None
    window = (min(s for s, _ in bench), max(e for _, e in bench))

    def inside(ev) -> bool:
        return ev[0] >= window[0] and ev[1] <= window[1]

    threads = {t: [ev for ev in evs if inside(ev)]
               for t, evs in by_thread.items()}
    dispatching = [t for t, evs in threads.items()
                   if any(ev[2] == DISPATCH for ev in evs)]
    if not dispatching:
        say(f"no host half from inside: the trace holds no {DISPATCH} "
            "span (a program from before its spans were the profiler's "
            "too)")
        return None
    thread = max(dispatching, key=lambda t: len(threads[t]))
    events = sorted(threads[thread], key=lambda ev: (ev[0], -ev[1]))
    dispatches = [ev for ev in events if ev[2] == DISPATCH]
    steps, ids = [], []
    for start, end, _, stats in dispatches:
        step = {DISPATCH: end - start}
        for s, e, name, _ in events:
            if s >= start and e <= end:
                if name in PHASES:
                    step[name] = step.get(name, 0.0) + e - s
            elif name == HARNESS_DISPATCH and s <= start and e >= end:
                step[HARNESS_DISPATCH] = e - s
        step[OWN] = step[DISPATCH] - step.get(LAUNCH, 0.0)
        steps.append(step)
        ids.append(int(stats["step"]) if "step" in stats else None)
    half = HostHalf(
        window=window, thread=thread[1], steps=steps,
        step_ids=ids,
        gc=[(e - s, int(stats.get("generation", -1)))
            for evs in threads.values() for s, e, name, stats in evs
            if name == GC])

    if device_trace is None:
        device_trace = trace_reduce.summarize(data, chips)
    if device_trace is None:          # a CPU: the host plane alone
        return half
    busy = device_trace.busy_seconds()
    device = device_trace.devices[busy.index(min(busy))]
    gaps = trace_reduce.subtract(
        [device_trace.window], device.stretches(*trace_reduce.KINDS))
    idle = trace_reduce.total(gaps)
    by_name: Dict[str, List[Interval]] = {}
    for s, e, name in innermost([
            ev[:3] for ev in events if ev[2] in (DISPATCH, GC, *PHASES)]):
        by_name.setdefault(name, []).append((s, e))
    half.idle = {}
    for name, stretches in by_name.items():
        seconds = idle - trace_reduce.total(trace_reduce.subtract(
            gaps, trace_reduce.union(stretches)))
        if seconds > 0.0:
            half.idle[name] = seconds
    half.idle[OUTSIDE] = max(0.0, idle - sum(half.idle.values()))
    half.idle_device = device.ordinal
    own = trace_reduce.subtract(
        trace_reduce.union((s, e) for s, e, _, _ in dispatches),
        trace_reduce.union(ev[:2] for ev in events if ev[2] == LAUNCH))
    half.idle_own_s = idle - trace_reduce.total(
        trace_reduce.subtract(gaps, own))

    # the device that ran the most: a program of one device runs on the
    # first, a program of the mesh on all
    ordinal = max(modules, key=lambda k: (len(modules[k]), -k), default=None)
    runs = [(s, name) for s, name in modules.get(ordinal, ())
            if window[0] <= s <= window[1]]
    launches = [ev[0] for ev in events if ev[2] == LAUNCHED]
    half.joined = bool(runs) and len(launches) == len(runs)
    per_step = 1.0 / len(steps)
    half.programs = {}
    for i, (_, name) in enumerate(runs):
        under = not half.joined or any(
            s <= launches[i] <= e for s, e, _, _ in dispatches)
        counts = half.programs if under else half.programs_outside
        counts[name] = counts.get(name, 0.0) + per_step
    return half


def read(xplane_path: str, chips: Optional[int] = None, device_trace=None,
         say: Callable[[str], None] = print) -> Optional[HostHalf]:
    """:func:`summarize` of one ``.xplane.pb`` file."""
    try:
        from jax.profiler import ProfileData
        return _summarize(ProfileData.from_file(xplane_path), chips,
                          device_trace, say)
    except Exception as e:
        return _refused(say, e)


def of_run(obs: dict, metric_file: str) -> Optional[HostHalf]:
    """The host half of the run that ``obs`` is of, or None where it
    took no trace or its program has no such span.  The first metric
    file to ask reads the run's trace (the newest under the
    ``.bench_traces`` of the checkout the file lies in, and no older
    than the run's window: the driver hands no path over, and on a CPU
    ``obs["trace"]`` is None though a trace was taken), says the table,
    and leaves it under ``obs["program_spans"]`` for the others."""
    if "program_spans" in obs:
        return obs["program_spans"]
    obs["program_spans"] = None
    if "window" not in obs:           # of no run
        return None
    say = functools.partial(print, flush=True)
    try:
        # the window's start on the files' clock, a second of slack
        started = time.time() - (
            time.perf_counter() - obs["window"]["start_s"]) - 1.0
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(metric_file))))
        traces = [p for p in glob.glob(os.path.join(
            root, ".bench_traces", "*", "plugins", "profile", "*",
            "*.xplane.pb")) if os.path.getmtime(p) >= started]
        if not traces:
            return None
        t = time.perf_counter()
        device_trace = obs.get("trace")
        half = obs["program_spans"] = read(
            max(traces, key=os.path.getmtime), obs.get("chips"),
            device_trace, say)
        if half is not None:
            harness_idle = None if device_trace is None else sum(
                v for _, v in device_trace.top_idle_gaps(1 << 30))
            for line in half.lines(harness_idle):
                say(line)
            if half.idle is not None and abs(
                    sum(half.idle.values()) - harness_idle
                    ) > SUM_TOLERANCE * max(harness_idle, 1e-9):
                say("  the idle by phase does not sum to the harness's "
                    "idle_gaps: not reported")
                half.idle = None
        say(f"  (reading the trace's host planes took "
            f"{time.perf_counter() - t:.1f} s)")
        return half
    except Exception as e:
        return _refused(say, e)


def metric(obs: dict, metric_file: str,
           of: Callable[[HostHalf], Optional[float]]) -> Optional[float]:
    """What a file of ``benchmarks/layer_metrics`` returns."""
    half = of_run(obs, metric_file)
    return None if half is None else of(half)
