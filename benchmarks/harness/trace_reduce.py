"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a trace of
this installation (jax 0.9.0, libtpu 0.0.34) holds, as seen by hand in
the recorded ones under ``benchmarks/testdata``:

- one plane a device, ``/device:TPU:<n>``.  Its line ``XLA Ops`` has one
  event for every instruction the core ran, one after the other, whose
  name is the instruction's whole text in the compiled program
  (``%fusion.23 = bf16[...]{...} fusion(...), kind=kOutput, ...``).  Its
  line ``Async XLA Ops`` has one event for every asynchronous operation
  from its start to its done (copies and slices that prefetch, and
  asynchronous collectives), which overlap the instructions.  ``XLA
  Modules`` has one event for every program run;
- a host plane ``/host:CPU`` whose line ``python`` holds the harness's
  own spans (``bench.dispatch`` and so on, written by
  ``harness/spans.py``), on the same clock as the device's events.

The traced window is the stretch from the first harness span's start to
the last one's end: the harness starts the profiler right before a group
of steps and stops it right after the group's sync.  Everything below is
inside that window:

- an instruction is a *kernel* when it is a Mosaic custom call
  (``custom_call_target="tpu_custom_call"`` in its text), a *collective*
  when its name or operation says so (all-reduce, all-gather,
  reduce-scatter, all-to-all, collective-permute, and the TPU's
  ``async-collective-start`` / ``-done`` fusions), and *other* otherwise
  (fusions, matrix multiplications, copies);
- a device is busy wherever an instruction of ``XLA Ops`` runs (the union
  of the intervals); asynchronous copies alone do not make it busy;
- a device is in a collective wherever a collective instruction runs or
  an asynchronous collective is between its start and its done, and the
  collective is exposed wherever no kernel or other instruction runs at
  the same time;
- an idle gap is a stretch of the window in which a device runs no
  instruction; its seconds go to the harness spans the host was in
  meanwhile, and to ``between_spans`` where it was in none.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective")
# `%name = <result type> operation(operands), attributes`; a result type
# holds no lower-case word followed by a bracket, an operation is one
INSTRUCTION = re.compile(
    r"%?(?P<name>[^\s=]+) = (?P<type>.*?)(?<=[\s)])(?P<op>[a-z][a-z0-9-]*)\(")
KINDS = ("kernel", "collective", "other")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The same stretches with overlaps merged, in order."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """What of the merged stretches ``a`` the merged stretches ``b`` leave
    uncovered."""
    out = []
    j = 0
    for start, end in a:
        at = start
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def parse_instruction(text: str) -> Tuple[str, str, str]:
    """(name, operation, result type without layouts) of an event's name;
    a name that is no instruction text is its own name and operation."""
    m = INSTRUCTION.match(text)
    if not m:
        return text.lstrip("%"), text.lstrip("%"), ""
    return (m.group("name"), m.group("op"),
            re.sub(r"\{[^{}]*\}", "", m.group("type")).strip())


def op_kind(text: str, name: str, operation: str) -> str:
    if MOSAIC_CALL in text:
        return "kernel"
    if COLLECTIVE.search(name) or COLLECTIVE.search(operation):
        return "collective"
    return "other"


def op_group(text: str, name: str, operation: str, result: str) -> str:
    """What instructions of one sort share: the name without its number,
    the operation with a fusion's kind, and the shape of the result, so
    that the same instruction of every layer falls together."""
    stem = re.sub(r"[.\d]+\Z", "", name) or name
    kind = re.search(r"kind=k(\w+)", text)
    what = operation + (":" + kind.group(1) if kind else "")
    label = what if stem == operation else f"{stem} {what}"
    return f"{label} -> {result}"[:120]


@dataclass
class Op:
    group: str       # op_group() of the instruction
    kind: str        # one of KINDS
    on_core: bool    # a line of XLA Ops; False: an asynchronous span
    start_s: float
    end_s: float


@dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Op]

    def stretches(self, *kinds: str, with_async: bool = False
                  ) -> List[Interval]:
        """Merged intervals of the core's instructions of these kinds;
        ``with_async`` takes the asynchronous spans in as well."""
        return union((o.start_s, o.end_s) for o in self.ops
                     if o.kind in kinds and (o.on_core or with_async))


@dataclass
class TraceSummary:
    window: Interval
    spans: List[Tuple[str, float, float]]   # harness spans, profiler clock
    devices: List[DeviceTrace]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def steps(self) -> int:
        return sum(1 for name, _, _ in self.spans if name == "dispatch")

    def _mean(self, of_device) -> float:
        return sum(of_device(d) for d in self.devices) / len(self.devices)

    def busy_seconds(self) -> List[float]:
        return [total(d.stretches(*KINDS)) for d in self.devices]

    @property
    def busy_s(self) -> float:
        """Seconds in which an instruction ran, averaged over the devices."""
        return self._mean(lambda d: total(d.stretches(*KINDS)))

    def idle_share(self) -> float:
        """Of the idlest device."""
        return 1.0 - min(self.busy_seconds()) / self.window_s

    def kind_seconds(self, kind: str) -> float:
        """Seconds in which an instruction of the kind ran (for a
        collective: or was between its start and its done), averaged over
        the devices."""
        return self._mean(lambda d: total(d.stretches(kind, with_async=True)))

    def kind_count(self, kind: str) -> float:
        """Instructions of the kind run, averaged over the devices."""
        return self._mean(lambda d: sum(
            1 for o in d.ops if o.kind == kind and o.on_core))

    def exposed_collective_seconds(self) -> float:
        """Averaged over the devices."""
        return self._mean(lambda d: total(subtract(
            d.stretches("collective", with_async=True),
            d.stretches("kernel", "other"))))

    def top_ops(self, n: int) -> List[list]:
        """The instruction groups that took most of the core's time, in
        seconds a step, averaged over the devices."""
        by_group: Dict[str, float] = {}
        for d in self.devices:
            for o in d.ops:
                if o.on_core:
                    key = o.kind + ": " + o.group
                    by_group[key] = (by_group.get(key, 0.0)
                                     + o.end_s - o.start_s)
        scale = len(self.devices) * max(self.steps, 1)
        ranked = sorted(by_group.items(), key=lambda kv: -kv[1])
        return [[k, v / scale] for k, v in ranked[:n]]

    def top_idle_gaps(self, n: int) -> List[list]:
        """Idle seconds of the idlest device over the window, by the
        harness span the host was in meanwhile."""
        busy = self.busy_seconds()
        device = self.devices[busy.index(min(busy))]
        gaps = subtract([self.window], device.stretches(*KINDS))
        idle = total(gaps)
        by_span: Dict[str, float] = {}
        for name, start, end in self.spans:
            inside = idle - total(subtract(gaps, [(start, end)]))
            if inside > 0.0:
                by_span[name] = by_span.get(name, 0.0) + inside
        outside = total(subtract(gaps, union(
            (s, e) for _, s, e in self.spans)))
        if outside > 0.0:
            by_span["between_spans"] = outside
        ranked = sorted(by_span.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(xplane_path: str, chips: Optional[int] = None
           ) -> Optional[TraceSummary]:
    """The summary of one ``.xplane.pb`` file; see :func:`summarize`."""
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(xplane_path), chips)


def summarize(data, chips: Optional[int] = None) -> Optional[TraceSummary]:
    """The summary of a ``ProfileData``, or None where it holds no harness
    span or no device instruction (a trace taken on a CPU has no device
    plane).  ``chips`` keeps the first so many device planes."""
    spans: List[Tuple[str, float, float]] = []
    planes = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):],
                                  ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    if not spans or not planes:
        return None
    window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    parsed: Dict[str, Tuple[str, str]] = {}     # text -> (group, kind)
    devices = []
    for ordinal, plane in sorted(planes, key=lambda p: p[0])[:chips]:
        ops = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            on_core = line.name == OPS_LINE
            for ev in line.events:
                start = max(ev.start_ns * 1e-9, window[0])
                end = min((ev.start_ns + ev.duration_ns) * 1e-9, window[1])
                if end <= start:
                    continue
                if ev.name not in parsed:
                    name, operation, result = parse_instruction(ev.name)
                    parsed[ev.name] = (
                        op_group(ev.name, name, operation, result),
                        op_kind(ev.name, name, operation))
                group, kind = parsed[ev.name]
                # of the asynchronous spans only collectives count: a
                # prefetching copy is not work the core waits for
                if on_core or kind == "collective":
                    ops.append(Op(group, kind, on_core, start, end))
        devices.append(DeviceTrace(ordinal, ops))
    if not any(o.on_core for d in devices for o in d.ops):
        return None
    return TraceSummary(window, sorted(spans, key=lambda s: s[1]), devices)
