"""From a profiler trace to device time by block of the model.

``trace_reduce.py`` tells instructions apart from outside: Mosaic
kernel, collective, other.  This module names them from inside.  The
program opens six ``jax.named_scope``s (``embed``, ``attn``, ``mlp``,
``head``, ``loss`` in ``models/gpt.py``, ``optimizer`` in
``distributed/runner.py``), which reach the compiled program as every
instruction's ``metadata={op_name="jit(step)/transpose(jvp(attn))/..."}``
(jax writes the forward pass as ``jvp(<scope>)`` and the backward pass
as ``transpose(jvp(<scope>))`` itself).

Where the names are read from, as seen by hand in the traces recorded
on the chip under ``benchmarks/testdata`` (jax 0.9.0, libtpu 0.0.34): the
trace itself.  ``jax.profiler.ProfileData`` shows an event's name and
times and nothing of that metadata, and the name is the instruction's
text without it.  But the plane ``/host:metadata`` of an ``.xplane.pb``
holds, for every program that ran, an event metadata entry called like
the program's runs on ``XLA Modules`` (``jit_step(<hash>)``) with one
stat, ``Hlo Proto``: the optimized module of the executable that ran,
fused computations and every ``op_name`` included.  ``ProfileData`` does
not show that plane's contents, so those four nested fields are read
from the protobuf wire format directly (:func:`wire_fields`; field
numbers from ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``) and jaxlib's own ``HloModule`` turns the
module into the text ``compiled.as_text()`` gives.  The driver hands
nothing over and keeps nothing for this; the join is to the executable
that ran, not to one compiled beside it.

Rules:

1. *Join.*  Only ``XLA Ops`` events that start inside an ``XLA Modules``
   event of the step's program count (the runner's jitted step:
   ``jit_step(<hash>)``; of two with that name, the one with more runs).
   An event is matched to the text's instruction of the same name *and*
   the same operation and result type, all three read from the event's
   name and from the text's line by ``trace_reduce.parse_instruction``.
   A name that matches with another operation or type is *not found*: a
   text that is not the executable's (two executables of one step are
   numbered apart) must not be read as if it were.  One spelling is
   bridged: the text writes an asynchronous operation by its short name
   (``slice-start``) where the trace has the general one
   (``async-start``).
2. *Blocks of an instruction.*  The set of the six scopes found in the
   ``op_name`` of the instruction itself and, where it ``calls=`` a
   computation (a fusion, an asynchronous wrapper), of every instruction
   of that computation, and so on down.  One block: the time goes there.
   Several: to a row named by the members in ``SCOPES``' order
   (``mlp+optimizer``, ``head+loss``), never split by guess.  None:
   ``unscoped``.  A member with no scope does not make a row mixed (a
   residual add fused into ``mlp`` is ``mlp``'s).  A ``constant`` or an
   ``iota`` member lends no scope: it computes nothing from the step's
   data, and XLA keeps one copy of equal ones under the ``op_name`` of
   the first, so the zero that every reduction starts from would carry
   the embedding's name into each bias gradient.
3. *One partition.*  Kernels, collectives and other instructions alike.
   Where events nest, the time goes to the innermost.  The rows, *not
   found* among them, sum to the busy time of the step's program (the
   union of its events' intervals); a table off by more than 1 % is no
   table.  Every row says how much of it is backward (an instruction
   that holds a ``transpose(jvp(`` anywhere: a forward value recomputed
   inside a backward fusion is the backward pass's cost) and its three
   longest instruction groups under ``trace_reduce.op_group`` names, so
   that a mixed row can be read.
4. *Refusal.*  Where more than 5 % of the program's busy time is not
   found, or no instruction of the text carries any of the six scopes
   (a program from before them, or an executable that the persistent
   compile cache kept from then: metadata is no part of the cache's
   key), the reader says so in one line and returns None.  It never
   raises: whatever goes wrong in here, the existing metrics are read
   as before.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import time
import traceback
from dataclasses import dataclass, field
from typing import (Callable, Dict, FrozenSet, Iterator, List, Optional,
                    Tuple)

from . import trace_reduce

SCOPES = ("embed", "attn", "mlp", "head", "loss", "optimizer")
UNSCOPED = "unscoped"
NOT_FOUND = "not found"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "jit_step"      # DistributedRunner's jitted step
PROGRAMS_PLANE = b"/host:metadata"
MOST_NOT_FOUND = 0.05      # of the program's busy time
SUM_TOLERANCE = 0.01       # rows against the program's busy time

# a scope stands between "/" and "/" or inside jvp( ), transpose(jvp( ))
SCOPE = re.compile(r"(?:^|[/(])(%s)(?=[/)]|\Z)" % "|".join(SCOPES))
BACKWARD = "transpose(jvp("
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
MODULE = re.compile(r"HloModule ([^\s,]+)")
# "%name (parameters) -> result {" opens a computation, "}" closes it
COMPUTATION = re.compile(r"(?:ENTRY )?%?([^\s(]+) \(.*\) -> .*\{\Z")
LINE = re.compile(r"\s+(?:ROOT )?(%?([^\s=]+) = .*)\Z")
NO_SCOPE_OF_ITS_OWN = re.compile(r"[\s)](?:constant|iota)\(")

Blocks = FrozenSet[str]


def row_name(blocks: Blocks) -> str:
    return "+".join(s for s in SCOPES if s in blocks) or UNSCOPED


@dataclass
class Instruction:
    head: str              # the line without "ROOT" and without metadata
    scopes: Blocks         # of its own op_name
    backward: bool
    calls: Tuple[str, ...]  # the computations it calls
    lends: bool            # False: a constant or an iota


class StepText:
    """The text of the step's program, read once: every instruction of every
    computation by name, and the members of every computation."""

    def __init__(self, text: str):
        m = MODULE.match(text)
        self.module = m.group(1) if m else ""
        self.instructions: Dict[str, Instruction] = {}
        self.members: Dict[str, List[str]] = {}
        self._resolved: Dict[str, Tuple[Blocks, bool]] = {}
        inside: Optional[List[str]] = None
        for line in text.splitlines():
            if inside is None:
                m = COMPUTATION.match(line)
                if m:
                    inside = self.members.setdefault(m.group(1), [])
                continue
            if line.startswith("}"):
                inside = None
                continue
            m = LINE.match(line)
            if not m:
                continue
            head, _, metadata = m.group(1).partition(", metadata={")
            op_name = OP_NAME.search(metadata)
            op_name = op_name.group(1) if op_name else ""
            self.instructions[m.group(2)] = Instruction(
                head, frozenset(SCOPE.findall(op_name)),
                BACKWARD in op_name, tuple(CALLS.findall(head)),
                not NO_SCOPE_OF_ITS_OWN.search(head))
            inside.append(m.group(2))

    @property
    def scoped(self) -> bool:
        return any(i.scopes for i in self.instructions.values())

    def signature(self, name: str) -> Tuple[str, str, str]:
        """(name, operation, result type) of the text's instruction."""
        return trace_reduce.parse_instruction(self.instructions[name].head)

    def blocks(self, name: str) -> Tuple[Blocks, bool]:
        """Rule 2: the scopes of the instruction and of all it calls, and
        whether any of them is of the backward pass."""
        if name not in self._resolved:
            self._resolved[name] = (frozenset(), False)   # ends a cycle
            own = self.instructions[name]
            scopes, backward = set(own.scopes), own.backward
            for computation in own.calls:
                for member in self.members.get(computation, ()):
                    if self.instructions[member].lends:
                        s, b = self.blocks(member)
                        scopes |= s
                        backward |= b
            self._resolved[name] = (frozenset(scopes), backward)
        return self._resolved[name]


@dataclass
class Row:
    name: str
    blocks: Blocks                      # empty: unscoped, or not found
    seconds: float = 0.0                # a step, averaged over devices
    backward_s: float = 0.0
    groups: Dict[str, float] = field(default_factory=dict)  # by op_group

    def longest(self, n: int) -> List[Tuple[str, float]]:
        return sorted(self.groups.items(), key=lambda kv: -kv[1])[:n]


@dataclass
class BlockTable:
    module: str
    steps: int            # runs of the step's program on a device
    busy_s: float         # of the program, a step, averaged over devices
    rows: List[Row]       # longest first

    def ms_per_step(self, counted: Callable[[Blocks], bool]) -> float:
        """Of the rows whose blocks ``counted`` accepts; *not found* is
        no block's."""
        return 1e3 * sum(r.seconds for r in self.rows
                         if r.name != NOT_FOUND and counted(r.blocks))

    @property
    def not_found_s(self) -> float:
        return sum(r.seconds for r in self.rows if r.name == NOT_FOUND)

    def lines(self) -> List[str]:
        out = [f"device time by block: {self.steps} runs of {self.module} "
               f"on a device, {1e3 * self.busy_s:.3f} ms busy a step; "
               f"{1e3 * self.not_found_s:.3f} ms of it not found in the "
               "program's text"]
        for r in self.rows:
            longest = "; ".join(f"{g} {1e3 * s:.3f}"
                                for g, s in r.longest(3))
            out.append(f"  {r.name}: {1e3 * r.seconds:.3f} ms a step, "
                       f"{1e3 * r.backward_s:.3f} backward; {longest}")
        return out


def exclusive(events: List[Tuple[float, float, str]]
              ) -> List[Tuple[str, float]]:
    """(key, seconds) of every event with the time of the events nested
    inside it taken out; ``events`` are (start, end, key)."""
    out = []
    open_: List[list] = []          # [end, key, seconds left]

    def close():
        _, key, left = open_.pop()
        out.append((key, left))

    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][0] <= start:
            close()
        if open_:
            open_[-1][2] -= min(end, open_[-1][0]) - start
        open_.append([end, key, end - start])
    while open_:
        close()
    return out


def wire_fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of every field of one protobuf message: an
    int for a varint, the bytes of a length-delimited or fixed field."""
    def varint(i):
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value, i

    i = 0
    while i < len(buf):
        key, i = varint(i)
        kind = key & 7
        if kind == 0:
            value, i = varint(i)
        else:
            if kind == 2:
                size, i = varint(i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in a trace")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def program_modules(xspace: bytes) -> Dict[str, bytes]:
    """The module (a serialized HloModuleProto) of every program the
    trace keeps one of, by the name its runs have on ``XLA Modules``:
    XSpace.planes (1) -> the XPlane called /host:metadata (name 2) ->
    event_metadata (4: a map entry, value 2) -> XEventMetadata name (2)
    and stats (5) -> XStat bytes_value (6): an HloProto, whose hlo_module
    is field 1."""
    out = {}
    for number, plane in wire_fields(xspace):
        if number != 1 or (2, PROGRAMS_PLANE) not in wire_fields(plane):
            continue
        for number, entry in wire_fields(plane):
            if number != 4:
                continue
            metadata = list(wire_fields(dict(wire_fields(entry))[2]))
            for number, stat in metadata:
                proto = dict(wire_fields(stat)).get(6) if number == 5 else None
                if proto:
                    out[dict(metadata)[2].decode()] = dict(
                        wire_fields(proto))[1]
    return out


def _refused(say, e: Exception) -> None:
    """Rule 4: no existing metric is lost to a fault in here."""
    at = traceback.extract_tb(e.__traceback__)[-1]
    say(f"no device time by block: {type(e).__name__}: {e} "
        f"({os.path.basename(at.filename)}:{at.lineno})")


def block_table(data, text: str, chips: Optional[int] = None,
                say: Callable[[str], None] = print) -> Optional[BlockTable]:
    """The table of a ``ProfileData`` and the text of the step's program,
    or None with one line said (rule 4).  ``chips`` keeps the first so
    many device planes."""
    try:
        return _block_table(data, text, chips, say)
    except Exception as e:
        return _refused(say, e)


def read(xplane_path: str, chips: Optional[int] = None,
         say: Callable[[str], None] = print) -> Optional[BlockTable]:
    """:func:`block_table` of one ``.xplane.pb`` file and the module it
    keeps of the step's program, as the text ``compiled.as_text()`` would
    give for it."""
    try:
        from jax._src.lib import xla_client
        from jax.profiler import ProfileData
        with open(xplane_path, "rb") as f:
            xspace = f.read()
        modules = {name: module
                   for name, module in program_modules(xspace).items()
                   if name.partition("(")[0] == STEP_PROGRAM}
        if not modules:
            say(f"no device time by block: the trace keeps no module of a "
                f"program called {STEP_PROGRAM} (a CPU's trace has none)")
            return None
        data = ProfileData.from_serialized_xspace(xspace)
        ran = [ev.name for plane in data.planes for line in plane.lines
               if line.name == MODULES_LINE for ev in line.events]
        program = max(modules, key=ran.count)
        text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            modules[program]).to_string()
        return _block_table(data, text, chips, say, program)
    except Exception as e:
        return _refused(say, e)


def ms_per_step(obs: dict, metric_file: str,
                counted: Callable[[Blocks], bool]) -> Optional[float]:
    """What a file of ``benchmarks/layer_metrics`` returns: device
    milliseconds a step of the rows whose blocks ``counted`` accepts, or
    None where the run has no table.  The first of them to ask reads the
    run's trace (the newest under the ``.bench_traces`` of the checkout
    the metric's file lies in: the driver hands no path over), says the
    table, and leaves it under ``obs["scopes"]`` for the others."""
    if obs.get("trace") is None:      # no trace, or one of no device
        return None
    if "scopes" not in obs:
        obs["scopes"] = None
        say = functools.partial(print, flush=True)
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(metric_file))))
        traces = glob.glob(os.path.join(
            root, ".bench_traces", "*", "plugins", "profile", "*",
            "*.xplane.pb"))
        if traces:
            t = time.perf_counter()
            table = obs["scopes"] = read(
                max(traces, key=os.path.getmtime), obs["chips"], say)
            for line in table.lines() if table is not None else ():
                say(line)
            say(f"  (reading the trace by block took "
                f"{time.perf_counter() - t:.1f} s)")
    table = obs["scopes"]
    return None if table is None else table.ms_per_step(counted)


def _block_table(data, text, chips, say, program=None
                 ) -> Optional[BlockTable]:
    """``program``: the full name of the step's runs on ``XLA Modules``;
    None: every run of a program called like the text's module."""
    step = StepText(text)
    if not step.scoped:
        say("no device time by block: no instruction of the program's "
            f"text carries any of the scopes {', '.join(SCOPES)} "
            "(a program from before them, or an executable the compile "
            "cache kept from then)")
        return None
    planes = sorted(
        (int(m.group(1)), plane) for plane in data.planes
        for m in [trace_reduce.DEVICE_PLANE.match(plane.name)] if m)[:chips]
    devices = []        # (runs of the step, its events) of each device
    for _, plane in planes:
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or trace_reduce.OPS_LINE not in lines:
            continue
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in lines[MODULES_LINE].events
            if (ev.name == program if program
                else ev.name.partition("(")[0] == step.module))
        starts = [s for s, _ in modules]
        events = []
        for ev in lines[trace_reduce.OPS_LINE].events:
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i >= 0 and ev.start_ns < modules[i][1]:
                events.append((ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9,
                               ev.name))
        if events:
            devices.append((len(modules), events))
    if not devices:
        say(f"no device time by block: the trace holds no run of "
            f"{step.module or 'the step'} on a device (a CPU has no "
            "device plane)")
        return None
    seen: Dict[str, tuple] = {}      # event name -> what _place() said
    rows: Dict[str, Row] = {}
    busy = 0.0
    for runs, events in devices:
        a_step = 1.0 / (runs * len(devices))     # and averaged over them
        busy += a_step * trace_reduce.total(trace_reduce.union(
            (s, e) for s, e, _ in events))
        for name, seconds in exclusive(events):
            if name not in seen:
                seen[name] = _place(step, name)
            row, group, blocks, backward = seen[name]
            r = rows.setdefault(row, Row(row, blocks))
            r.seconds += a_step * seconds
            r.backward_s += a_step * seconds if backward else 0.0
            r.groups[group] = r.groups.get(group, 0.0) + a_step * seconds
    table = BlockTable(step.module, devices[0][0], busy, sorted(
        rows.values(), key=lambda r: -r.seconds))
    summed = sum(r.seconds for r in table.rows)
    if abs(summed - table.busy_s) > SUM_TOLERANCE * table.busy_s:
        say(f"no device time by block: the rows sum to {1e3 * summed:.3f} "
            f"ms a step and the program is busy {1e3 * table.busy_s:.3f}")
        return None
    if table.not_found_s > MOST_NOT_FOUND * table.busy_s:
        say(f"no device time by block: {1e3 * table.not_found_s:.3f} of "
            f"{1e3 * table.busy_s:.3f} ms a step are instructions that the "
            "program's text does not hold under the same name, "
            "operation and result type (another executable ran)")
        return None
    return table


def _place(step: StepText, event_name: str):
    """(row, instruction group, blocks, backward) of an event: rule 1,
    then rule 2."""
    name, operation, result = trace_reduce.parse_instruction(event_name)
    group = trace_reduce.op_group(event_name, name, operation, result)
    if name not in step.instructions:
        return NOT_FOUND, group, frozenset(), False
    _, in_text, result_in_text = step.signature(name)
    if result != result_in_text or not (
            operation == in_text or operation.startswith("async-")
            and in_text.endswith(operation[len("async"):])):
        return NOT_FOUND, group, frozenset(), False
    blocks, backward = step.blocks(name)
    return row_name(blocks), group, blocks, backward
