"""What a driver hands back, and the one line made of it.

A driver (``benchmarks/drivers/<driver>.py``) exposes
``run(cell, options, say) -> Record``.  The harness adds nothing to the
record but the per-layer metrics, each read by its own file
(``benchmarks/layer_metrics/<metric>.py``, ``read(obs) -> number or
None``), and prints the line the contract asks for.
"""

from __future__ import annotations

import importlib.util
import math
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from .cells import BenchmarkError, Cell


@dataclass(frozen=True)
class RunOptions:
    seed: int
    seconds: float
    trace: bool
    rehearse: bool     # toy size on the CPU: asked for, never detected


@dataclass
class Record:
    correct: bool
    attempted: int
    failed: int
    window_start_s: float            # time.perf_counter() at its start
    end_to_end: Dict[str, float]     # every end-to-end metric but setup_s
    devices: List[Any]               # the jax devices the cell ran on
    memory_peak_bytes: Optional[int]  # the fullest of them; None on a CPU
    # what the per-layer readers read: the cell, the family module, the
    # peaks, the window, the harness spans, counters, the compiled step's
    # facts and, in a traced run, the reduced device trace under "trace"
    obs: Dict[str, Any] = field(default_factory=dict)


def load_reader(root: str, metric: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(root, "benchmarks", "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(
            f"per-layer metric {metric!r} is declared in BENCHMARK.json "
            f"and has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.layer_metrics.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def per_layer_metrics(cell: Cell, obs: dict) -> Dict[str, float]:
    """The cell's declared per-layer metrics.  A reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.root, m["name"])(obs)
        if value is not None:
            out[m["name"]] = float(value)
    return out


def result(cell: Cell, options: RunOptions, record: Record,
           setup_s: float) -> dict:
    """The object of the last line: the end-to-end metrics without a
    trace, the per-layer metrics and the breakdown with one."""
    first = record.devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(record.devices),
              "memory_peak_bytes": record.memory_peak_bytes}
    out = {"correct": bool(record.correct),
           "attempted": int(record.attempted),
           "failed": int(record.failed)}
    if options.trace:
        values = per_layer_metrics(cell, record.obs)
        declared = cell.per_layer
        trace = record.obs.get("trace")
        if trace is not None:
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
            out["breakdown"] = {"device_ops": trace.top_ops(10),
                                "idle_gaps": trace.top_idle_gaps(10)}
    else:
        values = {**record.end_to_end, "setup_s": setup_s}
        declared = cell.end_to_end
        missing = [m["name"] for m in declared if m["name"] not in values]
        # a rehearsal has no peaks table and no device memory to read
        if missing and not options.rehearse:
            raise BenchmarkError(
                f"cell {cell.name!r} declares end-to-end metrics the "
                f"driver did not report: {missing}")
    units = {m["name"]: m["unit"] for m in declared}
    bad = [n for n, v in values.items() if n in units and not math.isfinite(v)]
    if bad:
        raise BenchmarkError(f"metrics that are not finite numbers: {bad}")
    out["metrics"] = {n: {"value": values[n], "unit": units[n]}
                      for n in units if n in values}
    out["device"] = device
    return out
