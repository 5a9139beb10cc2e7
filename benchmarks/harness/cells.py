"""Reads ``BENCHMARK.json`` and the data files a cell names.

No jax here: the command line loads its cell before anything that could
touch a device, and the tests walk every cell without one.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List

NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchmarkError(Exception):
    """The benchmark's own files disagree with each other."""


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""
    root: str            # the checkout the files were read from
    name: str
    chips: int
    config_name: str
    config: dict         # the configuration file's contents
    traffic_name: str
    traffic: dict        # the traffic file's contents
    end_to_end: List[dict]   # the metrics this cell reports, as declared
    per_layer: List[dict]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = REPO_ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[dict], what: str) -> Dict[str, dict]:
    out = {}
    for e in entries:
        name = e.get("name", "")
        if not NAME.match(name):
            raise BenchmarkError(f"{what} name {name!r} is not a plain name")
        if name in out:
            raise BenchmarkError(f"{what} {name!r} is declared twice")
        out[name] = e
    return out


def reported_in(metric: dict, workload: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: str = REPO_ROOT) -> Cell:
    bench = load_benchmark(root)
    workloads = _by_name(bench["workloads"], "workload")
    if workload not in workloads:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{sorted(workloads)}")
    w = workloads[workload]
    configs = _by_name(bench["configs"], "config")
    if w["config"] not in configs:
        raise BenchmarkError(
            f"workload {workload!r} names config {w['config']!r}, which "
            "BENCHMARK.json does not declare")
    if not NAME.match(w["traffic"]):
        raise BenchmarkError(f"traffic name {w['traffic']!r} is not plain")
    if w["chips"] not in (1, 4):
        raise BenchmarkError(f"workload {workload!r}: chips must be 1 or 4")
    e2e = _by_name(bench["end_to_end"], "end-to-end metric")
    layer = _by_name(bench["per_layer"], "per-layer metric")
    for m in layer.values():
        if m["moves"] not in e2e:
            raise BenchmarkError(
                f"per-layer metric {m['name']!r} moves {m['moves']!r}, "
                "which is not an end-to-end metric")
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(
            root, "benchmarks", "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in e2e.values() if reported_in(m, workload)],
        per_layer=[m for m in layer.values() if reported_in(m, workload)
                   and reported_in(e2e[m["moves"]], workload)])


def sized(data: dict, rehearse: bool) -> dict:
    """A configuration or a traffic mix at the size this run uses: as
    published, or with its ``rehearsal`` group's toy values laid over."""
    return {**data, **data["rehearsal"]} if rehearse else data


def least_seconds(flops: float, bytes_moved: float, peaks: dict):
    """The least time one chip could take, and which peak bounds it: the
    larger of operations over peak FLOP/s and bytes over peak bytes/s."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("operations" if by_flops >= by_bytes
                                     else "bytes")


def load_peaks(device_kind: str, root: str = REPO_ROOT) -> dict:
    """The published peaks of ``device_kind``.  A kind with no file is an
    error, never a default: a share of an unknown peak means nothing."""
    folder = os.path.join(root, "benchmarks", "peaks")
    for entry in sorted(os.listdir(folder)):
        if entry.endswith(".json"):
            peaks = load_json(os.path.join(folder, entry))
            if peaks["device_kind"] == device_kind:
                return peaks
    raise BenchmarkError(
        f"no published peaks for device_kind {device_kind!r} under "
        f"{folder}: add a file with its source before reporting a share "
        "of peak")
