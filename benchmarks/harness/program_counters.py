"""What the program counts of its own set-up, read from its registry.

Since PR 36 the program counts, where it happens and always
(``observability/host_events.py``, ``distributed/runner.py``):

- ``jax_compile_seconds_total{phase, fun}`` and
  ``jax_compile_events_total{phase, fun}``: every trace, lowering,
  backend compile (the persistent cache's read included), cache
  retrieval and cache miss, by the function jax built for; the runner's
  jitted step is ``fun="step"``;
- ``mesh_step_programs_total{reason}``: the executables the jitted step
  built, by what differed in its arguments from the one before, and a
  ``step_program`` entry in the decision ring (``observability/events``)
  that names the first few arguments that differ.

A reader gets the series of one counter by its labels.  None where the
program has no such counter (one from before PR 36), and for an
``obs`` that is of no run (no ``setup``): these metrics are about the
set-up of the run that ``obs`` is of, and the registry is the
process's.  Nothing here raises.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

Labels = Tuple[Tuple[str, str], ...]


def series(obs: dict, name: str) -> Optional[Dict[Labels, float]]:
    """``{labels: value}`` of every series of the counter ``name``."""
    if "setup" not in obs:
        return None
    try:
        from paddle_tpu.observability import metrics
        found = {i.labels: float(i.collect())
                 for i in metrics.registry().instruments()
                 if i.name == name and i.kind == "counter"}
        return found or None
    except Exception as e:
        print(f"no program counter {name}: {type(e).__name__}: {e}",
              flush=True)
        return None


def total(found: Dict[Labels, float], **labels: str) -> float:
    """Summed over the series that carry all of ``labels``."""
    return sum(v for have, v in found.items()
               if labels.items() <= dict(have).items())


def step_compile(obs: dict) -> Optional[Tuple[dict, dict]]:
    """(seconds by phase, events by phase) of ``fun="step"``, said on a
    line once a run; None where the program counts no such thing or
    built no step."""
    if "program_counters" not in obs:
        obs["program_counters"] = None
        seconds = series(obs, "jax_compile_seconds_total")
        events = series(obs, "jax_compile_events_total")
        if seconds and events and total(events, fun="step"):
            by = functools.partial(_by_phase, fun="step")
            obs["program_counters"] = by(seconds), by(events)
            print("what jax built for the runner's step, by the program's "
                  "own counters: " + "; ".join(
                      f"{phase} {int(n)} in "
                      f"{obs['program_counters'][0].get(phase, 0.0):.3f} s"
                      for phase, n in obs["program_counters"][1].items()),
                  flush=True)
    return obs["program_counters"]


def _by_phase(found: Dict[Labels, float], fun: str) -> Dict[str, float]:
    return {dict(have)["phase"]: v for have, v in sorted(found.items())
            if dict(have).get("fun") == fun}


def step_programs(obs: dict) -> Optional[Dict[str, float]]:
    """``{reason: executables}`` of ``mesh_step_programs_total``, with
    the decision ring's entries said on a line each, once a run."""
    if "step_programs" not in obs:
        found = series(obs, "mesh_step_programs_total")
        obs["step_programs"] = found and {
            dict(have)["reason"]: v for have, v in found.items()}
        if found:
            from paddle_tpu.observability import events
            for e in events.snapshot():
                if e.get("kind") == "step_program":
                    print(f"the jitted step built an executable at step "
                          f"{e.get('step')}: reason {e.get('reason')}"
                          + "".join(f"\n    {d}"
                                    for d in e.get("differing", ())),
                          flush=True)
    return obs["step_programs"]
