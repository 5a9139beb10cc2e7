"""Layer: entry points and step engine (``distributed/runner.py``,
``framework/dispatch.py``).  Host milliseconds one ``runner.train_step``
call takes to return, which it does before the device finishes: the
median over the window's ``dispatch`` spans of the harness.  It moves
``tokens_per_s`` only where it nears the device's step time."""

import statistics


def read(obs):
    w = obs["window"]
    spans = obs["spans"].between(w["start_s"], w["end_s"], "dispatch")
    if not spans:
        return None
    return 1e3 * statistics.median(s.end_s - s.start_s for s in spans)
