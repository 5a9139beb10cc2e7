"""Layer: step engine.  Host milliseconds a step that Python's cyclic
collector held the interpreter inside the traced window: the program's
``host.gc`` spans, summed over every thread.  The table of
``harness/program_spans.py`` says the longest and its generation: one
pause of tens of milliseconds right after a sync is a group of steps
late."""

from benchmarks.harness import program_spans as ps


def read(obs):
    return ps.metric(obs, __file__, lambda half: half.gc_ms_per_step())
