"""Layer: step engine (``distributed/runner.py``).  Host milliseconds of
the program's span ``mesh.launch``, the call of the jitted step and
nothing else: the median over the traced window's steps, on the
profiler's clock (``harness/program_spans.py``).  jax's share of a
dispatch: argument handling, the launch, the outputs' wrappers."""

from benchmarks.harness import program_spans as ps


def read(obs):
    return ps.metric(obs, __file__, lambda half: half.median_ms(ps.LAUNCH))
