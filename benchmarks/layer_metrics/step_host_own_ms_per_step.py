"""Layer: step engine (``distributed/runner.py``).  Host milliseconds of
``mesh.dispatch`` less ``mesh.launch``, the median over the traced
window's steps: staging the batch, the two device scalars, the value
cache, rebinding what the step returned, the hooks: the runner's own
Python beside jax's launch (``harness/program_spans.py``)."""

from benchmarks.harness import program_spans as ps


def read(obs):
    return ps.metric(obs, __file__, lambda half: half.median_ms(ps.OWN))
