"""Layer: step engine.  Runs on one device's ``XLA Modules`` that a
``mesh.dispatch`` launched, a step: 1 is the step alone; each further
one is a small program the host makes the device run before it (the
learning rate and the step counter made device scalars).  The table of
``harness/program_spans.py`` says them by name; None without a device
trace."""

from benchmarks.harness import program_spans as ps


def read(obs):
    return ps.metric(obs, __file__, lambda half: half.programs_per_step())
