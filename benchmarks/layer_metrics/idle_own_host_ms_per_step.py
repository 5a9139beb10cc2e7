"""Layer: step engine.  Milliseconds a step in which the idlest device
ran nothing while the host was inside ``mesh.dispatch`` and outside
``mesh.launch`` (a collection under it included): what the runner's own
Python could give back.  From the traced window's idle gaps, laid to
the program's spans by ``harness/program_spans.py``; None without a
device trace."""

from benchmarks.harness import program_spans as ps


def read(obs):
    return ps.metric(obs, __file__, lambda half: half.idle_own_ms_per_step())
