"""Layer: model (``models/solar_open2.py``).  Device milliseconds a step of
a KDA mixer's four projections, hidden to the held heads' q, k and v and
back to hidden, forward and backward: the sub-scope ``kda_proj``, by
``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("kda_proj",))
