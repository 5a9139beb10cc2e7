"""Layer: model (``models/solar_open2.py``).  Device milliseconds a step
under the sub-scope ``shared_expert``: the expert every token visits
(three dense products at width 1280 and the SiLU gate between them),
forward and backward, by ``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("shared_expert",))
