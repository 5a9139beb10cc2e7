"""Layer: model (``ops/delta_rule.py``).  Device milliseconds a step of the
KDA layers' gated delta rule, forward, the forward again where the mixer
is recomputed, and backward: the sub-scope ``kda_core``, by
``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("kda_core",))
