"""Layer: model (``models/sambay.py``'s gated memory unit).  Device
milliseconds a step under the sub-scope ``gmu``: the projection of the
stream, its SiLU gate on the memory another layer's scan made, and the
projection out, forward, recomputed and backward (the memory's gradient
among it), by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__, ("gmu",))
