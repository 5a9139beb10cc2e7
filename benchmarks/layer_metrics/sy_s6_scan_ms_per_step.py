"""Layer: model (``ops/ssm.py:selective_scan``).  Device milliseconds a
step under the sub-scope ``s6_scan``: the selective scans of both Mamba-1
layers (a step size a channel, a decay a channel and state, chunks side
by side), the forward pass, its recomputation where the layer is
recomputed and the walk back, with the step sizes' softplus, whatever
implements them, by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__, ("s6_scan",))
