"""Layer: model (``models/lfm2_moe.py``).  Device milliseconds a step of a
convolution operator's two projections, hidden to ``[B | C | x]`` and back
to hidden, forward and backward: the sub-scope ``conv_proj``, by
``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("conv_proj",))
