"""Layer: model (``models/solar_open2.py``, ``ops/ssm.py``'s convolution).
Device milliseconds a step of what a KDA mixer does around its core: the
three short convolutions with their SiLU, the L2 normalisation of q and
k, the low-rank decay gate and beta, the output's norm and its low-rank
sigmoid gate, forward and backward: the sub-scope ``kda_conv_gate``, by
``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("kda_conv_gate",))
