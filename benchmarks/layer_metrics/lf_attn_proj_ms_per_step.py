"""Layer: model (``models/lfm2_moe.py``).  Device milliseconds a step of an
attention layer beside its core: the four projections, the RMS
normalisation of q and k a head and their rotation, forward and backward:
the sub-scope ``attn_proj``, by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("attn_proj",))
