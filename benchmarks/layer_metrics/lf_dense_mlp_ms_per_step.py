"""Layer: model (``models/lfm2_moe.py``).  Device milliseconds a step of
the leading dense layers' SiLU-gated MLP, three products at
``intermediate_size`` and the gate between them, forward and backward:
the sub-scope ``dense_mlp``, by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("dense_mlp",))
