"""Layer: model (``ops/pallas_ops.py:flash_attention``).  Device
milliseconds a step of the GQA layer's core, 8 query heads on 1 key/value
head of 128: the flash kernels (forward, the forward again where the
mixer is recomputed, dq, dkv): the sub-scope ``gqa_core``, by
``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("gqa_core",))
