"""Layer: model (``ops/pallas_ops.py:flash_attention``).  Device
milliseconds a step of the attention layers' cores, 32 query heads on 8
key/value heads of 64: the flash kernels (forward, dq, dkv) and the
repeat of K and V to the query heads with its sum back: the sub-scope
``gqa_core``, by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("gqa_core",))
