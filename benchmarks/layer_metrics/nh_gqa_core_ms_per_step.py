"""Layer: model (``models/nemotron_h.py``, ``ops/pallas_ops.py``).  Device
milliseconds a step under the sub-scope ``gqa_core``: the attention
block's core (``flash_attention``, 32 query heads on 2 key/value heads
repeated 16-fold, position-free), forward and backward, by
``harness/hybrid_moe_scopes.py``."""

from benchmarks.harness import hybrid_moe_scopes


def read(obs):
    return hybrid_moe_scopes.ms_per_step(obs, __file__, ("gqa_core",))
