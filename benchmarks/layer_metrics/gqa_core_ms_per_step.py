"""Layer: model (``models/granite_hybrid.py``, ``ops/pallas_ops.py``).
Device milliseconds a step under the sub-scope ``gqa_core``: the
attention layers' core (``flash_attention`` with its key and value heads
repeated, position-free), forward, recomputed and backward, by
``harness/ssm_scopes.py``."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ("gqa_core",))
