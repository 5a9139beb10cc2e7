"""Layer: model (``ops/pallas_ops.py``).  Device milliseconds a step under
the sub-scopes ``full_core`` and ``cross_core``: the four
``flash_attention`` calls of the K/V producer and of the cross layer that
reads its K and V, each over the whole causal triangle, forward,
recomputed and backward, by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__,
                                     ("full_core", "cross_core"))
