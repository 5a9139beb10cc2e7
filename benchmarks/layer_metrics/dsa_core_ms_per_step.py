"""Layer: model (``ops/sparse_attention.py``).  Device milliseconds a
step under the sub-scope ``sparse_core``: attention over the selected
keys, forward and backward, whatever implements it (Mosaic kernels under
a mask today), by ``harness/subscopes.py``."""

from benchmarks.harness import subscopes


def read(obs):
    return subscopes.ms_per_step(obs, __file__, ("sparse_core",))
