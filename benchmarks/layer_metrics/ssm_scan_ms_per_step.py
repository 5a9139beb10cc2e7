"""Layer: model (``ops/ssm.py``).  Device milliseconds a step under the
sub-scope ``ssm_scan``: the selective scans of every Mamba-2 layer, the
forward pass, its recomputation and the backward pass with the chunk
matrices made again, whatever implements them (XLA operations today), by
``harness/ssm_scopes.py``."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ("ssm_scan",))
