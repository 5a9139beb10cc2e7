"""Layer: device.  Device milliseconds a step of the step's instructions
that carry none of the program's six scopes (layer norms and residual
adds of a decoder layer, data placement, asynchronous copies' ends),
averaged over the devices, by ``harness/scopes.py``.  It keeps the other
four honest: what they lose has to turn up here or in a mixed row."""

from benchmarks.harness import scopes


def read(obs):
    return scopes.ms_per_step(obs, __file__, lambda blocks: not blocks)
