"""Layer: model (``models/gpt.py``).  Device milliseconds a step under
the program's ``mlp`` scope alone (``GPTMLP.forward``, forward and
backward), averaged over the devices, by ``harness/scopes.py``.  A row
that mixes ``mlp`` with another block is not counted here."""

from benchmarks.harness import scopes


def read(obs):
    return scopes.ms_per_step(obs, __file__, lambda blocks: blocks == {"mlp"})
