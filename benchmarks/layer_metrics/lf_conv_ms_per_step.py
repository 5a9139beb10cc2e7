"""Layer: model (``ops/short_conv.py``).  Device milliseconds a step of the
double-gated short convolutions, ``C * conv(B * x)`` and its own backward
pass, which makes ``B * x`` and the taps' sums again: the sub-scope
``short_conv``, by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("short_conv",))
