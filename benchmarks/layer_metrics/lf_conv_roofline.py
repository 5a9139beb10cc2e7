"""Layer: kernels (``ops/short_conv.py``, XLA operations today, between
two products of ``models/lfm2_moe.py``).  The least time a chip could take
for one step's convolution operators, each whole: both projections'
operations and the bytes u, ``[B | C | x]``, y, the output and their
gradients must move once (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, by the family's ``conv_cost``), as per cent of
all device time in rows that hold ``conv_proj`` or ``short_conv``, rows
mixed with another sub-scope included: no fusion takes time out of the
denominator, so no form of the operator reads over 100 %, and one that
moves ``[B | C | x]`` more than once reads lower."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    ms = lfm2_scopes.ms_per_step(obs, __file__, ("conv_proj", "short_conv"),
                                 mixed=True)
    return lfm2_scopes.roofline(obs, ms, "conv_cost")
