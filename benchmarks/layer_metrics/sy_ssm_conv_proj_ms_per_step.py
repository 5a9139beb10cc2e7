"""Layer: model (``models/sambay.py``'s Mamba-1 mixer).  Device
milliseconds a step of a Mamba-1 layer's mixer beside its scan: the
sub-scopes ``ssm_proj`` (the projections in and out, the low-rank step
and B, C) and ``ssm_conv`` (the causal convolution over x and its SiLU),
forward, recomputed and backward, by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__, ("ssm_proj", "ssm_conv"))
