"""Layer: model (``models/granite_hybrid.py``).  Device milliseconds a
step of a Mamba-2 mixer's dense parts: the sub-scopes ``ssm_proj`` (the
projections in and out) and ``ssm_norm`` (the gated RMSNorm), forward,
recomputed and backward, by ``harness/ssm_scopes.py``."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ("ssm_proj", "ssm_norm"))
