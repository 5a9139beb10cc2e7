"""Layer: model (``models/granite_hybrid.py``'s mixer, as
``models/nemotron_h.py`` builds it).  Device milliseconds a step of a
Mamba-2 block beside its scan: the sub-scopes ``ssm_proj`` (the
projections in and out), ``ssm_conv`` (the causal convolution over xBC,
its SiLU and the split) and ``ssm_norm`` (the gated RMSNorm by group),
forward, recomputed and backward, by ``harness/hybrid_moe_scopes.py``."""

from benchmarks.harness import hybrid_moe_scopes


def read(obs):
    return hybrid_moe_scopes.ms_per_step(
        obs, __file__, ("ssm_proj", "ssm_conv", "ssm_norm"))
