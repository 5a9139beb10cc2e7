"""Layer: model (``ops/ssm.py``, ``models/granite_hybrid.py``).  Device
milliseconds a step under the sub-scope ``ssm_conv``: the causal
depthwise convolution over xBC, its SiLU and the split into x, B, C,
forward, recomputed and backward, by ``harness/ssm_scopes.py``."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ("ssm_conv",))
