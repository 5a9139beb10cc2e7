"""Layer: model (``ops/ssm.py``, ``ops/ssm_kernels.py``).  Device
milliseconds a step under the sub-scope ``ssm_scan``: the selective scans
of every Mamba-2 block (eight groups of B and C, chunks of 128), the
forward pass, its recomputation where the block is recomputed and the
backward pass, with the step sizes, the running sums and the copies XLA
lays around a kernel's operands, whatever implements them, by
``harness/hybrid_moe_scopes.py``."""

from benchmarks.harness import hybrid_moe_scopes


def read(obs):
    return hybrid_moe_scopes.ms_per_step(obs, __file__, ("ssm_scan",))
