"""Layer: kernels (``ops/ssm_kernels.py``).  The least time a chip could
take for one step's selective scans (the larger of operations over peak
FLOP/s and bytes over peak bytes/s, by the family's ``scan_cost`` at
eight groups and chunks of 128: the chunked form's products once forward
and twice backward; x, B, C, dt and y moved once forward, and with dy
and the gradients once backward), as per cent of
``nh_ssm_scan_ms_per_step``.  It reads the same work whatever implements
it: a scan that recomputes, or whose operands are re-laid around a
kernel, reads lower."""

from benchmarks.harness import hybrid_moe_scopes
from benchmarks.harness.cells import least_seconds


def read(obs):
    ms = hybrid_moe_scopes.ms_per_step(obs, __file__, ("ssm_scan",))
    cost = getattr(obs.get("family"), "scan_cost", None)
    if not ms or cost is None:
        return None
    needs = cost(obs["config"], obs["traffic"]["batch"],
                 obs["traffic"]["seq_len"])
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
