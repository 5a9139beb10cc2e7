"""Layer: kernels (``ops/pallas_ops.py``).  The least time a chip could
take for one step's causal attention at 32 query heads on 8 key/value
heads of 64 (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, by the family's ``gqa_cost``: the causal triangle's two products
once forward and twice backward; q, k, v, the output and their gradients
moved once), as per cent of ``lf_gqa_core_ms_per_step``.  A score map
computed again in the backward kernels, or K and V written out at the
query heads' count, reads lower."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    ms = lfm2_scopes.ms_per_step(obs, __file__, ("gqa_core",))
    return lfm2_scopes.roofline(obs, ms, "gqa_cost")
