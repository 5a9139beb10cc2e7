"""Layer: kernels (``ops/pallas_ops.py``).  The least time a chip could
take for one step's full causal attention, the K/V producer's and the
cross layer's (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, by the family's ``full_cost``: a streaming kernel's seven
products over the causal triangle's pairs, two score maps and a V of
twice the head's width a head pair; q, k, v, the output and the
gradients moved once), as per cent of ``sy_full_core_ms_per_step``.  A
score map computed twice, as the four calls at one head width compute
it, reads lower."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.roofline(obs, __file__,
                                  ("full_core", "cross_core"), "full_cost")
