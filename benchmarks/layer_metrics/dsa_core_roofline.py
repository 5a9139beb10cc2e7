"""Layer: kernels (``ops/sparse_attention.py``).  The least time a chip
could take for one step's attention over the selected keys only (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, by
the family's ``sparse_core_cost``: six products over sum_t min(t + 1,
topk) pairs), as per cent of all the device time under the sub-scope
``sparse_core``.  It reads the same work whatever implements it: a core
that computes masked-out pairs, or anything else beside the products,
reads lower."""

from benchmarks.harness import subscopes
from benchmarks.harness.cells import least_seconds


def read(obs):
    ms = subscopes.ms_per_step(obs, __file__, ("sparse_core",))
    cost = getattr(obs.get("family"), "sparse_core_cost", None)
    if not ms or cost is None:
        return None
    needs = cost(obs["config"], obs["traffic"]["batch"],
                 obs["traffic"]["seq_len"])
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
