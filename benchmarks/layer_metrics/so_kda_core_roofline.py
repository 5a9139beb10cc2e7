"""Layer: kernels (``ops/delta_rule.py``, XLA operations today).  The least
time a chip could take for one step's gated delta rules (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, by the family's
``kda_cost``: the chunked form's products at chunk 64 once forward and
twice backward; q, k, v, log alpha, beta and the output moved once
forward, and with their gradients once backward), as per cent of
``so_kda_core_ms_per_step``.  It reads the same work whatever implements
it: a rule that recomputes, or walks its chunks one small product at a
time, reads lower."""

from benchmarks.harness import solar_scopes
from benchmarks.harness.cells import least_seconds


def read(obs):
    ms = solar_scopes.ms_per_step(obs, __file__, ("kda_core",))
    cost = getattr(obs.get("family"), "kda_cost", None)
    if not ms or cost is None:
        return None
    needs = cost(obs["config"], obs["traffic"]["batch"],
                 obs["traffic"]["seq_len"])
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
