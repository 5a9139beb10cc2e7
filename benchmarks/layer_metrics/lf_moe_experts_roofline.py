"""Layer: kernels (``ops/grouped_matmul.py``).  The least time a chip
could take for one step's routed-expert products over the pairs the
program counted (``moe_pairs_total``: 6 operations a weight and pair,
three matrices a pair; the held experts' weights and the pairs' rows
moved, by the family's ``experts_cost``), as per cent of
``lf_moe_experts_ms_per_step``."""

import os

from benchmarks.harness import report
from benchmarks.harness.cells import least_seconds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(obs):
    ms = report.load_reader(ROOT, "lf_moe_experts_ms_per_step")(obs)
    cost = getattr(obs.get("family"), "experts_cost", None)
    before, after = obs["counters"]["before"], obs["counters"]["after"]
    observed = after.get("observed", 0) - before.get("observed", 0)
    if not ms or cost is None or not observed:
        return None
    pairs = (after["moe_pairs"] - before["moe_pairs"]) / observed
    needs = cost(obs["config"], pairs)
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
