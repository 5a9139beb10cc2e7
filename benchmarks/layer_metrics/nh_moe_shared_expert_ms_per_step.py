"""Layer: model (``models/nemotron_h.py``).  Device milliseconds a step
under the sub-scope ``shared_expert``: the expert every token visits
(two dense products at width 3712 and the squared ReLU between them),
forward and backward, by ``harness/hybrid_moe_scopes.py``."""

from benchmarks.harness import hybrid_moe_scopes


def read(obs):
    return hybrid_moe_scopes.ms_per_step(obs, __file__, ("shared_expert",))
