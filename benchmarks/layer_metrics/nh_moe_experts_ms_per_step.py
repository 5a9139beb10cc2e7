"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of the routed experts held here, forward and
backward: the sub-scope ``experts`` (the squared ReLU between the
products) and the grouped matrix products themselves, which XLA makes of
``jax.lax.ragged_dot`` as Mosaic custom calls named ``ragged-dot-...``
with no ``op_name``, so the scope cannot find them and their name does;
by ``harness/hybrid_moe_scopes.py``."""

from benchmarks.harness import hybrid_moe_scopes


def read(obs):
    between = hybrid_moe_scopes.ms_per_step(obs, __file__, ("experts",))
    products = hybrid_moe_scopes.unscoped_group_ms_per_step(
        obs, __file__, "ragged-dot")
    return None if between is None or products is None \
        else between + products
