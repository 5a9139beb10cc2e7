"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of the experts held here, forward and backward: the
sub-scope ``experts`` (the gate between the products) and the grouped
matrix products themselves, which XLA makes of ``jax.lax.ragged_dot`` as
Mosaic custom calls named ``ragged-dot-...`` with no ``op_name``, so the
scope cannot find them and their name does; by
``harness/subscopes.py``."""

from benchmarks.harness import subscopes


def read(obs):
    gate = subscopes.ms_per_step(obs, __file__, ("experts",))
    products = subscopes.unscoped_group_ms_per_step(obs, __file__,
                                                    "ragged-dot")
    return None if gate is None or products is None else gate + products
