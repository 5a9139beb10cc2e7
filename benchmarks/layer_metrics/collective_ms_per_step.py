"""Layer: sharding (``distributed/collective.py``, the parameters'
``dist_spec``s, ``pallas_ops._per_device``).  Device milliseconds a step
spent in all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute operations, averaged over the devices."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.steps:
        return None
    return 1e3 * trace.kind_seconds("collective") / trace.steps
