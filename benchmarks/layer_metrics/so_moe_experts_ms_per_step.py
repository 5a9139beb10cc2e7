"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of the routed experts held here, forward and
backward: the sub-scope ``experts``, the grouped products' kernels of
``ops/grouped_matmul.py`` (which carry their ``op_name``) and the SiLU
gate between them; by ``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(obs, __file__, ("experts",))
