"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of the routed experts held here, forward and
backward: the sub-scope ``experts``, the grouped products' kernels of
``ops/grouped_matmul.py`` (which carry their ``op_name``) and the SiLU
gate between them; by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(obs, __file__, ("experts",))
