"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of getting tokens to the experts held here and back:
the sub-scopes ``router`` (float32 logits, sigmoid, top-k over ``s + b``,
gates over ``sum + 1e-6``, the balancing rule), ``dispatch`` (the sort by
expert and the gather) and ``combine`` (the weighted gather back), forward
and backward, by ``harness/lfm2_scopes.py``."""

from benchmarks.harness import lfm2_scopes


def read(obs):
    return lfm2_scopes.ms_per_step(
        obs, __file__, ("router", "dispatch", "combine"))
