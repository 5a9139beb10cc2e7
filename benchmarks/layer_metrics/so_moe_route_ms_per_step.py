"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of getting tokens to the experts held here and back:
the sub-scopes ``router`` (float32 logits over 320 experts, sigmoid,
top-8 over ``s + b``, gates, the balancing rule), ``dispatch`` (the sort
by expert and the gather) and ``combine`` (the way back to the tokens),
forward and backward, by ``harness/solar_scopes.py``."""

from benchmarks.harness import solar_scopes


def read(obs):
    return solar_scopes.ms_per_step(
        obs, __file__, ("router", "dispatch", "combine"))
