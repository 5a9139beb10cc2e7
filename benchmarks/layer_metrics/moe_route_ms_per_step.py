"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  Device
milliseconds a step of getting tokens to the experts held here and
back: the sub-scopes ``router`` (logits, softmax, top-k), ``dispatch``
(the sort by expert and the gather) and ``combine`` (the weighted gather
back), forward and backward, by ``harness/subscopes.py``."""

from benchmarks.harness import subscopes


def read(obs):
    return subscopes.ms_per_step(obs, __file__,
                                 ("router", "dispatch", "combine"))
