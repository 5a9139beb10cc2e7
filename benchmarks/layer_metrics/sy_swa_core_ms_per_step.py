"""Layer: model (``ops/pallas_ops.py`` with a window).  Device
milliseconds a step under the sub-scope ``swa_core``: the window layer's
four ``flash_attention`` calls inside the band of ``sliding_window`` keys,
forward, recomputed and backward, with the copies XLA lays around the
kernels' operands (the halves of the head pairs, the repeated K and V),
by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__, ("swa_core",))
