"""Layer: model (``models/sambay.py:_diff_combine``).  Device
milliseconds a step under the sub-scope ``diff_combine``: the difference
of a head pair's two maps' outputs at lambda, its RMS norm over the
pair's values and the scale, in the three attention-kind layers, forward,
recomputed and backward, by ``harness/sambay_scopes.py``."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.ms_per_step(obs, __file__, ("diff_combine",))
