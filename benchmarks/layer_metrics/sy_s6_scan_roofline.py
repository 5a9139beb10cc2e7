"""Layer: kernels (``ops/ssm.py:selective_scan``, XLA operations today).
The least time a chip could take for one step's selective scans (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, by the
family's ``scan_cost``: the ``channels x state`` updates once forward and
twice backward; x, B, C, dt and y moved once forward, and with dy and the
gradients once backward), as per cent of ``sy_s6_scan_ms_per_step``.  It
reads the same work whatever implements it: a scan that writes its
states to memory, or runs again where its layer is recomputed, reads
lower."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.roofline(obs, __file__, ("s6_scan",), "scan_cost")
