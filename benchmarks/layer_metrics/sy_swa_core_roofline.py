"""Layer: kernels (``ops/pallas_ops.py`` with a window).  The least time
a chip could take for one step's window attention (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, by the family's
``window_cost``: a streaming kernel's seven products over the band's
pairs alone, two score maps and a V of twice the head's width a head
pair; q, k, v, the output and the gradients moved once), as per cent of
``sy_swa_core_ms_per_step``.  A walk that visits tiles outside the band,
a score map computed twice, or a forward pass run again reads lower."""

from benchmarks.harness import sambay_scopes


def read(obs):
    return sambay_scopes.roofline(obs, __file__, ("swa_core",),
                                  "window_cost")
