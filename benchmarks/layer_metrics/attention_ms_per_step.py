"""Layer: model (``models/gpt.py``).  Device milliseconds a step under
the program's ``attn`` scope (``GPTAttention.forward``: the projections
and the core, Mosaic kernels or composed, forward and backward), averaged
over the devices, by ``harness/scopes.py``.  Counts the row ``attn`` and
any row that mixes ``attn`` with ``embed`` or ``mlp`` only; the kernels
are inside it, so it is the same thing at s1024 and at s128."""

from benchmarks.harness import scopes


def read(obs):
    return scopes.ms_per_step(
        obs, __file__,
        lambda blocks: "attn" in blocks and blocks <= {"attn", "embed", "mlp"})
