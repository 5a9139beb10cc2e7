"""Layer: model (``models/gpt.py``).  Device milliseconds a step under
the program's ``head`` scope (the logits matmul against the tied
embedding), its ``loss`` scope (``GPTPretrainingCriterion.forward``) or
both, forward and backward, averaged over the devices, by
``harness/scopes.py``: the rows ``head``, ``loss`` and ``head+loss``."""

from benchmarks.harness import scopes


def read(obs):
    return scopes.ms_per_step(
        obs, __file__,
        lambda blocks: bool(blocks) and blocks <= {"head", "loss"})
