"""Layer: optimizer (``optimizer/optimizer.py``, the step body of
``distributed/runner.py``).  Device milliseconds a step of every row that
holds the program's ``optimizer`` scope (``apply_gradients_tree``,
clipping inside it, and the re-pinning of the new parameters), averaged
over the devices, by ``harness/scopes.py``.  A weight gradient that XLA
fused with its AdamW update is the optimizer's: the rows
``attn+optimizer`` and ``mlp+optimizer`` are counted here and nowhere
else, and the table said on an earlier line shows how much sits in them."""

from benchmarks.harness import scopes


def read(obs):
    return scopes.ms_per_step(
        obs, __file__, lambda blocks: "optimizer" in blocks)
