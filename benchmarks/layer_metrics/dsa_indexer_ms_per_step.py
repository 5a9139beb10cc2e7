"""Layer: model (``models/keye_lm.py``, ``ops/sparse_attention.py``).
Device milliseconds a step of the learned selection: the sub-scopes
``indexer`` (index scores), ``select`` (the exact top-k mask) and
``indexer_kl`` (head-averaged probabilities, the indexer's loss and its
gradient), forward and backward, by ``harness/subscopes.py``."""

from benchmarks.harness import subscopes


def read(obs):
    return subscopes.ms_per_step(obs, __file__,
                                 ("indexer", "select", "indexer_kl"))
