"""The one generator of training traffic.  A mix is a data file of its
parameters (``benchmarks/traffic/<mix>.json``):

    batch        sequences in one optimizer step, over all chips
    seq_len      tokens in a sequence
    ring         distinct host batches made before the window and cycled
    tokens       {"distribution": "uniform"}: ids uniform over the
                 vocabulary.  Enough for a dense model, whose work does
                 not depend on the data.
    sync_every   steps dispatched back to back between two reads of the
                 loss, as a user's loop logs
    kernels      "required": the compiled step must hold the attention
                 kernels or the run is wrong; "any": their count is
                 printed and decides nothing
    rehearsal    the same keys at a toy size, for the CPU rehearsal

The same seed gives the same batches.
"""

from __future__ import annotations

import numpy as np


def token_batches(traffic: dict, vocab_size: int, seed: int):
    """``ring`` pairs ``([ids], [labels])`` of int64 ``[batch, seq_len]``
    numpy arrays; the labels are the ids shifted by one."""
    dist = traffic["tokens"]["distribution"]
    if dist != "uniform":
        raise ValueError(f"token distribution {dist!r}: the generator "
                         "draws 'uniform' only")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(int(traffic["ring"])):
        ids = rng.integers(0, vocab_size,
                           (int(traffic["batch"]), int(traffic["seq_len"])),
                           dtype=np.int64)
        out.append(([ids], [np.roll(ids, -1, axis=1)]))
    return out
