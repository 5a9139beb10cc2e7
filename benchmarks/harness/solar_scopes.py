"""Device time by part of a Solar Open 2 layer: the nine sub-scopes
``models/solar_open2.py`` and the code it calls open.  Inside ``attn``:
``kda_proj`` (a KDA mixer's q, k, v and output projections),
``kda_conv_gate`` (the three short convolutions with their SiLU, the q/k
normalisation, the decay gate and beta, the output norm and its gate),
``kda_core`` (the delta rule, forward and backward) and ``gqa_core`` (the
GQA layer's flash calls).  Inside ``mlp``: ``router`` (float32 logits,
sigmoid, top-k over ``s + b``, gates, the balancing rule), ``dispatch``
(the plan and the gather into expert order), ``experts`` (the grouped
products and the gate between them), ``shared_expert`` (the expert every
token visits) and ``combine`` (the way back to the tokens).  They reach
the compiled step as further parts of an instruction's ``op_name``
(``jit(step)/jvp(attn)/kda_core/...``).

One reader knows all nine, so that a fusion mixed of two of them is a
row of its own and counts for neither metric.  What ``jax.checkpoint``
runs again is read by the second reader of ``harness/ssm_scopes.py``.

The join is ``scopes.py``'s, by ``subscopes._reader_for``: a further copy
of that file under these names, with its own table of the run.
"""

from __future__ import annotations

from typing import Iterable, Optional

from . import subscopes

SUBSCOPES = ("kda_proj", "kda_conv_gate", "kda_core", "gqa_core", "router",
             "dispatch", "experts", "shared_expert", "combine")
TABLE = "solar_scopes"

reader = subscopes._reader_for(SUBSCOPES)


def ms_per_step(obs: dict, metric_file: str,
                names: Iterable[str]) -> Optional[float]:
    """Device milliseconds a step of the rows made of ``names`` only, or
    None where the run has no table (no trace, no device, a program
    without these scopes)."""
    names = frozenset(names)
    view = obs.setdefault(TABLE, {"trace": obs.get("trace"),
                                  "chips": obs.get("chips")})
    return reader.ms_per_step(
        view, metric_file, lambda blocks: bool(blocks) and blocks <= names)
