"""Device time by part of a block of a hybrid of state-space, attention
and expert blocks: the ten sub-scopes ``models/nemotron_h.py`` and the
code it calls open.  Inside ``attn``: ``ssm_proj`` (a Mamba-2 mixer's two
projections), ``ssm_conv`` (the causal convolution, its SiLU and the
split), ``ssm_scan`` (the step sizes and the selective scan), ``ssm_norm``
(the gated RMSNorm by group) and ``gqa_core`` (the attention block's
core).  Inside ``mlp``: ``router`` (logits, sigmoid, top-k, gates),
``dispatch`` (the plan and the gather into expert order), ``experts`` (the
grouped products and the squared ReLU between them), ``combine`` (the
weighted gather back) and ``shared_expert`` (the expert every token
visits).  They reach the compiled step as further parts of an
instruction's ``op_name`` (``jit(step)/jvp(mlp)/shared_expert/...``).

One reader knows all ten, so that a fusion mixed of a state-space and an
expert sub-scope is a row of its own (``ssm_scan+router``) and counts for
neither metric; ``harness/ssm_scopes.py``'s reader would give such a row
to its state-space part and ``harness/subscopes.py``'s to its expert
part.  What ``jax.checkpoint`` runs again is read by the second reader
of ``harness/ssm_scopes.py``.

The join is ``scopes.py``'s, by ``subscopes._reader_for``: a further copy
of that file under these names, with its own table of the run.
"""

from __future__ import annotations

from typing import Iterable, Optional

from . import subscopes

SUBSCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_norm", "gqa_core",
             "router", "dispatch", "experts", "combine", "shared_expert")
TABLE = "hybrid_moe_scopes"

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


def unscoped_group_ms_per_step(obs: dict, metric_file: str,
                               prefix: str) -> Optional[float]:
    """Device milliseconds a step of the instructions outside every
    sub-scope whose group (``trace_reduce.op_group``) starts with
    ``prefix``: the ``ragged-dot`` custom calls XLA makes of
    ``jax.lax.ragged_dot`` carry no ``op_name``."""
    if ms_per_step(obs, metric_file, ()) is None:
        return None
    table = obs[TABLE]["scopes"]
    return 1e3 * sum(seconds for row in table.rows
                     if row.name == reader.UNSCOPED
                     for group, seconds in row.groups.items()
                     if group.startswith(prefix))
