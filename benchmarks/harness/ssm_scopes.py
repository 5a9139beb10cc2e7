"""Device time by part of a hybrid state-space block: the sub-scopes
``models/granite_hybrid.py`` opens inside ``attn``: ``ssm_proj`` (a
Mamba-2 mixer's two projections), ``ssm_conv`` (the causal convolution,
its SiLU and the split), ``ssm_scan`` (the step sizes and the selective
scan), ``ssm_norm`` (the gated RMSNorm) and ``gqa_core`` (the attention
layer's core).  They reach the compiled step as further parts of an
instruction's ``op_name`` (``jit(step)/jvp(attn)/ssm_scan/...``).

A second reader names what ``jax.checkpoint`` runs again: in the
backward pass the forward of a recomputed layer is traced under
``rematted_computation``, so an instruction (or a fusion with a member)
that carries that name is the recomputation's.  Its second name is
``optimizer``: a weight gradient fused with its AdamW update reads a
recomputed activation through a member of that name, and goes to the row
``rematted_computation+optimizer``, which is the backward pass's and the
optimizer's, not the recomputation's.  A fusion that mixes recomputed
members with the backward pass's own and no update is still counted
whole: the reading is an upper bound.  ``ops/ssm.py``'s backward makes
its chunk matrices again by itself, outside ``jax.checkpoint``, and is
not in it.

The join is ``scopes.py``'s, by ``subscopes._reader_for``: further copies
of that file under these names, each with its own table of the run.
"""

from __future__ import annotations

from typing import Iterable, Optional

from . import subscopes

SUBSCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_norm", "gqa_core")
RECOMPUTED = ("rematted_computation", "optimizer")

readers = {"ssm_scopes": subscopes._reader_for(SUBSCOPES),
           "recompute_scopes": subscopes._reader_for(RECOMPUTED)}


def ms_per_step(obs: dict, metric_file: str, names: Iterable[str],
                table: str = "ssm_scopes") -> Optional[float]:
    """Device milliseconds a step of the rows made of ``names`` only, by
    the reader ``table``, or None where the run has no table (no trace,
    no device, a program without these scopes)."""
    names = frozenset(names)
    view = obs.setdefault(table, {"trace": obs.get("trace"),
                                  "chips": obs.get("chips")})
    return readers[table].ms_per_step(
        view, metric_file, lambda blocks: bool(blocks) and blocks <= names)
