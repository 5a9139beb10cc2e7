"""Device time by part of an LFM2-MoE layer: the nine sub-scopes
``models/lfm2_moe.py`` and the code it calls open.  Inside ``attn``:
``conv_proj`` (a convolution operator's two projections), ``short_conv``
(the double-gated short convolution between them, forward and backward),
``attn_proj`` (an attention layer's four projections, the q/k norm a head
and the rotary) and ``gqa_core`` (the flash calls and the K/V repeat).
Inside ``mlp``: ``dense_mlp`` (a leading dense layer's SiLU-gated MLP),
``router`` (float32 logits, sigmoid, top-k over ``s + b``, gates, the
balancing rule), ``dispatch`` (the plan and the gather into expert order),
``experts`` (the grouped products and the gate between them) and
``combine`` (the weighted gather back).  They reach the compiled step as
further parts of an instruction's ``op_name``
(``jit(step)/jvp(attn)/short_conv/...``).

One reader knows all nine, so that a fusion mixed of two of them is a
row of its own and counts for neither metric; the operator's roofline
asks for such rows too (``mixed``), so that no fusion takes time out of
its denominator.  What ``jax.checkpoint`` runs again is read by the
second reader of ``harness/ssm_scopes.py``.

The join is ``scopes.py``'s, by ``subscopes._reader_for``: a further copy
of that file under these names, with its own table of the run.
"""

from __future__ import annotations

from typing import Iterable, Optional

from . import subscopes

SUBSCOPES = ("conv_proj", "short_conv", "attn_proj", "gqa_core", "dense_mlp",
             "router", "dispatch", "experts", "combine")
TABLE = "lfm2_scopes"

reader = subscopes._reader_for(SUBSCOPES)


def ms_per_step(obs: dict, metric_file: str, names: Iterable[str],
                mixed: bool = False) -> Optional[float]:
    """Device milliseconds a step of the rows made of ``names`` only (or,
    ``mixed``, of every row that holds one of them), or None where the run
    has no table (no trace, no device, a program without these scopes)."""
    names = frozenset(names)
    view = obs.setdefault(TABLE, {"trace": obs.get("trace"),
                                  "chips": obs.get("chips")})
    return reader.ms_per_step(
        view, metric_file, (lambda blocks: bool(blocks & names)) if mixed
        else (lambda blocks: bool(blocks) and blocks <= names))


def roofline(obs: dict, ms: Optional[float], cost: str) -> Optional[float]:
    """The least time a chip could take for what the family's function
    ``cost`` counts of one step (the larger of operations over peak
    FLOP/s and bytes over peak bytes/s), as per cent of ``ms``."""
    from .cells import least_seconds
    needs = getattr(obs.get("family"), cost, None)
    if not ms or needs is None:
        return None
    needs = needs(obs["config"], obs["traffic"]["batch"],
                  obs["traffic"]["seq_len"])
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
