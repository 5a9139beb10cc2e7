"""Device time by part of a SambaY layer: the nine sub-scopes
``models/sambay.py`` opens inside ``attn``.  ``ssm_proj`` (a Mamba-1
mixer's projections: in, the low-rank step and B, C, out) and ``ssm_conv``
(the causal convolution and its SiLU); ``s6_scan`` (the step sizes and the
selective scan with a decay a channel and state); ``gmu`` (a gated memory
unit's two projections and its gate); ``attn_proj`` (an attention layer's
query, key and value and output projections); ``swa_core``, ``full_core``
and ``cross_core`` (the four ``flash_attention`` calls of a window layer,
of the K/V producer and of a cross layer); ``diff_combine`` (the
difference of the two maps' outputs, its norm and scale).  They reach the
compiled step as further parts of an instruction's ``op_name``
(``jit(step)/jvp(attn)/s6_scan/...``).

One reader knows all nine, so that a fusion mixed of two of them is a
row of its own and counts for neither metric.  What ``jax.checkpoint``
runs again is read by the second reader of ``harness/ssm_scopes.py``.

The join is ``scopes.py``'s, by ``subscopes._reader_for``: a further copy
of that file under these names, with its own table of the run.
"""

from __future__ import annotations

from typing import Iterable, Optional

from . import subscopes

SUBSCOPES = ("ssm_proj", "ssm_conv", "s6_scan", "gmu", "attn_proj",
             "swa_core", "full_core", "cross_core", "diff_combine")
TABLE = "sambay_scopes"

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


def roofline(obs: dict, metric_file: str, names: Iterable[str],
             cost: str) -> Optional[float]:
    """The least time a chip could take for what the family's function
    ``cost`` counts (the larger of operations over peak FLOP/s and bytes
    over peak bytes/s), as per cent of the device time of ``names``."""
    from .cells import least_seconds
    ms = ms_per_step(obs, metric_file, names)
    needs = getattr(obs.get("family"), cost, None)
    if not ms or needs is None:
        return None
    needs = needs(obs["config"], obs["traffic"]["batch"],
                  obs["traffic"]["seq_len"])
    least, _ = least_seconds(needs["flops"] / obs["chips"],
                             needs["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / (ms / 1e3)
