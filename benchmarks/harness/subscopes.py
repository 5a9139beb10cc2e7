"""Device time by part of a block: the sub-scopes a model opens inside
the six scopes ``scopes.py`` knows.

``models/keye_lm.py`` and the code it calls open, inside ``attn``:
``indexer`` (the index scores), ``select`` (the exact top-k mask) and
``sparse_core`` (attention over the selection); inside ``loss``:
``indexer_kl`` (the head-averaged probabilities, the indexer's loss and
its gradient); inside ``mlp``: ``router``, ``dispatch`` (the plan and the
gather into expert order), ``experts`` (the grouped products) and
``combine``.  They reach the compiled step as further parts of an
instruction's ``op_name`` (``jit(step)/jvp(attn)/sparse_core/...``).

The join is ``scopes.py``'s, rule for rule, and its code: this module
loads a second copy of that file and gives the copy these names in
place of the six, so the two readers cannot drift apart and the one
that is there is not edited.  A row is named by the sub-scopes an
instruction (and what it calls) carries; everything outside them is
``unscoped`` here.  Each reader keeps its own table of the run.
"""

from __future__ import annotations

import importlib.util
import re
import sys
from typing import Iterable, Optional

from . import scopes

SUBSCOPES = ("indexer", "select", "sparse_core", "indexer_kl", "router",
             "dispatch", "experts", "combine")


def _reader_for(names):
    spec = importlib.util.spec_from_file_location(
        scopes.__name__ + "_of_subscopes", scopes.__file__)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module       # dataclasses look their module up
    spec.loader.exec_module(module)
    module.SCOPES = tuple(names)
    module.SCOPE = re.compile(
        scopes.SCOPE.pattern.replace("|".join(scopes.SCOPES),
                                     "|".join(names)))
    return module


reader = _reader_for(SUBSCOPES)


def ms_per_step(obs: dict, metric_file: str,
                names: Iterable[str]) -> Optional[float]:
    """Device milliseconds a step of the rows made of ``names`` only, or
    None where the run has no table (no trace, no device, a program
    without these scopes)."""
    names = frozenset(names)
    view = obs.setdefault("subscopes", {"trace": obs.get("trace"),
                                        "chips": obs.get("chips")})
    return reader.ms_per_step(
        view, metric_file, lambda blocks: bool(blocks) and blocks <= names)


def unscoped_group_ms_per_step(obs: dict, metric_file: str,
                               prefix: str) -> Optional[float]:
    """Device milliseconds a step of the instructions outside every
    sub-scope whose group (``trace_reduce.op_group``) starts with
    ``prefix``: for kernels the compiler names itself and gives no
    ``op_name``, as it does the ``ragged-dot`` custom calls it makes of
    ``jax.lax.ragged_dot``."""
    if ms_per_step(obs, metric_file, ()) is None:
        return None
    table = obs["subscopes"]["scopes"]
    return 1e3 * sum(seconds for row in table.rows
                     if row.name == reader.UNSCOPED
                     for group, seconds in row.groups.items()
                     if group.startswith(prefix))
