"""Layer: model (``distributed/fleet/recompute``).  Device milliseconds a
step of the instructions that carry ``rematted_computation`` in their
``op_name`` and no ``optimizer``: the forward passes ``jax.checkpoint``
runs again in the backward pass.  A weight gradient fused with its update
reads a recomputed activation and is left out (its row is
``rematted_computation+optimizer``); any other fusion that mixes
recomputed members with the backward pass's own counts whole, so this is
an upper bound; by the second reader of ``harness/ssm_scopes.py``.
Nothing to read where the step recomputes nothing."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ssm_scopes.RECOMPUTED[:1],
                                  table="recompute_scopes") or None
