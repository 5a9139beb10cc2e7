"""Layer: model (``distributed/fleet/recompute``).  Device milliseconds a
step of the instructions that carry ``rematted_computation`` in their
``op_name`` and no ``optimizer``: the mixers' forward passes
``jax.checkpoint`` runs again in the backward pass, and the small pieces
that keep their input only (norms, gates, the delta rule's pairs inside a
sub-chunk).  An upper bound, as ``recompute_ms_per_step`` is; by the
second reader of ``harness/ssm_scopes.py``."""

from benchmarks.harness import ssm_scopes


def read(obs):
    return ssm_scopes.ms_per_step(obs, __file__, ssm_scopes.RECOMPUTED[:1],
                                  table="recompute_scopes") or None
