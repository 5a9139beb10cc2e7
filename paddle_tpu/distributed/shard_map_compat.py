"""The one spelling of ``shard_map`` in-repo call sites use: jax's
top-level ``jax.shard_map`` with the ``check_vma`` keyword (the
installed jax is 0.9.0; ``jax.experimental.shard_map`` and its
``check_rep`` are its predecessor's)."""

from __future__ import annotations

from jax import shard_map
from jax.lax import axis_size

__all__ = ["shard_map", "axis_size"]
