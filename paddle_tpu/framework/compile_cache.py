"""Persistent XLA compilation cache: the one place that decides where it
lives.

A process that compiles the same programs as the one before it — a
restarted server, the next run of ``chip_smoke.py`` or ``bench.py`` —
loads executables from disk instead of recompiling.  The cache's path
is part of its key, so it must not move:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
  cache.  jax reads the variable itself; no code here or elsewhere in
  the repo sets another directory;
- where it is not, the cache is ``<checkout>/.jax_compile_cache``
  (gitignored).  The tests pass their own fixed default,
  ``tests/.jax_compile_cache``.

``PADDLE_TPU_COMPILE_CACHE`` is on/off only: ``1`` enables the cache
at ``paddle_tpu`` import (and at ``LLMServer`` construction); unset,
``0`` or empty leaves jax's default (off unless the variable above is
set).  Programs that always want it — ``chip_smoke.py``, the tests —
call :func:`enable_compilation_cache` themselves.  Thresholds are
dropped to zero so even the tiny serving decode programs persist —
jax's default heuristics only cache "expensive" compiles, which is
backwards for a server whose cold start is the sum of many small ones.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "PADDLE_TPU_COMPILE_CACHE"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_active_dir: Optional[str] = None


def cache_dir(default_dir: Optional[str] = None) -> str:
    """Where the cache lives under the rule above."""
    return os.environ.get(JAX_ENV_VAR) or os.path.abspath(
        default_dir or os.path.join(_CHECKOUT, ".jax_compile_cache"))


def active_cache_dir() -> Optional[str]:
    """The directory compilation results persist to (None = this
    module has not enabled the cache)."""
    return _active_dir


def enable_compilation_cache(default_dir: Optional[str] = None) -> str:
    """Turn jax's persistent compilation cache on at :func:`cache_dir`.
    ``default_dir`` is used only where ``JAX_COMPILATION_CACHE_DIR`` is
    unset.  Idempotent; returns the active directory."""
    global _active_dir
    d = cache_dir(default_dir)
    if _active_dir == d:
        return d
    import jax
    if not os.environ.get(JAX_ENV_VAR):
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_enable_compilation_cache", True)
    # persist EVERYTHING: a serving cold-start is many small compiles,
    # each individually below the default "worth caching" thresholds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _active_dir = d
    return d


def enable_from_env() -> Optional[str]:
    """Honor ``PADDLE_TPU_COMPILE_CACHE`` (see module doc).  Returns
    the active dir, or None when nothing has enabled the cache."""
    from . import env_knobs
    if env_knobs.get_bool(ENV_VAR):
        return enable_compilation_cache()
    return _active_dir
