"""Central registry of every ``PADDLE_TPU_*`` environment knob.

One module owns the full catalog — name, default, one-line doc — so
the set of knobs is discoverable (``python -c "from
paddle_tpu.framework import env_knobs; print(env_knobs.render_table())"``),
the README table is generated from it (``python scripts/lint.py
--write-env-table``), and the ``env-knobs`` analysis pass
(``scripts/analysis/env_knobs_pass.py``) can enforce that

* every read of a ``PADDLE_TPU_*`` variable anywhere in the package
  resolves through this registry (direct ``os.environ`` reads of the
  prefix are violations), and
* every registered knob is actually wired to a consumer — a registry
  entry nothing reads is documentation rot in the making.

The module is deliberately stdlib-only (no jax, no package imports):
the lint framework loads it straight from this file, and import-time
consumers (``observability/__init__.py``) must not pay for anything.

Call-site parsing stays at the call site on purpose: knobs like
``PADDLE_TPU_DP_COMPRESS`` ("8"/"int8"/"exact16"...) have bespoke
grammars and bespoke error messages that belong next to the feature.
What the
registry centralizes is the *name*, the *documented default*, and the
*doc line* — the three things that rot when scattered.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional


class Knob(NamedTuple):
    name: str      # full variable name, PADDLE_TPU_ prefix included
    default: str   # documented default, as rendered in the README
    kind: str      # bool | int | float | str — how consumers parse it
    doc: str       # one line


KNOBS: Dict[str, Knob] = {}


def _k(name: str, default: str, kind: str, doc: str) -> None:
    assert name.startswith("PADDLE_TPU_"), name
    assert name not in KNOBS, name
    KNOBS[name] = Knob(name, default, kind, doc)


# -- kernels (ops/pallas_ops.py) --------------------------------------------
_k("PADDLE_TPU_PALLAS_INTERPRET", "off", "bool",
   "Run Pallas kernels in interpreter mode so CPU tests exercise the "
   "actual kernel code, not just the composed fallback.")
_k("PADDLE_TPU_DISABLE_PALLAS", "off", "bool",
   "Force the composed JAX fallback for every Pallas kernel.")

# -- datasets ---------------------------------------------------------------
_k("PADDLE_TPU_SYNTH_N", "dataset-native size", "int",
   "Row count for synthetic fallback datasets (MNIST/CIFAR/text) when "
   "the real archives are absent.")

# -- observability ----------------------------------------------------------
_k("PADDLE_TPU_TRACE", "off", "bool",
   "Arm the span recorder at import, before any instrumented module "
   "dispatches.")
_k("PADDLE_TPU_TRACE_CAPACITY", "0 (default ring)", "int",
   "Span ring capacity when PADDLE_TPU_TRACE is armed.")
_k("PADDLE_TPU_EVENTS_CAPACITY", "0 (default 256)", "int",
   "Decision-ring capacity for the observability action loop.")
_k("PADDLE_TPU_METRICS_PORT", "0 (disarmed)", "int",
   "Metrics-plane base port: the controller serves on base, rank r on "
   "base+1+r.")

# -- compile cache / dispatch engine (framework/) ---------------------------
_k("PADDLE_TPU_COMPILE_CACHE", "off", "bool",
   "Persistent XLA compile cache on/off. Its place is "
   "JAX_COMPILATION_CACHE_DIR where set, else "
   "<checkout>/.jax_compile_cache.")
_k("PADDLE_TPU_FOLD_OVERHEAD_TARGET", "0.05", "float",
   "Auto-fold tuner: target host-overhead fraction per dispatch "
   "group.")
_k("PADDLE_TPU_FOLD_MAX", "32", "int",
   "Auto-fold tuner: upper bound on the fold factor K.")
_k("PADDLE_TPU_FOLD_CALIB_GROUPS", "3", "int",
   "Auto-fold tuner: calibration dispatches before K is decided.")
_k("PADDLE_TPU_RETRACE_STRICT", "off", "bool",
   "Arm the retrace sentinel: any trace of a single-trace compiled "
   "entry after its first dispatch raises RetraceError (tests arm "
   "this via the retrace_strict fixture).")

# -- serving (inference/serving/) -------------------------------------------
_k("PADDLE_TPU_SERVING_POLL_TARGET", "0.05", "float",
   "Decode loop: target host-overhead fraction for the done-poll "
   "auto-tuner.")
_k("PADDLE_TPU_SERVING_POLL_MAX", "64", "int",
   "Decode loop: max dispatches between done-mask polls.")
_k("PADDLE_TPU_SERVING_POLL_CALIB", "3", "int",
   "Decode loop: calibration groups for the done-poll auto-tuner.")
_k("PADDLE_TPU_PREFILL_CHUNK", "off", "int",
   "Chunked prefill: chunk length in tokens (multiple of the KV "
   "block size; 0/empty = whole-prompt prefill).")
_k("PADDLE_TPU_PREFIX_CACHE", "off", "bool",
   "Enable the shared-prefix KV cache for prefill reuse.")
_k("PADDLE_TPU_PAGED_ATTENTION", "auto", "str",
   "Decode-attention implementation: gather | pallas | auto (pallas "
   "on TPU backends, gather elsewhere).")
_k("PADDLE_TPU_SPEC_K", "4", "int",
   "Speculative decoding: draft tokens proposed per decode dispatch "
   "(active only when the engine is given draft weights).")

# -- hapi fit loop ----------------------------------------------------------
_k("PADDLE_TPU_FIT_WATCHDOG", "on", "bool",
   "Hang watchdog around Model.fit (0/false/no disarms it).")
_k("PADDLE_TPU_FIT_WATCHDOG_TIMEOUT_S", "1800", "float",
   "Fit watchdog timeout in seconds.")

# -- program transforms / native helpers ------------------------------------
_k("PADDLE_TPU_NO_DY2STATIC", "off", "bool",
   "Disable the dy2static AST rewrite (run decorated functions "
   "as-is).")
_k("PADDLE_TPU_DISABLE_NATIVE", "off", "bool",
   "Skip building/loading the native C++ helper library.")
_k("PADDLE_TPU_EXTENSION_DIR", "~/.cache/paddle_tpu_extensions", "str",
   "Build/cache root for user C++ extensions (utils.cpp_extension).")

# -- explicit-dp engine (distributed/runner.py) -----------------------------
_k("PADDLE_TPU_DP_COMPRESS", "off", "str",
   "Explicit-dp gradient compression: 0/off, 8/int8 ring, 16/exact16 "
   "ring (overrides the strategy knob).")
_k("PADDLE_TPU_DP_SHARD_UPDATE", "off", "bool",
   "Explicit-dp sharded weight update (ZeRO-style) override.")
_k("PADDLE_TPU_DP_DONATE", "off", "bool",
   "Opt the explicit-dp path back into carry donation (off by "
   "default: shard_map donation caveat, DESIGN-DCN.md).")

# -- checkpoint digests -----------------------------------------------------
_k("PADDLE_TPU_CKPT_DIGEST_CHUNK_MB", "64", "float",
   "Checkpoint manifest digest chunk size in MB (0 = whole-file "
   "digests).")
_k("PADDLE_TPU_CKPT_DIGEST_SAMPLE_CHUNKS", "0 (all chunks)", "int",
   "Cap how many chunks of a large checkpoint file are digested "
   "(sampling is opt-in).")

# -- pipeline engine --------------------------------------------------------
_k("PADDLE_TPU_PP_DISPATCH", "auto", "str",
   "Pipeline dispatch engine: auto/unified (fold-K scheduler) or "
   "legacy (per-batch jit parity reference).")
_k("PADDLE_TPU_PP_UNROLL_TICKS", "auto", "str",
   "Tick-loop form for the unified pipeline program: auto (unroll on "
   "hybrid meshes only), 1/0 force.")

# -- launch controller ------------------------------------------------------
_k("PADDLE_TPU_STRAGGLER_FACTOR", "2.0", "float",
   "Straggler detector threshold: flag ranks slower than factor x "
   "fleet median.")
_k("PADDLE_TPU_DRAIN_STRAGGLERS", "0 (attribution only)", "int",
   "Consecutive straggler windows before the controller drains a "
   "rank (0 = never drain).")
_k("PADDLE_TPU_NODE_LEASE_TIMEOUT", "3.0", "float",
   "Multi-host mode: seconds a host agent's lease may freeze before "
   "the controller declares node death.")


_TRUTHY = ("1", "true", "yes", "on")


def get_raw(name: str, default=None, env=None) -> Optional[str]:
    """The raw env value for a *registered* knob, or ``default``.

    ``env`` is an optional mapping standing in for ``os.environ``
    (the observability HTTP plane resolves ports against captured
    launch environments).  Unregistered names raise ``KeyError`` —
    that is the point of the registry."""
    if name not in KNOBS:
        raise KeyError(
            f"{name} is not a registered PADDLE_TPU knob; add it to "
            "paddle_tpu/framework/env_knobs.py (the env-knobs lint "
            "pass enforces this)")
    src = os.environ if env is None else env
    val = src.get(name)
    return default if val is None else val


def get_bool(name: str, default: bool = False, env=None) -> bool:
    """Strict truthy parse: {1, true, yes, on} (case-insensitive)."""
    raw = get_raw(name, env=env)
    if raw is None or not str(raw).strip():
        return default
    return str(raw).strip().lower() in _TRUTHY


def get_int(name: str, default: int = 0, env=None) -> int:
    try:
        return int(get_raw(name, env=env) or default)
    except ValueError:  # malformed knob must never kill an import
        return default


def get_float(name: str, default: float = 0.0, env=None) -> float:
    try:
        return float(get_raw(name, env=env) or default)
    except ValueError:
        return default


def render_table() -> str:
    """The README env-knob table (kept fresh by the env-knobs pass;
    regenerate with ``python scripts/lint.py --write-env-table``)."""
    rows = ["| Variable | Default | Description |",
            "| --- | --- | --- |"]
    for knob in KNOBS.values():
        rows.append(f"| `{knob.name}` | {knob.default} | {knob.doc} |")
    return "\n".join(rows) + "\n"
