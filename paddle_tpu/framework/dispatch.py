"""One dispatch engine for single-chip and mesh training
(DESIGN-PERF.md §Unified dispatch engine).

PR 5 built the step-folding machinery — K logical train steps fused
into ONE compiled rolled ``lax.scan`` dispatch — inside ``Model.fit``'s
single-chip path.  This module extracts it so BOTH training paths run
the same engine:

- :func:`build_folded_step` compiles the shared scan program.  The
  caller supplies a pure ``per_step`` body (the single-chip step or the
  mesh step with its sharding constraints / gradient-accumulation
  microbatch scan); the engine owns everything the two paths must agree
  on — the donated ``(params, buffers, opt_state, metric_acc)`` carry,
  in-program per-step PRNG keys ``fold_in(base_key, ctr0 + i)``, the
  per-step ``(loss, metric stats)`` stacks, and the ROLLED scan whose
  body compiles identically for every fold length (which is what makes
  fold=K bit-identical to fold=1, trailing partials included).
- :class:`GroupDispatcher` owns the host side: buffering logical steps
  into fold groups, splitting at batch-shape changes, flushing through
  a caller-provided ``run_group``, and replaying per-logical-step
  callbacks via ``emit_group`` with ``LazyStack``-sliced views.
- :class:`AutoFoldTuner` replaces PR 5's hardcoded K=8: the first few
  groups run at K=1 with a calibration block that measures the
  host-overhead / device-step-time ratio, then K is chosen to cap host
  overhead at a target fraction of the group's device time — bounded,
  logged, and overridable via ``fit(steps_per_dispatch=...)``.

Knobs (all optional):

- ``PADDLE_TPU_FOLD_MAX``              upper bound on auto-K (def 32)
- ``PADDLE_TPU_FOLD_OVERHEAD_TARGET``  host-overhead budget as a
  fraction of per-step device time (def 0.05 — 5%)
- ``PADDLE_TPU_FOLD_CALIB_GROUPS``     measured calibration dispatches
  after the compile warmup (def 3)
"""

from __future__ import annotations

import functools
import logging
import math
import os
import time
from typing import Any, Callable, List, Optional, Tuple

from . import env_knobs
from .lazy import LazyStack
from ..observability import metrics as _obs_metrics
from ..observability import trace as _obs_trace

logger = logging.getLogger("paddle_tpu.dispatch")


def _observe_dispatch(n_steps: int, wall_s: float):
    """Always-on step-time profiling (DESIGN-OBSERVABILITY.md): every
    dispatch group records its host wall time and step count into the
    process-wide registry — host floats only, never a device value, so
    the hot loop stays sync-free.  Instruments are fetched from the
    registry per call (a dict hit under a lock) so a test-time
    ``registry().reset()`` cannot orphan them."""
    reg = _obs_metrics.registry()
    reg.counter("dispatch_groups_total",
                "compiled dispatch groups issued").inc()
    reg.counter("dispatch_steps_total",
                "logical train steps dispatched").inc(n_steps)
    reg.histogram("dispatch_wall_s",
                  "host wall time per dispatch group (dispatch + "
                  "callback replay; device work is async)"
                  ).observe(wall_s)


# -- retrace sentinel ------------------------------------------------------
#
# The dispatch-count discipline every headline win rides on (fold-K,
# the unified pp schedule, EQuARX dp) holds only if compiled entries
# are PROGRAM-STABLE: traced once, dispatched forever.  The silent
# failure mode is an equivalent-but-unequal input — a PartitionSpec
# with trailing Nones, a size-1 mesh axis normalized away by GSPMD, an
# uncommitted default-device array — that misses the jit cache and
# quietly retraces the whole program after dispatch 1 (the PR-11/PR-15
# recompile-pin bug class).  The sentinel turns the hand-written
# ``entries == 1, traces == 1`` pins into an ambient property: every
# program built through :func:`guarded_jit` counts its traces and
# dispatches, exports ``dispatch_retraces_total``, and — when strict
# mode is armed (``PADDLE_TPU_RETRACE_STRICT=1`` or the tests'
# ``retrace_strict`` fixture) — raises :class:`RetraceError` on any
# trace after the entry's first dispatch.


class RetraceError(RuntimeError):
    """A single-trace compiled entry re-traced after it had already
    dispatched — an equivalent-but-unequal input missed the jit cache
    (see DESIGN-ANALYSIS.md §Retrace sentinel)."""


class _GuardEntry:
    __slots__ = ("label", "single_trace", "traces", "dispatches")

    def __init__(self, label: str, single_trace: bool):
        self.label = label
        self.single_trace = single_trace
        self.traces = 0
        self.dispatches = 0


_guard_entries: List[_GuardEntry] = []
#: tri-state strict override: None = follow the env knob
_strict_override: Optional[bool] = None


def set_retrace_strict(flag: Optional[bool]) -> None:
    """Arm/disarm strict mode programmatically (tests); ``None``
    restores the ``PADDLE_TPU_RETRACE_STRICT`` env-knob default."""
    global _strict_override
    _strict_override = flag


def retrace_strict_enabled() -> bool:
    if _strict_override is not None:
        return _strict_override
    return env_knobs.get_bool("PADDLE_TPU_RETRACE_STRICT")


def retrace_report() -> List[dict]:
    """Per-entry (label, traces, dispatches) for introspection."""
    return [{"label": e.label, "single_trace": e.single_trace,
             "traces": e.traces, "dispatches": e.dispatches}
            for e in _guard_entries]


def _note_trace(entry: _GuardEntry) -> None:
    entry.traces += 1
    if entry.single_trace and entry.dispatches > 0:
        reg = _obs_metrics.registry()
        reg.counter("dispatch_retraces_total",
                    "traces of single-trace compiled entries after "
                    "their first dispatch (each one recompiles the "
                    "whole program mid-run)").inc()
        logger.warning(
            "retrace sentinel: %r traced again (trace %d) after %d "
            "dispatches — an equivalent-but-unequal input missed the "
            "jit cache", entry.label, entry.traces, entry.dispatches)
        if retrace_strict_enabled():
            raise RetraceError(
                f"compiled entry {entry.label!r} re-traced (trace "
                f"{entry.traces}) after {entry.dispatches} "
                f"dispatch(es).  Some input is equivalent-but-unequal "
                f"to the first dispatch's — a non-canonical "
                f"PartitionSpec, an uncommitted / differently-placed "
                f"array, or a weak-type flip (the PR-11/PR-15 "
                f"recompile-pin bug class).  Canonicalize the input "
                f"at the placement seam, or build the entry with "
                f"single_trace=False if its trace set is genuinely "
                f"open-ended.")


class GuardedProgram:
    """A jitted program wrapped with trace/dispatch accounting.  All
    jit attributes (``_cache_size`` et al.) delegate to the wrapped
    callable, so ``compile_stats()`` introspection is unchanged."""

    __slots__ = ("_fn", "entry")

    def __init__(self, fn, entry: _GuardEntry):
        self._fn = fn
        self.entry = entry

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        self.entry.dispatches += 1
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def guarded_jit(fun: Callable, label: str, single_trace: bool = True,
                **jit_kwargs) -> GuardedProgram:
    """``jax.jit`` with the retrace sentinel attached.

    ``single_trace=True`` declares the entry program-stable: its one
    legitimate trace happens on the first dispatch, and any later
    trace ticks ``dispatch_retraces_total`` (and raises under strict
    mode).  Entries whose trace set is legitimately open-ended
    (bucketed serving prefill, shape-polymorphic eval) pass
    ``single_trace=False`` to keep the accounting without the
    contract."""
    import jax

    entry = _GuardEntry(label, single_trace)
    _guard_entries.append(entry)
    # the counter exists (at 0) from the moment a guarded program is
    # built, so the retrace lane is scrape-visible before any trouble
    _obs_metrics.registry().counter(
        "dispatch_retraces_total",
        "traces of single-trace compiled entries after their first "
        "dispatch (each one recompiles the whole program mid-run)")

    @functools.wraps(fun)
    def traced(*args, **kwargs):
        _note_trace(entry)
        return fun(*args, **kwargs)

    return GuardedProgram(jax.jit(traced, **jit_kwargs), entry)


# -- the shared compiled program ------------------------------------------


def build_folded_step(per_step: Callable, fold: int,
                      donate_buffers: bool = True,
                      place_data: Optional[Callable] = None,
                      donate_carry: bool = True, **jit_kwargs):
    """ONE compiled program running ``fold`` train steps as a rolled
    ``lax.scan`` over batches stacked on a new leading axis.

    ``per_step(params, frozen, buffers, opt_state, lr, key, md)`` is
    the pure single-step body and must return
    ``(loss_f32, mstats_tuple, new_params, new_opt_state, new_buf)``;
    the engine threads the donated carry (params / buffers / opt_state
    / metric accumulators), derives per-step PRNG keys in-program from
    ``(base_key, ctr0 + i)`` — bit-identical to the key sequence the
    single-step entries consume — and accumulates metric stats by plain
    addition in the carry.

    The scan stays ROLLED on purpose: the loop body compiles once,
    identically for every fold length, so full groups (scan-of-K),
    trailing partials (scan-of-P) and K=1 (scan-of-1) all execute the
    same body and the end state is bit-identical for every grouping.

    ``place_data`` (mesh path) pins the stacked ``[K, ...]`` batch
    arrays to their data shardings inside the program, before the scan
    slices them.  ``donate_buffers=False`` keeps the buffers dict alive
    for callers whose cached value dicts alias it (DistributedRunner).
    ``donate_carry=False`` disables carry donation entirely — the
    explicit-dp (shard_map) mesh programs use it because this
    container's jaxlib corrupts donated buffers aliased through
    shard_map manual collectives (see DistributedRunner._build).
    ``jit_kwargs`` go to ``jax.jit`` as they are (the runner's
    compiler options on a multi-device TPU mesh: DESIGN-DCN.md).
    """
    import jax
    import jax.numpy as jnp

    def program(params, frozen, buffers, opt_state, macc, lr, base_key,
                ctr0, *data):
        if place_data is not None:
            data = place_data(data)

        def body(carry, xs):
            p, bufs, st, acc = carry
            i, md = xs
            key = jax.random.fold_in(base_key, ctr0 + i)
            loss_val, mstats, new_p, new_st, new_buf = per_step(
                p, frozen, bufs, st, lr, key, md)
            bufs = {**bufs, **new_buf}
            if mstats:
                acc = tuple(a + s for a, s in zip(acc, mstats))
            return (new_p, bufs, new_st, acc), (loss_val, mstats)

        idx = jnp.arange(fold, dtype=jnp.uint32)
        (new_params, new_buf, new_opt_state, new_acc), \
            (losses, mstacks) = jax.lax.scan(
                body, (params, dict(buffers), opt_state, macc),
                (idx, tuple(data)))
        return (losses, mstacks, new_acc, new_params, new_opt_state,
                new_buf)

    # the whole carry is donated — params/opt_state/accumulators update
    # in place across the K steps; buffers join the donation only where
    # the caller does not alias them (hapi TrainState does not, the
    # runner's cached value dicts do)
    if not donate_carry:
        donate = ()
    else:
        donate = (0, 2, 3, 4) if donate_buffers else (0, 3, 4)
    # every folded program is single-trace by contract: callers cache
    # one entry per (fold, batch signature), so a second trace of THIS
    # entry is always the silent-retrace bug class
    return guarded_jit(program, label=f"folded_step[fold={fold}]",
                       single_trace=True, donate_argnums=donate,
                       **jit_kwargs)


# -- auto-K ---------------------------------------------------------------


class AutoFoldTuner:
    """Pick the fold factor K from measured dispatch economics instead
    of a hardcoded constant.

    Protocol: the :class:`GroupDispatcher` runs its first
    ``1 + calib_groups`` dispatches at ``fold == 1``.  The first (the
    compile) is discarded; for each of the rest the dispatcher reports
    ``observe(n_steps, host_s, wait_s)`` — the host wall time spent
    dispatching + replaying callbacks, and the residual device wait
    measured by the calibration block.  K is then frozen at::

        K = clamp(ceil(host_per_step / (target * device_per_step)),
                  1, max_fold)

    i.e. the smallest K whose amortized per-step host overhead is at
    most ``target`` (default 5%) of the per-step device time.  A
    host-bound loop (device wait ~0 — exactly the small-model regime
    folding exists for) saturates at ``max_fold``; a device-bound loop
    (big model) stays at K=1 — folding would only delay callbacks.  The
    decision is logged and kept in ``self.decision`` for bench/test
    introspection.
    """

    def __init__(self, target: Optional[float] = None,
                 max_fold: Optional[int] = None,
                 calib_groups: Optional[int] = None):
        self.target = (target if target is not None else
                       env_knobs.get_float(
                           "PADDLE_TPU_FOLD_OVERHEAD_TARGET", 0.05))
        self.max_fold = max(1, max_fold if max_fold is not None else
                            env_knobs.get_int("PADDLE_TPU_FOLD_MAX",
                                              32))
        self.calib_groups = max(1, calib_groups if calib_groups
                                is not None else
                                env_knobs.get_int(
                                    "PADDLE_TPU_FOLD_CALIB_GROUPS", 3))
        self.fold = 1
        self.decided = False
        self.decision: Optional[dict] = None
        self._host: List[float] = []
        self._wait: List[float] = []
        self._seen_compile = False

    def observe(self, n_steps: int, host_s: float, wait_s: float):
        if self.decided or n_steps <= 0:
            return
        if not self._seen_compile:
            # the first dispatch traces + compiles the scan program;
            # its wall time says nothing about steady-state economics
            self._seen_compile = True
            return
        self._host.append(host_s / n_steps)
        self._wait.append(wait_s / n_steps)
        if len(self._host) >= self.calib_groups:
            self._decide()

    @staticmethod
    def _median(xs: List[float]) -> float:
        s = sorted(xs)
        return s[len(s) // 2]

    def _decide(self):
        host = self._median(self._host)
        step = self._median(self._wait)
        if step <= 0.0 or host > self.target * step * self.max_fold:
            # host-bound (or device time unmeasurably small): saturate
            k = self.max_fold
        else:
            k = max(1, math.ceil(host / (self.target * step)))
        self.fold = min(self.max_fold, k)
        self.decided = True
        self.decision = {
            "fold": self.fold,
            "host_ms_per_step": round(host * 1e3, 4),
            "device_ms_per_step": round(step * 1e3, 4),
            "overhead_target": self.target,
            "max_fold": self.max_fold,
            "calib_groups": self.calib_groups,
        }
        logger.info("auto-fold: host %.3f ms/step, device %.3f ms/step "
                    "-> steps_per_dispatch=%d (target %.0f%%, max %d)",
                    host * 1e3, step * 1e3, self.fold,
                    self.target * 100, self.max_fold)
        # calibration numbers used to die here (ISSUE 8 motivation);
        # now they land on the registry for any scrape to read
        reg = _obs_metrics.registry()
        reg.gauge("dispatch_auto_fold",
                  "auto-tuned steps_per_dispatch K").set(self.fold)
        reg.gauge("dispatch_host_ms_per_step",
                  "measured host overhead per step (calibration)"
                  ).set(round(host * 1e3, 4))
        reg.gauge("dispatch_device_ms_per_step",
                  "measured device time per step (calibration)"
                  ).set(round(step * 1e3, 4))


# -- host-side grouping ---------------------------------------------------


class GroupDispatcher:
    """Buffer logical train steps into fold groups and dispatch each
    group as ONE compiled scan program.

    ``run_group(groups)`` receives ``[(inputs, labels), ...]`` and must
    return ``(losses, mstacks)`` — per-step stacks (``LazyStack`` or
    device arrays) for callback replay.  ``emit_group(entries, losses,
    mstacks)`` replays the buffered per-logical-step callbacks in
    order; marker entries (``inputs is None`` — gradient-accumulation
    intermediates) carry no compute and replay in place so callbacks
    see a monotone step series.

    A batch-shape change (uneven trailing batch, bucketed loader)
    closes the open group — a group must stack along one leading axis —
    and the homogeneous prefix dispatches as scan-of-P over the same
    rolled body, so grouping never changes numerics.

    With a :class:`AutoFoldTuner` the first dispatches run at K=1 and
    carry the calibration probe; the tuned K applies from the moment it
    is decided.
    """

    def __init__(self, run_group: Callable, emit_group: Callable,
                 fold: int = 1, tuner: Optional[AutoFoldTuner] = None):
        self._run = run_group
        self._emit = emit_group
        self._fold = max(1, int(fold))
        self.tuner = tuner
        self._group: List[Tuple[int, Any, Any]] = []
        self._sig = None

    @property
    def fold(self) -> int:
        return self.tuner.fold if self.tuner is not None else self._fold

    @property
    def pending(self) -> bool:
        return bool(self._group)

    @staticmethod
    def _group_sig(inputs, labels):
        return tuple(tuple(v.shape) for v in (*inputs, *labels))

    def feed(self, step: int, inputs, labels):
        sig = self._group_sig(inputs, labels)
        n_logical = sum(1 for _, i, _l in self._group if i is not None)
        if self._group and sig != self._sig:
            # shape change: scan the homogeneous prefix now
            self.flush()
            n_logical = 0
        if not self._group:
            self._sig = sig
        self._group.append((step, inputs, labels))
        if n_logical + 1 >= self.fold:
            self.flush()

    def feed_marker(self, step: int):
        """Buffer an accumulate intermediate between logical steps so
        its callbacks replay in step order at the next flush."""
        self._group.append((step, None, None))

    def flush(self):
        """Dispatch the buffered group through ONE compiled scan, then
        replay the per-logical-step callbacks with index-sliced lazy
        values."""
        if not self._group:
            return
        entries, self._group = self._group, []
        logical = [(i, l) for _, i, l in entries if i is not None]
        if not logical:
            self._emit(entries, None, [])
            return
        tuner = self.tuner
        sp = _obs_trace.span(
            "dispatch.group",
            args=({"steps": len(logical), "fold": self.fold}
                  if _obs_trace.enabled() else None))
        if tuner is not None and not tuner.decided:
            with sp:
                t0 = time.perf_counter()
                losses, mstacks = self._run(logical)
                t1 = time.perf_counter()
                self._calibration_block(losses)
                t2 = time.perf_counter()
                self._emit(entries, losses, mstacks)
                t3 = time.perf_counter()
            tuner.observe(len(logical), (t1 - t0) + (t3 - t2), t2 - t1)
            _observe_dispatch(len(logical), t3 - t0)
            return
        t0 = time.perf_counter()
        with sp:
            losses, mstacks = self._run(logical)
            self._emit(entries, losses, mstacks)
        _observe_dispatch(len(logical), time.perf_counter() - t0)

    @staticmethod
    def _calibration_block(losses):
        """Calibration-only device wait: block on the group's loss
        stack so the tuner can split host overhead from device step
        time.  Runs during the first ``calib_groups`` dispatches of an
        auto-tuned fit ONLY — the steady-state hot loop never blocks
        (the host-sync guard whitelists exactly this function)."""
        if isinstance(losses, LazyStack):
            losses.block()
        elif losses is not None:
            import jax
            jax.block_until_ready(losses)
