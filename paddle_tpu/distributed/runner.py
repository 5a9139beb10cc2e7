"""DistributedRunner: one jitted train step over the global Mesh.

This is the TPU replacement for the whole of upstream's distributed
execution machinery — Reducer buckets, ShardingOptimizer passes,
FleetExecutor (SURVEY.md §2.1) — collapsed into sharding placement +
one XLA compile:

* dp / sharding axes: batch sharded on ('dp','sharding'); the gradient
  all-reduce (dp) or reduce-scatter (ZeRO-2) is emitted by XLA from the
  placement of grads/optimizer state.
* mp axis: parameters carry PartitionSpecs from the mp layers; the
  Megatron collectives emerge from SPMD propagation.
* ZeRO stage 1/2/3 (GroupSharded parity): stage 1 shards optimizer
  state, stage 2 additionally constrains grads, stage 3 shards the
  params themselves — all expressed as NamedShardings, implementing the
  cross-replica weight-update sharding of PAPERS.md entry 4.

Used by fleet-driven training loops, __graft_entry__.dryrun_multichip,
and bench.py.
"""

from __future__ import annotations

import os
import time
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tensor import Tensor
from ..nn import functional_call as F
from ..framework import random as _random
from ..io.staging import to_device_values, stack_to_device
from . import collective as coll
from . import step_schedule as _schedule
from .fleet.meta_parallel.sharding_parallel import shard_spec_for
from .resilience import elastic_rank as _elastic
from .resilience import faults as _faults
from .resilience import watchdog as _watchdog
from ..framework import env_knobs
from ..observability import events as _obs_events
from ..observability import host_events as _host_events
from ..observability import metrics as _obs_metrics
from ..observability import trace as _obs_trace


def _observe_mesh_steps(n_steps: int, wall_s: float):
    """Always-on mesh dispatch profiling: host wall time + step count
    per compiled dispatch (host floats only — no device sync)."""
    reg = _obs_metrics.registry()
    reg.counter("mesh_steps_total",
                "logical train steps dispatched on the mesh"
                ).inc(n_steps)
    reg.histogram("mesh_dispatch_wall_s",
                  "host wall time per mesh dispatch (device work is "
                  "async)").observe(wall_s)
    # per-rank step pace as a first-class level metric: the fleet
    # scrape reads it off every rank's /metrics, cross-checking the
    # controller's beacon-derived straggler attribution with the
    # rank's own measurement (host float — no device sync)
    reg.gauge("mesh_step_time_s",
              "host wall seconds per logical step in the last mesh "
              "dispatch").set(wall_s / max(int(n_steps), 1))


_data_axes = coll.data_axes

# what jax builds for these functions is counted under their own names
# (jax_compile_*_total{fun}): the jitted step of _build, the folded
# entry of framework/dispatch.build_folded_step, the inference steps
_host_events.register_fun("step", "program", "eval_step", "predict_step")

#: env overrides for the dp gradient-path knobs (DESIGN-DCN.md): a set
#: env var WINS over the constructor/strategy value, so a bench or an
#: operator can flip compression on a job whose profile doesn't carry
#: the knob.  PADDLE_TPU_DP_COMPRESS ∈ {"", "0", "8", "16"};
#: PADDLE_TPU_DP_SHARD_UPDATE ∈ {"", "0", "1"}.
_DP_COMPRESS_ENV = "PADDLE_TPU_DP_COMPRESS"
_DP_SHARD_ENV = "PADDLE_TPU_DP_SHARD_UPDATE"


def _resolve_dp_knobs(dp_compress_bits, dp_shard_update):
    """(bits, shard_update) after env overrides — bits ∈ {0, 8, 16}."""
    env_bits = (env_knobs.get_raw(_DP_COMPRESS_ENV, "")
                or "").strip().lower()
    if env_bits:
        dp_compress_bits = {"0": 0, "off": 0, "none": 0,
                            "8": 8, "int8": 8,
                            "16": 16, "exact16": 16}.get(env_bits)
        if dp_compress_bits is None:
            raise ValueError(
                f"{_DP_COMPRESS_ENV}={env_bits!r}: expected 0, 8 or 16")
    bits = int(dp_compress_bits or 0)
    if bits not in (0, 8, 16):
        raise ValueError(
            f"dp_compress_bits / DistributedStrategy.quantized_allreduce"
            f" must be 0 (off), 8 (int8 ring) or 16 (exact ring), got "
            f"{dp_compress_bits!r}")
    env_sh = (env_knobs.get_raw(_DP_SHARD_ENV, "")
              or "").strip().lower()
    if env_sh:
        if env_sh not in ("0", "1", "true", "false"):
            raise ValueError(
                f"{_DP_SHARD_ENV}={env_sh!r}: expected 0 or 1")
        dp_shard_update = env_sh in ("1", "true")
    return bits, bool(dp_shard_update)


class DistributedRunner:
    def __init__(self, network, optimizer, loss_fn=None,
                 mesh: Optional[Mesh] = None, sharding_stage: int = 0,
                 accumulate_steps: int = 1, input_specs=None,
                 amp_level: Optional[str] = None,
                 amp_dtype: str = "bfloat16",
                 capture_outputs: bool = False,
                 remat: bool = False,
                 dp_compress_bits: Optional[int] = None,
                 dp_shard_update: Optional[bool] = None):
        self.network = network
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or coll.ensure_mesh()
        self.sharding_stage = sharding_stage
        self.accumulate_steps = accumulate_steps
        # dp gradient-path knobs (DESIGN-DCN.md; strategy knobs
        # quantized_allreduce / sharded_weight_update, env override
        # wins): bits ∈ {0, 8, 16} selects the wire format of the
        # explicit dp gradient reduction; shard_update reduce-scatters
        # grads, updates only this replica's 1/dp shard of
        # params+opt_state and all-gathers params back.  Both route
        # the shared step body through an explicit shard_map over the
        # dp axis — see _dp_explicit_step_math.
        self._dp_compress_bits, self._dp_shard_update = \
            _resolve_dp_knobs(dp_compress_bits, dp_shard_update)
        self._dp_world = int(self.mesh.shape.get("dp", 1))
        self._dp_explicit = bool(
            (self._dp_compress_bits or self._dp_shard_update)
            and self._dp_world > 1)
        self._validate_dp_knobs()
        self._dp_comm_info = None
        # per-input PartitionSpec overrides (position → PartitionSpec or
        # None to keep the tensor out of the dspec heuristic below)
        self.input_specs = input_specs
        # amp_level "O1": auto_cast around the forward inside the
        # compiled step (O2 is param-level — use amp.decorate up front)
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        # capture_outputs: step also returns the network outputs
        # (hapi.Model needs them for metrics)
        self.capture_outputs = capture_outputs
        # remat: jax.checkpoint around the per-microbatch loss —
        # DistributedStrategy.recompute wiring (trade FLOPs for HBM)
        self.remat = remat
        self._step_fn = None
        # (jitted step, arguments of the call that built its program
        # last on a multi-device mesh) and that program's collectives,
        # counted when step_collectives is first read (_observe_schedule)
        self._scheduled = None
        self._schedule_counts = None
        # the argument signature of the executable _step_fn built last
        # (_count_step_program): why a further one is built
        self._step_signature = None
        self._opt_state = None
        self._placed = False
        # folded dispatch (the unified engine, framework/dispatch.py):
        # one compiled scan program per (fold, metric-arity, shapes)
        # signature; the base PRNG key is shared with the per-step
        # entry so both consume the identical key sequence; the device
        # metric accumulators ride the donated scan carry between
        # dispatches (owner: hapi Model.fit)
        self._fold_cache: Dict[Any, Any] = {}
        self._base_key = None
        self._metric_acc = None
        # deferred wrapper sync (same boundary protocol as hapi
        # TrainState): when True, train_step updates only the cached
        # value dicts and the Layer wrappers re-bind at
        # sync_to_layers() — hapi Model.fit enables this inside fit
        self._defer_wrapper_sync = False
        self._wrappers_dirty = False

    def _validate_dp_knobs(self):
        """Refuse — never silently drop — a dp compression / sharded-
        update knob the explicit path cannot honor (the strategy
        contract: every knob is consumed or refused)."""
        if not (self._dp_compress_bits or self._dp_shard_update):
            return
        busy = {ax: int(self.mesh.shape.get(ax, 1))
                for ax in ("mp", "pp", "sep", "sharding")
                if int(self.mesh.shape.get(ax, 1)) > 1}
        if busy:
            raise ValueError(
                "quantized_allreduce / sharded_weight_update run the "
                "step through an explicit shard_map over the dp axis "
                "and currently require every other mesh axis to be "
                f"size 1; got {busy}.  Use the implicit path (knobs "
                "off) for hybrid dp x mp/pp/sep/ZeRO meshes.")
        if self._dp_shard_update and self._dp_world > 1:
            clip = getattr(self.optimizer, "_grad_clip", None)
            if clip is not None and hasattr(clip, "pure_clip"):
                from ..nn.clip_grad import (ClipGradByGlobalNorm,
                                            ClipGradByValue)
                if not isinstance(clip, (ClipGradByGlobalNorm,
                                         ClipGradByValue)):
                    raise ValueError(
                        "sharded_weight_update supports "
                        "ClipGradByGlobalNorm (cross-shard psum of the "
                        "norm) and ClipGradByValue (elementwise); got "
                        f"{type(clip).__name__}")

    # -- sharding assignment -------------------------------------------------
    def _param_spec(self, p) -> P:
        if getattr(p, "dist_spec", None) is not None:
            return P(*p.dist_spec)
        if self.sharding_stage >= 3:
            size = int(self.mesh.shape.get("sharding", 1))
            if size > 1:
                return P(*shard_spec_for(p.shape, size))
        return P()

    def _state_spec(self, pspec: P, leaf, name: Optional[str] = None
                    ) -> P:
        """Optimizer-state leaf sharding: follow the param, except under
        ZeRO-1/2 where flat state shards on the 'sharding' axis, and
        under the dp-sharded weight update where every param-shaped
        slot shards its update dim on 'dp' (per-replica optimizer
        memory drops to ~1/dp — PAPERS.md arxiv 2004.13336)."""
        if np.ndim(leaf) == 0:
            return P()
        if self._dp_explicit and self._dp_shard_update and \
                name is not None:
            d = self._dp_shard_dims.get(name)
            p = self._name_to_param.get(name)
            if d is not None and p is not None and \
                    tuple(np.shape(leaf)) == tuple(p.shape):
                # no trailing Nones: shard_map canonicalizes its output
                # NamedSharding to P('dp',) — an equivalent-but-unequal
                # P('dp', None) on the placed input would miss the jit
                # cache and retrace the step once after dispatch 1
                spec = [None] * d + ["dp"]
                return P(*spec)
            return P()
        if self.sharding_stage >= 1:
            size = int(self.mesh.shape.get("sharding", 1))
            if size > 1 and pspec == P():
                return P(*shard_spec_for(np.shape(leaf), size))
        return pspec if len(pspec) <= np.ndim(leaf) else P()

    def _shard(self, value, spec: P):
        return jax.device_put(value, NamedSharding(self.mesh, spec))

    def place(self):
        """Device-put params/state with their shardings (done once)."""
        name_to_param = dict(self.network.named_parameters())
        self._name_to_param = name_to_param
        self._name_to_buf = dict(self.network.named_buffers())
        self._pspecs = {n: self._param_spec(p)
                        for n, p in name_to_param.items()}
        # dp-sharded weight update: which dim of each trainable param
        # the update/opt-state shards on the dp axis (None = nothing
        # divides — that leaf updates replicated, grads full-reduced)
        self._dp_shard_dims = {}
        if self._dp_shard_update and self._dp_world > 1:
            for n, p in name_to_param.items():
                if p.stop_gradient:
                    continue
                spec = shard_spec_for(p.shape, self._dp_world, "dp")
                self._dp_shard_dims[n] = next(
                    (i for i, a in enumerate(spec) if a == "dp"), None)
        self._compute_dp_comm_info(name_to_param)
        # per-param weight-decay coefficient and LR multiplier
        # (ParamAttr regularizer / learning_rate parity with step())
        (self._decay_coeffs, self._l1_coeffs,
         self._lr_scales) = self.optimizer._per_param_coeffs(name_to_param)
        for n, p in name_to_param.items():
            p._value = self._shard(p._value, self._pspecs[n])
        params = F.param_dict(self.network)
        if self._opt_state is None:
            # a checkpoint restored via optimizer.set_state_dict lands
            # in _opt_state_tree; adopt it when the keys line up
            restored = getattr(self.optimizer, "_opt_state_tree", None)
            if restored and set(restored) == set(params):
                self._opt_state = restored
            else:
                if restored:
                    import warnings
                    diff = sorted(set(restored) ^ set(params))[:8]
                    warnings.warn(
                        "DistributedRunner: restored optimizer state "
                        "keys do not match this network's parameters; "
                        f"re-initializing moments (key diff sample: "
                        f"{diff})")
                self._opt_state = self.optimizer.init_state_tree(params)
        placed_state = {}
        for n, st in self._opt_state.items():
            pspec = self._pspecs.get(n, P())
            placed_state[n] = {
                k: self._shard(v, self._state_spec(pspec, v, name=n))
                for k, v in st.items()}
        self._opt_state = placed_state
        self._placed = True

    def _compute_dp_comm_info(self, name_to_param):
        """Host-side dp-comm byte model for the observability counters
        (`dp_allreduce_bytes_total`, `dp_compress_ratio`): modeled
        per-device bytes per step over the dp axis, cross-checked
        against compiled-HLO collective sizes by the bench's
        bytes-moved audit."""
        W = self._dp_world
        if W <= 1:
            self._dp_comm_info = None
            return
        from .compressed import dp_comm_bytes_per_step
        bits = self._dp_compress_bits if self._dp_explicit else 0
        shard_on = self._dp_shard_update and self._dp_explicit
        n_elems = 0
        bytes_step = 0
        for n, p in name_to_param.items():
            if p.stop_gradient:
                continue
            leaf = int(np.prod(p.shape))
            n_elems += leaf
            # a leaf with no dp-divisible dim falls back to a full
            # all-reduce even under the sharded update — model what
            # the compiled program actually does, per leaf
            leaf_sharded = (shard_on and
                            self._dp_shard_dims.get(n) is not None)
            bytes_step += dp_comm_bytes_per_step(
                leaf, W, bits, leaf_sharded)
        baseline = dp_comm_bytes_per_step(n_elems, W, 0, False)
        self._dp_comm_info = {
            "bytes_per_step": bytes_step,
            "ratio": (baseline / bytes_step) if bytes_step else 1.0,
            "grad_elems": n_elems,
        }

    # -- the compiled step ---------------------------------------------------
    def _data_pspecs(self, shapes, stacked: bool):
        """``PartitionSpec`` per data position (None = leave the leaf
        unconstrained), shared by the per-step entry, the folded entry
        and the fold-group staging path so all three agree: batch dim
        on dp/sharding; seq dim (axis 1) on 'sep' when context
        parallelism is on and the length divides (SURVEY.md §5.7 —
        the heuristic can be wrong for non-sequence side inputs;
        ``input_specs={idx: PartitionSpec(...)|None}`` overrides it).
        ``shapes`` are PER-STEP ``[B, ...]`` shapes; ``stacked``
        prefixes the (unsharded) fold axis of a ``[K, ...]`` group.
        Returns None when the mesh gives data nothing to shard."""
        daxes = _data_axes(self.mesh)
        sep = int(self.mesh.shape.get("sep", 1))
        overrides = self.input_specs or {}
        if not (daxes or sep > 1 or overrides):
            return None
        lead = (None,) if stacked else ()
        out = []
        for i, shape in enumerate(shapes):
            if i in overrides:
                s = overrides[i]
                out.append(None if s is None else P(*lead, *tuple(s)))
                continue
            spec = list(lead) + [daxes if daxes else None]
            if sep > 1 and len(shape) >= 2 and shape[1] % sep == 0:
                spec.append("sep")
            out.append(P(*spec))
        return out

    def _place_with_specs(self, data, specs):
        """In-program sharding pin of the step's data leaves."""
        if specs is None:
            return data
        return tuple(
            d if s is None else jax.lax.with_sharding_constraint(
                d, NamedSharding(self.mesh, s))
            for d, s in zip(data, specs))

    def _step_math(self, n_in: int, metric_fns=()):
        """The ONE per-step train body both compiled entries share —
        amp/remat, microbatch gradient accumulation, ZeRO grad
        constraints, canonical-sharding pin on the updated params —
        so the legacy per-step program and the folded scan body cannot
        drift apart (their bit-parity is the engine's contract).  The
        dp gradient-path knobs (quantized allreduce, sharded weight
        update) swap the reduction/update half here, INSIDE the shared
        body, so both entries get them for free — that sharing is
        pinned by ``test_dp_compressed.py``.

        Returns ``per_step(params, frozen, buffers, opt_state, lr,
        key, md) -> (loss_f32, mstats, out_vals, new_params,
        new_state, new_buf)``; ``mstats`` are the in-step metric stat
        vectors (fold path), empty without ``metric_fns``."""
        if self._dp_explicit:
            return self._dp_explicit_step_math(n_in, metric_fns)
        return self._implicit_step_math(n_in, metric_fns)

    def _grad_math(self, n_in: int, metric_fns=()):
        """The forward/backward half of the step body — amp/remat,
        microbatch gradient-accumulation scan, in-step metric stats —
        shared verbatim by the implicit (XLA-reduced) and the explicit
        dp (shard_map-reduced) update paths.  Returns
        ``grad_step(params, frozen, buffers, key, md) -> (loss_f32,
        mstats, out_vals, grads, new_buf)`` where ``grads`` are the
        gradients of the loss as seen by this program (global-mean
        loss under the implicit path; local-mean loss inside the
        explicit per-replica body)."""
        net = self.network
        loss_layer = self.loss_fn
        runner = self
        acc = max(int(self.accumulate_steps), 1)
        capture = bool(self.capture_outputs or metric_fns)

        def grad_step(params, frozen, buffers, key, md):
            def loss_of(p, bufs_in, micro_data, micro_key):
                import contextlib
                inputs = [Tensor(v) for v in micro_data[:n_in]]
                labels = [Tensor(v) for v in micro_data[n_in:]]
                amp_ctx = contextlib.nullcontext()
                if runner.amp_level:
                    from ..amp import auto_cast
                    amp_ctx = auto_cast(level=runner.amp_level,
                                        dtype=runner.amp_dtype)
                with F.bind(net, p, bufs_in, frozen) as holder:
                    from ..autograd import tape as _tape
                    with _tape.no_grad_ctx():
                        with _random.key_provider(
                                _random.make_split_provider(micro_key)):
                            with amp_ctx:
                                out = net(*inputs)
                            outs = out if isinstance(out, (list, tuple)) \
                                else [out]
                            if loss_layer is not None:
                                loss = loss_layer(*outs, *labels)
                            else:
                                loss = outs[0]
                out_vals = ([o._value for o in outs] if capture else [])
                return loss._value.astype(jnp.float32), (
                    holder.get("buffers", {}), out_vals)

            if runner.remat:
                loss_of = jax.checkpoint(loss_of)

            if acc == 1:
                (loss_val, (new_buf, out_vals)), grads = \
                    jax.value_and_grad(loss_of, has_aux=True)(
                        params, buffers, md, key)
            else:
                # gradient accumulation (paddle gradient_merge parity):
                # microbatch loop compiled as lax.scan, grads averaged;
                # buffers (e.g. BN running stats) thread through the
                # carry so each microbatch sees the previous update
                micro = tuple(
                    d.reshape((acc, d.shape[0] // acc) + d.shape[1:])
                    for d in md)

                def body(carry, xs):
                    g_acc, l_acc, bufs_c = carry
                    mdd, mk = xs
                    (l, (nb, ov)), g = jax.value_and_grad(
                        loss_of, has_aux=True)(params, bufs_c, mdd, mk)
                    bufs_c = {**bufs_c, **nb}
                    g_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b, g_acc, g)
                    return (g_acc, l_acc + l, bufs_c), ov

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.result_type(p)),
                    params)
                keys = jax.random.split(key, acc)
                (grads, loss_sum, new_buf), out_stack = jax.lax.scan(
                    body,
                    (g0, jnp.asarray(0.0, jnp.float32), dict(buffers)),
                    (micro, keys))
                # [acc, bm, ...] per output → full-batch [B, ...]
                out_vals = [o.reshape((-1,) + o.shape[2:])
                            for o in out_stack]
                grads = jax.tree_util.tree_map(lambda g: g / acc, grads)
                loss_val = loss_sum / acc
            mstats = (tuple(mf(out_vals[0], md[n_in])
                            for mf in metric_fns)
                      if metric_fns and len(md) > n_in and out_vals
                      else ())
            return loss_val, mstats, out_vals, grads, new_buf

        return grad_step

    def _implicit_step_math(self, n_in: int, metric_fns=()):
        """The default update half: XLA emits the dp gradient
        all-reduce (or ZeRO reduce-scatter) implicitly from the
        shardings; the optimizer update runs replicated (or
        'sharding'-axis sharded under ZeRO-1/2)."""
        mesh = self.mesh
        opt = self.optimizer
        stage = self.sharding_stage
        runner = self
        grad_step = self._grad_math(n_in, metric_fns)

        def per_step(params, frozen, buffers, opt_state, lr, key, md):
            loss_val, mstats, out_vals, grads, new_buf = grad_step(
                params, frozen, buffers, key, md)
            size = int(mesh.shape.get("sharding", 1))
            if stage >= 1 and size > 1:
                grads = runner._constrain_zero_grads(grads, stage, size)
            with jax.named_scope("optimizer"):
                new_params, new_state = opt.apply_gradients_tree(
                    params, grads, opt_state, lr,
                    decay_coeffs=runner._decay_coeffs,
                    lr_scales=runner._lr_scales,
                    l1_coeffs=runner._l1_coeffs)
                # pin updated params back to their canonical shardings
                # so the ZeRO-1 weight-update all-gather happens here,
                # not lazily
                new_params = {
                    n: jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, runner._pspecs.get(n, P())))
                    for n, v in new_params.items()}
            return (loss_val, mstats, out_vals, new_params, new_state,
                    new_buf)

        return per_step

    # -- explicit dp gradient path (DESIGN-DCN.md) ---------------------------
    def _dp_data_in_specs(self, shapes):
        """shard_map in_specs for the per-step data leaves: the same
        placement `_data_pspecs` pins on the implicit path (batch dim
        on 'dp'; overrides honored), refused loudly if an override
        names an axis the explicit path cannot bind."""
        specs = self._data_pspecs(shapes, stacked=False)
        if specs is None:
            return tuple(P() for _ in shapes)
        out = []
        for s in specs:
            if s is None:
                out.append(P())
                continue
            for ax in s:
                names = [ax] if isinstance(ax, str) else list(ax or [])
                if any(a != "dp" for a in names):
                    raise ValueError(
                        "quantized_allreduce / sharded_weight_update: "
                        f"input spec {s} names a non-dp mesh axis; the "
                        "explicit dp path shards data on 'dp' only")
            out.append(s)
        return tuple(out)

    def _dp_state_spec_tree(self):
        """PartitionSpec tree of the (placed) opt_state — the
        shard_map in/out specs of the sharded weight update; must
        agree with place()'s device layout (both go through
        ``_state_spec``)."""
        return {
            n: {k: self._state_spec(self._pspecs.get(n, P()), v, name=n)
                for k, v in st.items()}
            for n, st in self._opt_state.items()}

    def _dp_sharded_clip_fn(self, clip, shard_dims):
        """Gradient clipping over the dp-sharded gradient layout.
        ClipGradByValue is elementwise (shard-safe as-is);
        ClipGradByGlobalNorm needs the TRUE global norm: sharded
        leaves contribute their local-shard sum-of-squares psum'd over
        dp (each element counted once), replicated-fallback leaves
        contribute locally (identical on every replica).  Anything
        else was refused at construction."""
        from ..nn.clip_grad import ClipGradByValue

        if isinstance(clip, ClipGradByValue):
            return clip.pure_clip

        def global_norm_clip(g_sh):
            sq_sharded = jnp.asarray(0.0, jnp.float32)
            sq_repl = jnp.asarray(0.0, jnp.float32)
            for n, g in g_sh.items():
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if shard_dims.get(n) is None:
                    sq_repl = sq_repl + s
                else:
                    sq_sharded = sq_sharded + s
            total = jax.lax.psum(sq_sharded, "dp") + sq_repl
            norm = jnp.sqrt(total)
            scale = clip.clip_norm / jnp.maximum(norm, clip.clip_norm)
            return {n: (g.astype(jnp.float32) * scale).astype(g.dtype)
                    for n, g in g_sh.items()}

        return global_norm_clip

    def _dp_explicit_step_math(self, n_in: int, metric_fns=()):
        """The compressed / sharded dp update half: the shared
        forward/backward (``_grad_math``) runs per-replica inside a
        ``shard_map`` over the dp axis, then the gradient reduction is
        an EXPLICIT collective site (DESIGN-DCN.md integration plan):

        * bits=16 — exact ring all-reduce (two 16-bit words per fp32
          element; the parity anchor: at dp=2 bit-identical to the
          implicit XLA path end-to-end);
        * bits=8  — EQuARX int8 ring (~3.97x fewer dp wire bytes,
          zero-mean stochastic-rounding noise);
        * sharded_weight_update — grads reduce-scatter (at the mode's
          wire width), Adam/SGD/... updates only this replica's 1/dp
          shard of params + opt_state, params all-gather back exactly
          (weights are state: persistent error is not zero-mean, so
          the param gather is never quantized).  Opt-state leaves stay
          full-shape arrays SHARDED on 'dp' via NamedSharding, so
          checkpoints keep the unsharded layout and per-device memory
          drops to ~1/dp.

        Model RNG folds the dp rank in (per-replica dropout masks —
        DataParallel semantics); batch statistics are per-replica with
        a pmean write-back of float buffers (SyncBN-approximate).
        """
        from .shard_map_compat import shard_map
        from .compressed import quantized_all_reduce, ring_reduce_scatter

        runner = self
        mesh = self.mesh
        opt = self.optimizer
        W = self._dp_world
        bits = self._dp_compress_bits
        shard_update = self._dp_shard_update
        shard_dims = dict(getattr(self, "_dp_shard_dims", {}))
        grad_step = self._grad_math(n_in, metric_fns)
        state_specs = self._dp_state_spec_tree()
        clip = getattr(opt, "_grad_clip", None)
        clip_fn = None
        if shard_update and clip is not None and \
                hasattr(clip, "pure_clip"):
            clip_fn = self._dp_sharded_clip_fn(clip, shard_dims)

        def reduce_full(g, qkey, i):
            """Full all-reduce of one grad leaf at the wire mode."""
            if bits:
                return quantized_all_reduce(
                    g, "dp", bits=bits,
                    key=jax.random.fold_in(qkey, i))
            return jax.lax.psum(g, "dp")

        def body(params, frozen, buffers, opt_state, lr, key, md):
            r = jax.lax.axis_index("dp")
            # per-replica model RNG (dropout decorrelates across dp,
            # exactly like process-per-rank DataParallel); a no-RNG
            # model is unaffected, preserving the bits=16 parity pin
            mkey = jax.random.fold_in(key, r)
            qkey = jax.random.fold_in(key, jnp.uint32(0x51ED5EED))
            loss_val, mstats, out_vals, grads, new_buf = grad_step(
                params, frozen, buffers, mkey, md)
            # grads are d(local-mean loss); the dp-mean of the
            # per-replica grads is the global-batch gradient
            if not shard_update:
                grads = {n: reduce_full(g, qkey, i) / W
                         for i, (n, g) in enumerate(grads.items())}
                with jax.named_scope("optimizer"):
                    new_params, new_state = opt.apply_gradients_tree(
                        params, grads, opt_state, lr,
                        decay_coeffs=runner._decay_coeffs,
                        lr_scales=runner._lr_scales,
                        l1_coeffs=runner._l1_coeffs)
            else:
                g_sh, p_sh = {}, {}
                for i, (n, g) in enumerate(grads.items()):
                    d = shard_dims.get(n)
                    if d is None:
                        g_sh[n] = reduce_full(g, qkey, i) / W
                        p_sh[n] = params[n]
                        continue
                    if bits:
                        gs = ring_reduce_scatter(
                            g, "dp", shard_axis=d, bits=bits,
                            key=jax.random.fold_in(qkey, i))
                    else:
                        gs = jax.lax.psum_scatter(
                            g, "dp", scatter_dimension=d, tiled=True)
                    g_sh[n] = gs / W
                    span_len = params[n].shape[d] // W
                    p_sh[n] = jax.lax.dynamic_slice_in_dim(
                        params[n], r * span_len, span_len, axis=d)
                with jax.named_scope("optimizer"):
                    if clip_fn is not None:
                        g_sh = clip_fn(g_sh)
                    new_p_sh, new_state = opt.apply_gradients_tree(
                        p_sh, g_sh, opt_state, lr,
                        decay_coeffs=runner._decay_coeffs,
                        lr_scales=runner._lr_scales,
                        l1_coeffs=runner._l1_coeffs,
                        apply_clip=clip_fn is None)
                    new_params = {
                        n: (v if shard_dims.get(n) is None else
                            jax.lax.all_gather(v, "dp",
                                               axis=shard_dims[n],
                                               tiled=True))
                        for n, v in new_p_sh.items()}
            loss_val = jax.lax.pmean(loss_val, "dp")
            mstats = jax.tree_util.tree_map(
                lambda s: jax.lax.psum(s, "dp"), mstats)
            new_buf = {
                n: (jax.lax.pmean(b, "dp")
                    if jnp.issubdtype(b.dtype, jnp.floating) else b)
                for n, b in new_buf.items()}
            return (loss_val, mstats, out_vals, new_params, new_state,
                    new_buf)

        def per_step(params, frozen, buffers, opt_state, lr, key, md):
            data_specs = self._dp_data_in_specs(
                [d.shape for d in md])
            wrapped = shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(), state_specs, P(), P(),
                          data_specs),
                out_specs=(P(), P(), P("dp"), P(), state_specs, P()),
                check_vma=False)
            return wrapped(params, frozen, buffers, opt_state, lr,
                           key, md)

        return per_step

    def _observe_dp_comm(self, n_steps: int):
        """dp-comm observability (host floats only, no device sync):
        modeled per-device dp wire bytes per dispatch on the registry
        (`dp_allreduce_bytes_total`) plus the achieved compression
        ratio gauge."""
        info = self._dp_comm_info
        if not info:
            return
        reg = _obs_metrics.registry()
        reg.counter(
            "dp_allreduce_bytes_total",
            "modeled per-device bytes moved over the dp axis by the "
            "gradient path (reduce-scatter + all-gather wire bytes)"
            ).inc(info["bytes_per_step"] * n_steps)
        reg.gauge(
            "dp_compress_ratio",
            "uncompressed-allreduce bytes / actual dp gradient-path "
            "bytes (1.0 = no compression)").set(info["ratio"])

    def _constrain_zero_grads(self, grads, stage: int, size: int):
        """Explicit sharding pins on the ZeRO grad boundary.

        Most leaves shard their ROW dim (dim 0) on the 'sharding' axis
        and XLA lowers the grad psum straight into a reduce-scatter.
        But a leaf whose dim 0 does not divide the axis shards an
        *inner* (feature) dim instead — e.g. a ``[2, 64]`` token-type
        embedding at sharding=4 — and the partitioner then tries to
        push that feature-dim sharding up into the batch-sharded
        activation that produces the grad, giving up with an
        "[SPMD] Involuntary full rematerialization" warning
        (MULTICHIP_r05).  For exactly those leaves we annotate the
        boundary explicitly: the grad is pinned fully-reduced and
        replicated first (cheap by construction — dim 0 indivisible
        means the leaf is small), and only then resharded onto the
        state/grad sharding, so every reshard is planned, not a
        last-resort remat.  ``test_hlo_collective_audit.py`` pins the
        compile warning-free."""
        mesh = self.mesh
        out = {}
        for n, g in grads.items():
            spec = shard_spec_for(g.shape, size)
            if spec == (None,) * len(spec):
                out[n] = g
                continue
            inner_dim = spec[0] is None
            if inner_dim:
                g = jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P()))
            if stage >= 2:
                g = jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P(*spec)))
            out[n] = g
        return out

    def _donate_explicit_ok(self) -> bool:
        """Whether this runner's compiled entries may donate the
        params/opt_state carry.  Always true on the implicit path;
        the explicit-dp path donates only under the
        ``PADDLE_TPU_DP_DONATE=1`` opt-in (see _build)."""
        if not self._dp_explicit:
            return True
        return env_knobs.get_raw("PADDLE_TPU_DP_DONATE", "") == "1"

    def _build(self):
        runner = self

        # base key drawn once per runner and SHARED with the folded
        # entry; per-step keys derived INSIDE the compiled program from
        # the step counter (saves two host-dispatched device ops per
        # step, and makes fold=K bit-identical to K per-step dispatches)
        base_key = self._ensure_base_key()

        def step(params, frozen, buffers, opt_state, lr, ctr, *data):
            key = jax.random.fold_in(base_key, ctr)
            data = runner._place_with_specs(
                data, runner._data_pspecs([d.shape for d in data],
                                          stacked=False))
            per_step = runner._step_math(runner._n_inputs)
            loss_val, _mstats, out_vals, new_params, new_state, \
                new_buf = per_step(params, frozen, buffers, opt_state,
                                   lr, key, data)
            return loss_val, new_params, new_state, new_buf, out_vals

        # the explicit-dp (shard_map) programs skip buffer donation: this
        # container's jaxlib CPU client corrupts donated buffers that
        # alias through shard_map manual collectives (intermittent NaN
        # end states / segfaults inside XLA execution — reproduced by
        # tests/test_dp_compressed.py with donation on, 3/3 clean with
        # it off; the same family the conftest's sync-dispatch note
        # documents for plain SPMD programs).  PADDLE_TPU_DP_DONATE=1
        # opts back in for real-TPU memory-bound runs (ROADMAP
        # re-measure backlog).
        donate = (0, 3) if self._donate_explicit_ok() else ()
        return jax.jit(step, donate_argnums=donate, **self._jit_options())

    def _jit_options(self) -> dict:
        """What the step programs add to ``jax.jit``: the compiler's
        options for the mesh (``step_schedule.compiler_options``: the dp
        gradient all-reduces under the backward pass on a multi-device
        TPU mesh), nothing where there are none."""
        options = _schedule.compiler_options(self.mesh)
        return {} if options is None else {"compiler_options": options}

    def _observe_schedule(self, fn, args):
        """Point ``step_collectives{kind, mode}`` at the program the
        call of ``fn`` on ``args`` just built, on a multi-device mesh.
        Nothing is read here but at the gauge's first read
        (:meth:`_step_collectives`): on four v5e chips at 8 of GPT-3
        XL's 24 layers a read takes 1.3 s, 0.9 s of it jax's handling
        of the arguments, which a run that never reads the gauge should
        not pay."""
        if self.mesh.devices.size <= 1:
            return
        self._scheduled, self._schedule_counts = (fn, args), None

        def counts(method=weakref.WeakMethod(self._step_collectives)):
            bound = method()       # None once the runner is gone
            return None if bound is None else bound()
        _schedule.observe(counts)

    def _step_collectives(self):
        """``step_schedule.collective_modes`` of the program the step
        built last, counted once: the step is lowered and compiled on
        the arguments of the call that built it, which jax answers from
        that call's caches, so nothing is traced, lowered or compiled
        again and the body, which reads the mesh, does not run (pinned
        by tests/test_step_schedule.py; a donated argument still gives
        its shape and sharding), and the executable's text is read."""
        if self._schedule_counts is None:
            fn, args = self._scheduled
            compiled = fn.lower(*args).compile()
            self._schedule_counts = _schedule.collective_modes(
                compiled.as_text() or "")
        return self._schedule_counts

    def train_step(self, inputs, labels) -> float:
        """Run one compiled step; commits params/state/buffers."""
        # the runner's mesh is the source of truth while the step traces
        # (context-parallel attention consults it); restored afterwards
        # so eager eval outside the runner doesn't inherit it
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            t0 = time.perf_counter()
            # step=: what the phases of one step share (mesh.stage,
            # .scalars, .val_cache, .launch, .commit lie inside it)
            with _obs_trace.span(
                    "mesh.dispatch",
                    {"step": getattr(self, "_step_ctr", 0) + 1}):
                out = self._train_step_inner(inputs, labels)
            _observe_mesh_steps(1, time.perf_counter() - t0)
            self._observe_dp_comm(1)
            return out
        finally:
            coll.set_mesh(prev_mesh)

    def _prep_step_args(self, inputs, labels):
        if not self._placed:
            self.place()
        if self._step_fn is None:
            self._step_fn = self._build()
        # the shared staging path (io/staging.py): Tensors and jax
        # arrays pass through, host leaves take one batched async put
        with _obs_trace.span("mesh.stage"):
            inputs_v = to_device_values(
                inputs if isinstance(inputs, (list, tuple))
                else [inputs])
            labels_v = to_device_values(
                labels if isinstance(labels, (list, tuple))
                else [labels])
        if getattr(self, "_n_inputs", None) is None:
            self._n_inputs = len(inputs_v)
        elif self._n_inputs != len(inputs_v):
            # the compiled step is specialised on the input/label split
            raise ValueError(
                f"DistributedRunner was compiled for {self._n_inputs} "
                f"inputs, got {len(inputs_v)}; create a new runner")
        return inputs_v, labels_v

    def lower_step(self, inputs, labels):
        """AOT-lower the compiled train step (no execution): for HLO
        collective audits and ``CompiledMemoryStats`` budget checks.
        Returns the ``jax.stages.Lowered`` object."""
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            inputs_v, labels_v = self._prep_step_args(inputs, labels)
            params, frozen, bufs = self._sync_val_cache()
            lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
            return self._step_fn.lower(
                params, frozen, bufs, self._opt_state, lr,
                jnp.uint32(1), *inputs_v, *labels_v)
        finally:
            coll.set_mesh(prev_mesh)

    def set_global_step(self, step: int):
        """Align the runner's step counter with a restored checkpoint:
        per-step RNG keys are folded from this counter, so resuming at
        the right count reproduces the uninterrupted trajectory; the
        resilience layer (kill-at-step fault plans, hang watchdog) also
        reports this counter."""
        self._step_ctr = int(step)

    def _train_step_inner(self, inputs, labels) -> float:
        inputs_v, labels_v = self._prep_step_args(inputs, labels)
        with _obs_trace.span("mesh.scalars"):
            lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
            self._step_ctr = getattr(self, "_step_ctr", 0) + 1
            ctr = jnp.uint32(self._step_ctr)
        with _obs_trace.span("mesh.val_cache"):
            params, frozen, bufs = self._sync_val_cache()
        step_fn = self._step_fn
        held = step_fn._cache_size()
        with _obs_trace.span("mesh.launch"):
            loss, new_p, new_s, new_buf, out_vals = step_fn(
                params, frozen, bufs,
                self._opt_state, lr, ctr, *inputs_v, *labels_v)
        with _obs_trace.span("mesh.commit"):
            if step_fn._cache_size() != held:
                self._count_step_program(
                    step_fn, (params, frozen, bufs, self._opt_state, lr,
                              ctr, *inputs_v, *labels_v))
            self._commit_step(params, bufs, new_p, new_s, new_buf, 1)
        if self.capture_outputs:
            return loss, out_vals
        return loss

    def _count_step_program(self, step_fn, args):
        """The jitted step gained an executable: count it under the
        reason, say once which arguments differ from those of the
        executable before it, and point ``step_collectives`` at it
        (:meth:`_observe_schedule`).  Walks the leaves, so it is called
        only when ``_step_fn._cache_size()`` grew; donated leaves still
        say all but their layout."""
        now = _host_events.argument_signature(args)
        before, self._step_signature = self._step_signature, now
        reason, differing = _host_events.signature_change(before, now)
        _obs_metrics.registry().counter(
            "mesh_step_programs_total",
            "executables the jitted train step built, by what differed "
            "in its arguments from the executable before",
            labels={"reason": reason}).inc()
        _obs_events.record("step_program", reason=reason,
                           step=self._step_ctr, differing=differing)
        self._observe_schedule(step_fn, args)

    def _commit_step(self, params, bufs, new_p, new_s, new_buf,
                     n_steps: int):
        """Everything after the launch, for the per-step and the folded
        entry alike: rebind parameters, optimizer state and buffers to
        what the program returned, then the resilience hooks."""
        if self._defer_wrapper_sync:
            # hot-loop mode (hapi fit): the cached value dicts are the
            # canonical copy; wrapper ._value rebinds wait for the
            # epoch/save/eval boundary (sync_to_layers) — zero per-step
            # wrapper writes
            params.update(new_p)
            self._wrappers_dirty = True
        else:
            for n, v in new_p.items():
                self._name_to_param[n]._value = v
                params[n] = v
                self._wrapper_snap[n] = v
        self._opt_state = new_s
        # keep the optimizer's canonical slots in sync for checkpointing
        self.optimizer._opt_state_tree = new_s
        if hasattr(self.optimizer, "_global_step"):
            self.optimizer._global_step += n_steps
        for n, v in new_buf.items():
            b = self._name_to_buf.get(n)
            if b is None:
                continue
            bufs[n] = v
            if self._defer_wrapper_sync:
                self._wrappers_dirty = True
            else:
                b._value = v
                self._buf_snap[n] = v
        # resilience hooks: the committed step feeds the hang watchdog
        # (progress proof) and the chaos layer (kill-at-step-N plans);
        # both are no-ops unless installed.  A folded dispatch ticks
        # them ONCE, with the step count advanced by its K.
        _watchdog.notify_step(self._step_ctr)
        _elastic.notify_step(self._step_ctr)
        _faults.fault_point("train.step", step=self._step_ctr)

    def _sync_val_cache(self):
        """Return (params, frozen, buffers) value dicts, kept coherent.

        The dicts are cached and updated in place after each step — no
        per-step rebuild over hundreds of params.  External in-place
        weight updates (``set_state_dict``, ``CheckpointManager.restore``
        writing ``p._value``) are detected by id-comparing each
        wrapper's current ``_value`` against the *snapshot of what the
        wrapper held at the last sync* — not against the cache, because
        under deferred wrapper sync the cache legitimately runs ahead
        of the wrappers between boundaries.  Any externally replaced
        leaf is re-placed with its canonical sharding before the
        compiled step consumes it.
        """
        if getattr(self, "_val_cache", None) is None:
            self._val_cache = (
                {n: p._value for n, p in self._name_to_param.items()
                 if not p.stop_gradient},
                {n: p._value for n, p in self._name_to_param.items()
                 if p.stop_gradient},
                {n: b._value for n, b in self._name_to_buf.items()
                 if b is not None})
            self._wrapper_snap = {n: p._value
                                  for n, p in self._name_to_param.items()}
            self._buf_snap = {n: b._value
                              for n, b in self._name_to_buf.items()
                              if b is not None}
            return self._val_cache
        params, frozen, bufs = self._val_cache
        for n, p in self._name_to_param.items():
            if self._wrapper_snap.get(n) is not p._value:
                v = self._shard(p._value, self._pspecs.get(n, P()))
                p._value = v
                self._wrapper_snap[n] = v
                (frozen if p.stop_gradient else params)[n] = v
                # trainability may have flipped with the external write
                (params if p.stop_gradient else frozen).pop(n, None)
        for n, b in self._name_to_buf.items():
            if b is not None and self._buf_snap.get(n) is not b._value:
                bufs[n] = b._value
                self._buf_snap[n] = b._value
        return self._val_cache

    def sync_to_layers(self):
        """Boundary write-back of the deferred wrapper sync (the same
        protocol as hapi ``TrainState.sync_to_layers``): rebind every
        Layer wrapper to the cached canonical values — pure reference
        writes, no device transfer."""
        if not self._wrappers_dirty or \
                getattr(self, "_val_cache", None) is None:
            return
        params, frozen, bufs = self._val_cache
        for n, v in params.items():
            p = self._name_to_param.get(n)
            if p is not None:
                p._value = v
                self._wrapper_snap[n] = v
        for n, v in bufs.items():
            b = self._name_to_buf.get(n)
            if b is not None:
                b._value = v
                self._buf_snap[n] = v
        self._wrappers_dirty = False

    def invalidate_cache(self):
        """Drop cached value dicts (call after bulk external updates).
        The caller asserts the wrappers are canonical again (checkpoint
        restore/reshard just wrote every ``p._value``), so any deferred
        wrapper sync still pending is DISCARDED, never flushed — the
        external writes win over superseded step results."""
        self._val_cache = None
        self._wrappers_dirty = False
        # a mid-run checkpoint restore (optimizer.set_state_dict)
        # rebuilds optimizer._opt_state_tree, but the compiled step
        # consumes self._opt_state — without re-adoption the resumed
        # trajectory silently trains on STALE moments (found by the
        # single-rank-replacement reform e2e: loss off by 1e-3, not
        # bit-identical).  Identity-compare is sound because every
        # committed step re-binds _opt_state_tree to _opt_state.
        restored = getattr(self.optimizer, "_opt_state_tree", None)
        if (self._placed and restored is not None
                and restored is not self._opt_state):
            if set(restored) == set(self._pspecs):
                # re-placement honors the dp-sharded-update layout too:
                # a promoted spare (or any external restore) hands in
                # full host arrays and each device re-adopts ONLY its
                # 1/dp opt-state shard via the NamedSharding put — the
                # sharded-elastic-restore contract at the reform
                # barrier (DESIGN-RESILIENCE.md)
                placed = {}
                for n, st in restored.items():
                    pspec = self._pspecs.get(n, P())
                    placed[n] = {
                        k: self._shard(v,
                                       self._state_spec(pspec, v,
                                                        name=n))
                        for k, v in st.items()}
                self._opt_state = placed
                self.optimizer._opt_state_tree = placed
            else:
                # mirror place()'s loud behavior: silently keeping the
                # pre-restore device moments is exactly the stale-
                # moments divergence this re-adoption exists to close
                import warnings
                diff = sorted(set(restored) ^ set(self._pspecs))[:8]
                warnings.warn(
                    "DistributedRunner.invalidate_cache: externally "
                    "restored optimizer state keys do not match this "
                    "network's parameters; keeping the current device "
                    f"moments (key diff sample: {diff})")

    # -- folded dispatch (the unified engine, framework/dispatch.py) ---------
    def _ensure_base_key(self):
        """Base PRNG key drawn ONCE per runner (at the first compiled-
        step build) and shared by the per-step and folded entries, so
        both consume the identical ``fold_in(base_key, ctr)`` key
        sequence — the parity contract of the unified engine."""
        if self._base_key is None:
            self._base_key = _random.default_generator().draw_key()
        return self._base_key

    def _stacked_shardings(self, sample):
        """Per-position ``NamedSharding`` for a stacked ``[K, ...]``
        fold group (the same specs as the in-program placement, via
        ``_data_pspecs``): leading fold axis unsharded, batch dim on
        the data axes, seq dim on 'sep' — host staging lands the group
        directly on its data layout instead of paying an in-program
        reshard of the whole stack.  None when the mesh has no data
        axes (nothing to pre-place)."""
        specs = self._data_pspecs([d.shape for d in sample],
                                  stacked=True)
        if specs is None:
            return None
        return [NamedSharding(self.mesh, P() if s is None else s)
                for s in specs]

    def _build_fold(self, fold: int, n_in: int, metric_fns):
        """The mesh fold program: the shared per-step body
        (:meth:`_step_math` — the SAME body the legacy entry compiles,
        so the two cannot drift) wrapped for the scan builder
        (``framework.dispatch.build_folded_step``), plus the in-step
        metric stat vectors that ride the folded carry.  Buffers are
        NOT donated: the runner's cached value dicts alias them across
        dispatches."""
        runner = self
        step_math = self._step_math(n_in, metric_fns)

        def per_step(p, frozen, bufs, st, lr, key, md):
            loss_val, mstats, _out_vals, new_p, new_st, new_buf = \
                step_math(p, frozen, bufs, st, lr, key, md)
            return loss_val, mstats, new_p, new_st, new_buf

        def place_data(data):
            # stacked [K, ...] layout: specs from the per-step shapes
            return runner._place_with_specs(
                data, runner._data_pspecs([d.shape[1:] for d in data],
                                          stacked=True))

        from ..framework.dispatch import build_folded_step
        return build_folded_step(per_step, fold, donate_buffers=False,
                                 place_data=place_data,
                                 donate_carry=self._donate_explicit_ok(),
                                 **self._jit_options())

    def train_steps_folded(self, groups, metric_fns=(),
                           metric_acc=None):
        """ONE rolled scan-of-K dispatch covering ``len(groups)``
        logical train steps over the mesh — the mesh half of the
        unified dispatch engine.  ``groups`` is ``[(inputs, labels),
        ...]``; returns ``(losses, mstacks, new_metric_acc)`` with the
        per-step losses/metric stats as shared-fetch ``LazyStack``s.
        The scan carry is the donated SHARDED state (params/opt_state)
        plus the device metric accumulators; per-step PRNG keys derive
        from the same ``(base_key, ctr)`` sequence the per-step entry
        consumes, so the end state is bit-identical for every K —
        including K=1 against the legacy per-step path."""
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            t0 = time.perf_counter()
            with _obs_trace.span(
                    "mesh.dispatch_folded",
                    args=({"k": len(groups)}
                          if _obs_trace.enabled() else None)):
                out = self._train_steps_folded_inner(
                    groups, metric_fns, metric_acc)
            _observe_mesh_steps(len(groups),
                                time.perf_counter() - t0)
            self._observe_dp_comm(len(groups))
            return out
        finally:
            coll.set_mesh(prev_mesh)

    def _train_steps_folded_inner(self, groups, metric_fns, metric_acc):
        if not self._placed:
            self.place()
        fold = len(groups)
        n_in = len(groups[0][0])
        if getattr(self, "_n_inputs", None) is None:
            self._n_inputs = n_in
        elif self._n_inputs != n_in:
            raise ValueError(
                f"DistributedRunner was compiled for {self._n_inputs} "
                f"inputs, got {n_in}; create a new runner")
        flat = [list(ins) + list(lbs) for ins, lbs in groups]
        # ONE batched async H2D put for the whole [K, ...] group,
        # pre-placed on the data shardings (io/staging.py)
        with _obs_trace.span("mesh.stage"):
            stacked = stack_to_device(flat,
                                      shardings=self._stacked_shardings(
                                          flat[0]))
        sig = (fold, len(metric_fns),
               tuple((v.shape, v.dtype) for v in stacked))
        fn = self._fold_cache.get(sig)
        if fn is None:
            fn = self._fold_cache[sig] = self._build_fold(
                fold, n_in, metric_fns)
        with _obs_trace.span("mesh.val_cache"):
            params, frozen, bufs = self._sync_val_cache()
        with _obs_trace.span("mesh.scalars"):
            lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
            ctr0 = getattr(self, "_step_ctr", 0) + 1
            macc = tuple(metric_acc) if metric_acc is not None else ()
            base_key = self._ensure_base_key()
        held = fn._cache_size()
        args = (params, frozen, bufs, self._opt_state, macc, lr,
                base_key, np.uint32(ctr0), *stacked)
        with _obs_trace.span("mesh.launch"):
            losses, mstacks, new_acc, new_p, new_st, new_buf = fn(*args)
        with _obs_trace.span("mesh.commit"):
            if fn._cache_size() != held:
                self._observe_schedule(fn, args)
            self._step_ctr = ctr0 + fold - 1
            self._commit_step(params, bufs, new_p, new_st, new_buf, fold)
        from ..framework.lazy import LazyStack
        return (LazyStack(losses), [LazyStack(s) for s in mstacks],
                tuple(new_acc))

    def compile_stats(self):
        """Recompile introspection for the folded mesh path (mirrors
        ``Model.compile_stats``): one fold-cache entry per (fold,
        metric-arity, shapes, dtypes) signature; growth on a fixed
        workload means silent retracing."""
        traces = 0
        for fn in self._fold_cache.values():
            try:
                traces += fn._cache_size()
            except Exception:
                pass
        return {"entries": len(self._fold_cache), "traces": traces}

    # -- eval / predict ------------------------------------------------------
    def _eval_build(self, with_loss: bool, n_in: int):
        """One compiled inference step per (mode, arity) — the input
        split is a builder argument, not trace-time ``self`` state, so
        a different arity compiles a new program instead of silently
        reusing a stale trace.  The buffers dict — the one state
        argument an inference step can alias — is donated: it passes
        through (updated under train-mode BN) and comes back, so XLA
        reuses the buffers instead of copying."""
        net = self.network
        loss_layer = self.loss_fn

        capture = self.capture_outputs

        def run(params, frozen, buffers, *data):
            inputs = [Tensor(v) for v in data[:n_in]]
            labels = [Tensor(v) for v in data[n_in:]]
            with F.bind(net, params, buffers, frozen) as holder:
                from ..autograd import tape as _tape
                with _tape.no_grad_ctx():
                    out = net(*inputs)
                    if with_loss and loss_layer is not None:
                        outs = out if isinstance(out, (list, tuple)) \
                            else [out]
                        loss = loss_layer(*outs, *labels)
                        lv = loss._value.astype(jnp.float32)
                        payload = (lv, [o._value for o in outs]) \
                            if capture else lv
                    elif isinstance(out, (list, tuple)):
                        payload = [o._value for o in out]
                    else:
                        payload = out._value
            return payload, holder.get("buffers", {})

        # the name jax.monitoring reports what it builds under
        run.__name__ = "eval_step" if with_loss else "predict_step"
        return jax.jit(run, donate_argnums=(2,))  # lint: allow(donation-safety): eval forward never enters the explicit-dp shard_map collectives — the donated buffers alias a plain SPMD program only, outside the DESIGN-DCN.md corruption mode

    def _eval_values(self):
        if not self._placed:
            self.place()
        return self._sync_val_cache()

    def _get_eval_fn(self, with_loss: bool, n_in: int):
        cache = getattr(self, "_eval_cache", None)
        if cache is None:
            cache = self._eval_cache = {}
        key = (with_loss, n_in)
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = self._eval_build(with_loss, n_in)
        return fn

    def _stage_eval_data(self, seq):
        """Host→device staging of one inference batch through the
        shared path (io/staging.py): Tensors and jax arrays pass
        through untouched — no D2H round trip — and host leaves take
        one batched async device_put."""
        return to_device_values(
            seq if isinstance(seq, (list, tuple)) else [seq])

    def _commit_eval_buffers(self, new_buf):
        """Rebind the donated buffers to the returned (aliased) arrays
        so the next step never touches the donated originals."""
        bufs = self._sync_val_cache()[2]
        for n, v in new_buf.items():
            b = self._name_to_buf.get(n)
            if b is not None:
                b._value = v
                self._buf_snap[n] = v
            bufs[n] = v

    def eval_step(self, inputs, labels):
        """Compiled forward + loss (no grad, no update)."""
        # validation batches are progress too: keep the hang watchdog
        # from declaring a long eval pass between train steps a hang
        _watchdog.notify_step()
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            params, frozen, bufs = self._eval_values()
            iv = self._stage_eval_data(inputs)
            lv = self._stage_eval_data(labels)
            if getattr(self, "_n_inputs", None) is None:
                self._n_inputs = len(iv)
            fn = self._get_eval_fn(True, len(iv))
            payload, new_buf = fn(params, frozen, bufs, *iv, *lv)
            self._commit_eval_buffers(new_buf)
            return payload
        finally:
            coll.set_mesh(prev_mesh)

    def predict_step(self, inputs):
        """Compiled forward; returns raw outputs."""
        _watchdog.notify_step()
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            params, frozen, bufs = self._eval_values()
            iv = self._stage_eval_data(inputs)
            fn = self._get_eval_fn(False, len(iv))
            out, new_buf = fn(params, frozen, bufs, *iv)
            self._commit_eval_buffers(new_buf)
            if isinstance(out, list):
                return [Tensor(o) for o in out]
            return Tensor(out)
        finally:
            coll.set_mesh(prev_mesh)


class _PipeStrategy:
    """Minimal strategy carrier for a runner-built pipeline engine."""

    def __init__(self, pipeline_configs):
        self.pipeline_configs = pipeline_configs


class PipelinedRunner:
    """``Model.fit``'s engine on pipeline meshes (ISSUE 15 /
    DESIGN-PERF.md §Unified dispatch engine): the DistributedRunner
    duck-type over the compiled pipeline-schedule engine
    (``fleet.meta_parallel.pipeline_parallel.PipelineParallel``), so a
    fit on a pp or dp×mp×pp mesh rides the SAME fold machinery —
    ``GroupDispatcher`` grouping, ``AutoFoldTuner`` K selection,
    donated carry, deferred wrapper sync — as the single-chip and
    dp/mp mesh paths.

    ``accumulate_steps`` maps ``fit(accumulate_grad_batches=M)`` onto
    the schedule's M microbatches (identical semantics: one optimizer
    step per M batches, gradient averaged — and the pipeline's bubble
    fraction (P-1)/(M+P-1) shrinks with M).
    """

    def __init__(self, network, optimizer, loss_fn=None,
                 mesh: Optional[Mesh] = None, accumulate_steps: int = 1,
                 amp_level: Optional[str] = None,
                 amp_dtype: str = "bfloat16", remat: Optional[bool] = None,
                 pipeline_configs: Optional[dict] = None):
        from .fleet.meta_parallel.pipeline_parallel import PipelineParallel
        self.network = network
        self.optimizer = optimizer
        self.mesh = mesh or coll.ensure_mesh()
        self.accumulate_steps = max(int(accumulate_steps), 1)
        if amp_level:
            import warnings
            warnings.warn(
                "PipelinedRunner: amp_level is not supported by the "
                "pipeline-schedule engine yet; training runs full "
                "precision")
        # the caller's pipeline_configs pass THROUGH (dispatch_mode,
        # unroll_ticks, remat_stage are documented engine knobs — a
        # strategy-exported knob must never silently no-op); the
        # runner's resolved accumulate wins, and `remat` only fills a
        # remat_stage the caller left unset
        cfg = dict(pipeline_configs or {})
        cfg["accumulate_steps"] = self.accumulate_steps
        if remat is not None and "remat_stage" not in cfg:
            cfg["remat_stage"] = bool(remat)
        self._engine = PipelineParallel(
            network, None, _PipeStrategy(cfg), optimizer=optimizer,
            loss_fn=loss_fn)
        self._metric_acc = None

    # deferred wrapper sync: the same boundary protocol as
    # DistributedRunner / hapi TrainState — Model.fit sets the flag,
    # the engine defers its stacked-leaf wrapper commit to
    # sync_to_layers()
    @property
    def _defer_wrapper_sync(self):
        return self._engine._defer_wrapper_sync

    @_defer_wrapper_sync.setter
    def _defer_wrapper_sync(self, value):
        self._engine._defer_wrapper_sync = bool(value)

    def train_step(self, inputs, labels):
        """One whole-schedule dispatch for one train batch (the fold-0
        escape of ``Model.train_batch``); returns (loss, out_vals)."""
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            return self._engine.train_step(inputs, labels)
        finally:
            coll.set_mesh(prev_mesh)

    def train_steps_folded(self, groups, metric_fns=(),
                           metric_acc=None):
        """ONE rolled scan-of-K dispatch covering ``len(groups)`` whole
        train batches — every stage × microbatch of each — through the
        shared engine."""
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            return self._engine.train_steps_folded(
                groups, metric_fns=metric_fns, metric_acc=metric_acc)
        finally:
            coll.set_mesh(prev_mesh)

    def eval_step(self, inputs, labels):
        """Inline forward + loss over the synced Layer tree (no pp
        overlap — validation passes are boundary work)."""
        _watchdog.notify_step()
        ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        lbs = labels if isinstance(labels, (list, tuple)) else [labels]
        prev_mesh = coll.get_mesh()
        coll.set_mesh(self.mesh)
        try:
            self._engine.sync_to_layers()
            from ..autograd import tape as _tape
            with _tape.no_grad_ctx():
                out = self.network(Tensor(to_device_values(ins)[0]))
                loss_layer = self._engine._loss_layer()
                if loss_layer is not None:
                    loss = loss_layer(out,
                                      Tensor(to_device_values(lbs)[0]))
                    return loss._value, [out._value]
            return out._value, [out._value]
        finally:
            coll.set_mesh(prev_mesh)

    def sync_to_layers(self):
        self._engine.sync_to_layers()

    def invalidate_cache(self):
        self._engine.invalidate_cache()

    def compile_stats(self):
        return self._engine.compile_stats()
