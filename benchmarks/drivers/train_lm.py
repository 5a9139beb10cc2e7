"""Driver: causal language-model pretraining, built and run the way a
user's script does it.

The job is built through the program's normal entry points, in the order
``chip_smoke.py`` builds its smoke: ``paddle.seed`` -> the model from its
config -> ``optimizer.AdamW`` -> ``amp.decorate`` (bf16 O2, float32 master
weights) -> ``collective.build_mesh`` -> ``DistributedRunner``, and run
with ``runner.train_step`` on numpy batches, so that the transfer to the
device is inside the step as in a user's loop.  Steps are dispatched back
to back and the loss is read every ``sync_every`` steps, as a user's loop
logs.

What is decided here and nowhere else: the end-to-end metrics of a
training cell (``tokens_per_s``, ``mfu``, ``peak_hbm_gb``) and what makes
a run ``correct``.  The tolerances stand beside the comparisons.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import math
import os
import shutil
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from ..harness import trace_reduce, traffic as traffic_gen
from ..harness.cells import (BenchmarkError, Cell, least_seconds,
                             load_peaks, sized)
from ..harness.report import Record, RunOptions
from ..harness.spans import Spans

# Program logits (bf16 O2) against the plain float32 reference, as the
# root-mean-square of the difference over the root-mean-square of the
# reference.  A bf16 result carries 8 bits: rounding it once costs
# 2^-9/sqrt(3) = 1.1e-3 of its size, and the residual stream is rounded
# twice a layer, so 24 layers come to about 1e-2 (measured on the chip:
# PERF.md section 6).  An 8-bit float with 3 bits of mantissa costs 16
# times as much a rounding and lands above 1e-1, and a wrong or missing
# layer moves the logits by their own size.
LOGITS_RTOL = 3e-2
# The public flash_attention on bf16 inputs against plain float32
# attention, forward and backward, as the largest error over the largest
# reference value.  One bf16 ulp is 2^-8 = 3.9e-3 of a value; the kernels
# measured 1.4e-3 to 6.3e-3 on the chip (PERF.md section 6).  An e4m3
# product has an ulp of 6.2e-2 and fails.
KERNEL_RTOL = 2e-2
# criterion 2: the first loss against ln(vocabulary)
FIRST_LOSS_ATOL = 0.5
# the reference's output head runs over the vocabulary in this many parts
VOCAB_PARTS = 8


# --------------------------------------------------------------------------
# building the job
# --------------------------------------------------------------------------
def build_runner(config: dict, seed: int, devices):
    """Seeded model, AdamW with master weights, bf16 O2 and the criterion
    on the configuration's mesh."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    if config["activation_function"] != "gelu_new" or \
            not config["tie_word_embeddings"]:
        raise ValueError("models/gpt.py has the tanh GELU and a tied head "
                         "only")
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_hidden_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout_prob=config["resid_pdrop"],
        attention_probs_dropout_prob=config["attn_pdrop"],
        initializer_range=config["initializer_range"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        recompute=config["recompute"]))
    opt = optimizer.AdamW(
        learning_rate=config["optimizer"]["learning_rate"],
        parameters=net.parameters(), multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh(config["mesh"], devices=devices)
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, GPTPretrainingCriterion(), mesh=mesh)


# --------------------------------------------------------------------------
# counting what jax builds
# --------------------------------------------------------------------------
class ProgramCounter:
    """Counts, through ``jax.monitoring``, every program jax builds or
    loads (one ``backend_compile`` event each, whichever function it is
    for) and how many of them the persistent cache did not hold."""

    BUILT = "/jax/core/compile/backend_compile_duration"
    MISSED = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.built = 0
        self.missed = 0

    def _on_duration(self, event, duration, **kw):
        self.built += event == self.BUILT

    def _on_event(self, event, **kw):
        self.missed += event == self.MISSED

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as m
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)
        try:
            yield self
        finally:
            m.unregister_event_duration_listener(self._on_duration)
            m.unregister_event_listener(self._on_event)


def memory_readings(devices, key: str) -> list:
    """``memory_stats()[key]`` of every device; None where the backend
    reports none (a CPU)."""
    return [(d.memory_stats() or {}).get(key) for d in devices]


MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
               "peak_bytes_reserved")


def say_memory(say, devices, when: str):
    """The runtime's own account of every device, for whoever reads the
    log; nothing where the backend keeps none."""
    if devices[0].memory_stats():
        say(f"bytes by device {when}: " + ", ".join(
            f"{key} {memory_readings(devices, key)}" for key in MEMORY_KEYS))


def fullest_device_peak(setup_peak: list, held: list,
                        reserved: list) -> Optional[int]:
    """The most bytes any device of the cell had to give, from three
    readings of the runtime, each a list by device: ``peak_bytes_in_use``
    after the program's own set-up, and ``bytes_in_use`` and
    ``peak_bytes_reserved`` at the window's end.

    The runtime counts the buffers it handed out (parameters, optimizer
    state, batches) as in use and keeps a running program's temporaries
    in a reservation of their own, outside that count (PERF.md section
    2).  So a device's peak is what it holds plus its largest
    reservation, or what set-up peaked at where that is more: a
    transient of set-up that exceeds the step is what stops a user's
    larger model.  The benchmark's reference check runs between the
    readings; it needs less than the step's temporaries, and what it held
    is gone by the window's end.  None where the backend reports no
    memory (a CPU)."""
    if None in setup_peak + held + reserved:
        return None
    return max(max(s, h + r) for s, h, r in zip(setup_peak, held, reserved))


def step_programs(runner) -> int:
    """Executables the jitted train step holds."""
    return int(runner._step_fn._cache_size())


def retraces() -> float:
    from paddle_tpu.observability import metrics
    return metrics.registry().counter("dispatch_retraces_total").collect()


def dispatch_wall() -> dict:
    from paddle_tpu.observability import metrics
    return metrics.registry().histogram("mesh_dispatch_wall_s").collect()


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
class Checks:
    def __init__(self, say):
        self.say = say
        self.failed: List[str] = []

    def __call__(self, ok: bool, what: str):
        self.say(("  ok: " if ok else "  WRONG: ") + what)
        if not ok:
            self.failed.append(what)


def check_logits(check: Checks, runner, family, config: dict, seq_len: int,
                 seed: int):
    """Criterion 1: the program's forward pass, as an evaluation calls
    it, against the family's plain float32 reference, on seeded sequences
    of the cell's length: one, or one for each data-parallel replica.

    The reference runs on the mesh's first device, which the cell has
    filled, so it is kept small: one sequence at a time, one layer's
    float32 parameters at a time, and the output head a part of the
    vocabulary at a time.  Beside the model it holds the embedding and
    one sequence's logits as the program keeps them (bf16) and a few
    ``[seq_len, vocab / VOCAB_PARTS]`` float32 arrays."""
    import jax
    import jax.numpy as jnp
    home = runner.mesh.devices.flat[0]
    n_seq = int(config["mesh"].get("dp", 1))
    vocab = config["vocab_size"]
    ids = np.random.default_rng(seed + 2).integers(
        0, vocab, (n_seq, seq_len), dtype=np.int64)
    got = runner.predict_step([ids])._value
    named = dict(runner.network.named_parameters())

    def param(name, rows=None):
        value = jax.device_put(named[name]._value, home)
        return (value if rows is None else value[rows]).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames="size")
    def squares(got_, hidden, embedding_, start, *, size):
        """Sums of squares, of the difference and of the reference, over
        ``size`` entries of the vocabulary from ``start``."""
        have = jax.lax.dynamic_slice_in_dim(got_, start, size, 1)
        rows = jax.lax.dynamic_slice_in_dim(embedding_, start, size, 0)
        want = family.reference_logits(hidden, rows.astype(jnp.float32))
        diff = have.astype(jnp.float32) - want
        return jnp.sum(diff * diff), jnp.sum(want * want)

    embedding = jax.device_put(named[family.EMBEDDING]._value, home)
    part = -(-vocab // VOCAB_PARTS)
    for i in range(n_seq):
        hidden = family.reference_hidden(param, config, ids[i])
        got_i = jax.device_put(got[i], home)
        sums = [squares(got_i, hidden, embedding, start,
                        size=min(part, vocab - start))
                for start in range(0, vocab, part)]
        err, size = (math.sqrt(sum(float(x[k]) for x in sums)
                               / (seq_len * vocab)) for k in (0, 1))
        check(math.isfinite(err) and err < LOGITS_RTOL * size,
              f"logits {(seq_len, vocab)} of seeded sequence {i} agree "
              f"with the plain float32 reference: rms difference "
              f"{err / size:.2e} of the reference's rms (< {LOGITS_RTOL})")


def check_kernels(check: Checks, runner, family, config: dict, batch: int,
                  seq_len: int, seed: int):
    """The public ``flash_attention``, forward and backward, at the cell's
    attention shape and sharding, against the family's plain float32
    attention.  The logits cannot prove the kernels: at initialisation
    attention moves them by about as much as bf16 rounds them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import collective
    from paddle_tpu.ops import pallas_ops
    mesh = runner.mesh
    home = mesh.devices.flat[0]
    heads = config["n_head"]
    shape = (batch, seq_len, heads, config["n_embd"] // heads)
    on_mesh = NamedSharding(mesh, P(
        collective.data_axes(mesh) or None, None,
        "mp" if mesh.shape["mp"] > 1 else None, None))
    q, k, v, w = jax.jit(
        lambda key: tuple(jax.random.normal(key_, shape, jnp.bfloat16)
                          for key_ in jax.random.split(key, 4)),
        out_shardings=(on_mesh,) * 4)(jax.random.PRNGKey(seed + 1))

    # w is an argument and not a closure: a closed-over array is a
    # constant of the program, and one that changes with the seed would
    # miss the compile cache in every run
    def weighted(q_, k_, v_, w_):
        out = pallas_ops.flash_attention.raw(q_, k_, v_, causal=True)
        return (out * w_).astype(jnp.float32).sum(), out

    (dq, dk, dv), out = jax.jit(
        jax.grad(weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
    want = family.reference_attention_grads(*(
        jax.device_put(x, home).astype(jnp.float32) for x in (q, k, v, w)))

    @jax.jit
    def error(got_, want_):
        return (jnp.abs(got_.astype(jnp.float32) - want_).max()
                / jnp.abs(want_).max())

    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, dq, dk, dv), want):
        err = float(error(jax.device_put(a, home), r))
        check(math.isfinite(err) and err < KERNEL_RTOL,
              f"flash_attention {name} {shape} agrees with plain float32 "
              f"attention: largest error {err:.2e} of the largest value "
              f"(< {KERNEL_RTOL})")


def check_losses(check: Checks, losses: List[float], vocab_size: int):
    """Criterion 2."""
    uniform = math.log(vocab_size)
    check(bool(losses) and all(math.isfinite(v) for v in losses),
          f"all {len(losses)} losses are finite")
    if not losses:
        return
    check(abs(losses[0] - uniform) < FIRST_LOSS_ATOL,
          f"the first loss {losses[0]:.4f} is within {FIRST_LOSS_ATOL} of "
          f"ln(vocabulary) = {uniform:.4f}")
    n = min(10, len(losses) // 2)
    first, last = sum(losses[:n]) / max(n, 1), sum(losses[-n:]) / max(n, 1)
    check(n > 0 and last < first,
          f"the mean of the last {n} losses {last:.4f} is below the mean "
          f"of the first {n}, {first:.4f}")


def compiled_step(runner, batch, say) -> dict:
    """Facts of the compiled train step: how many Mosaic custom calls it
    holds and the bytes it needs on a device."""
    compiled = runner.lower_step(*batch).compile()
    memory = compiled.memory_analysis()
    say(f"  compiled step, bytes a device: arguments "
        f"{memory.argument_size_in_bytes}, temporaries "
        f"{memory.temp_size_in_bytes}, outputs "
        f"{memory.output_size_in_bytes}, aliased "
        f"{memory.alias_size_in_bytes}")
    beside_arguments = int(memory.temp_size_in_bytes
                           + memory.output_size_in_bytes
                           - memory.alias_size_in_bytes)
    return {"kernel_sites": compiled.as_text().count(
                trace_reduce.MOSAIC_CALL),
            "beside_arguments_bytes": beside_arguments,
            "step_bytes": int(memory.argument_size_in_bytes)
            + beside_arguments}


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------
def trace_dir(cell: Cell) -> str:
    """Fixed and inside the checkout; holds the newest trace of the cell."""
    return os.path.join(cell.root, ".bench_traces", cell.name)


def counters(counter: ProgramCounter, runner) -> dict:
    """What the window may not move, and the program's own clock around
    its dispatches."""
    return {"step_programs": step_programs(runner), "retraces": retraces(),
            "built": counter.built, "dispatch": dispatch_wall()}


@dataclass
class Window:
    start_s: float = 0.0      # time.perf_counter() at the first sync ...
    end_s: float = 0.0        # ... and at the last
    group_s: list = field(default_factory=list)   # from sync to sync
    attempted: int = 0
    raised: int = 0
    losses: list = field(default_factory=list)    # device scalars
    xplane: Optional[str] = None


def measure(cell: Cell, options: RunOptions, runner, ring, sync_every: int,
            spans: Spans, say) -> Window:
    """Groups of ``sync_every`` steps dispatched back to back, the loss
    read at the end of each, until ``options.seconds`` have passed.  A
    traced run puts the profiler around the second group."""
    import jax
    w = Window()
    groups = 0
    w.start_s = mark = time.perf_counter()     # mark: the group's start
    while True:
        tracing = options.trace and groups == 1
        if tracing:
            shutil.rmtree(trace_dir(cell), ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir(cell), profiler_options=opts)
        for _ in range(sync_every):
            with spans.span("next_batch"):
                inputs, labels = ring[w.attempted % len(ring)]
            w.attempted += 1
            try:
                with spans.span("dispatch"):
                    w.losses.append(runner.train_step(inputs, labels))
            except Exception as e:        # counted, and the run is wrong
                w.raised += 1
                say(f"  step {w.attempted} raised {type(e).__name__}: {e}")
        with spans.span("sync"):
            if w.losses:
                float(w.losses[-1])
        w.end_s = time.perf_counter()
        w.group_s.append(w.end_s - mark)
        groups += 1
        if tracing:       # writing the trace out is no part of a group
            jax.profiler.stop_trace()
            w.xplane = trace_reduce.find_xplane(trace_dir(cell))
        mark = time.perf_counter() if tracing else w.end_s
        if w.raised or (w.end_s - w.start_s >= options.seconds
                        and groups >= (2 if options.trace else 1)):
            return w


def run(cell: Cell, options: RunOptions, say: Callable[[str], None]) -> Record:
    import jax
    family = importlib.import_module(
        f"benchmarks.families.{cell.config['family']}")
    config = sized(cell.config, options.rehearse)
    mix = sized(cell.traffic, options.rehearse)
    batch, seq_len = mix["batch"], mix["seq_len"]
    tokens_per_step = batch * seq_len
    if seq_len > config["n_positions"]:
        raise ValueError(f"seq_len {seq_len} exceeds the model's "
                         f"{config['n_positions']} positions")
    devices = jax.devices()[:cell.chips]
    # a share of an unknown peak means nothing: fail before the set-up
    peaks = None if options.rehearse else load_peaks(
        devices[0].device_kind, cell.root)
    check = Checks(say)
    spans = Spans()
    counter = ProgramCounter()
    clock = time.perf_counter

    with counter.listening():
        t = clock()
        runner = build_runner(config, options.seed, devices)
        ring = traffic_gen.token_batches(mix, config["vocab_size"],
                                         options.seed)
        say(f"built {cell.config_name} ({family.param_count(config)} "
            f"parameters) on mesh {config['mesh'] or 'of one device'} and "
            f"{len(ring)} batches of b{batch} x s{seq_len} in "
            f"{clock() - t:.1f} s")

        # warm-up: the cell's one shape.  Two steps, because the second
        # builds a second executable today (PERF.md section 5).
        losses = []
        warm_s = []
        for i in range(2):
            t = clock()
            losses.append(float(runner.train_step(*ring[i % len(ring)])))
            warm_s.append(clock() - t)
        say(f"first step {warm_s[0]:.2f} s, second {warm_s[1]:.2f} s")

        say("compiled train step:")
        step = compiled_step(runner, ring[0], say)
        want_kernels = 3 * config["n_layer"]
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites ({config['n_layer']} layers x "
                f"(forward + dq + dkv) = {want_kernels})")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        elif mix["kernels"] == "required":
            check(step["kernel_sites"] >= want_kernels, what)   # criterion 3
        else:
            say("  decides nothing in this cell: " + what)

        # read before the benchmark's reference puts anything of its own
        # on the device
        say_memory(say, devices, "after the program's set-up")
        setup_peak = memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        check_logits(check, runner, family, config, seq_len, options.seed)
        if mix["kernels"] == "required":
            check_kernels(check, runner, family, config, batch, seq_len,
                          options.seed)
        say(f"  ({clock() - t:.1f} s)")

        # one more step and a sync, so that the window starts on a device
        # that has just finished a step of the cell's own program
        losses.append(float(runner.train_step(*ring[2 % len(ring)])))
        say(f"set-up built or loaded {counter.built} programs; the "
            f"persistent cache did not hold {counter.missed} of them")

        before = counters(counter, runner)
        window = measure(cell, options, runner, ring, mix["sync_every"],
                         spans, say)
        after = counters(counter, runner)

    window_losses = [float(x) for x in jax.device_get(window.losses)]
    failed = window.raised + sum(
        1 for v in window_losses if not math.isfinite(v))
    check_losses(check, losses + window_losses, config["vocab_size"])
    check(failed == 0,
          f"{failed} of {window.attempted} steps of the window failed")

    say_memory(say, devices, "at the window's end")
    held = memory_readings(devices, "bytes_in_use")
    memory_peak = fullest_device_peak(
        setup_peak, held, memory_readings(devices, "peak_bytes_reserved"))
    if memory_peak is not None:
        say(f"peak on the fullest device: {memory_peak} bytes by the "
            f"runtime; {max(held) + step['beside_arguments_bytes']} by what "
            f"it holds and the compiled step's memory_analysis() "
            f"(temporaries + outputs - aliased)")

    # the harness's dispatch spans against the program's own histogram
    # of the same calls: two clocks around one thing
    mine = spans.between(window.start_s, window.end_s, "dispatch")
    theirs = {k: after["dispatch"][k] - before["dispatch"][k]
              for k in ("sum", "count")}
    if mine and theirs["count"]:
        mean_ms = 1e3 * sum(e.end_s - e.start_s for e in mine) / len(mine)
        say(f"host dispatch a step: {mean_ms:.3f} ms by the harness's "
            f"spans, {1e3 * theirs['sum'] / theirs['count']:.3f} ms by the "
            f"program's mesh_dispatch_wall_s")

    # The rate of the whole window: the tokens of the steps that completed
    # between the first and the last sync, over the host clock between
    # the two.  Whatever stalls a step (a compile, a host sync, a save)
    # shows in it.  The groups' own times are said for diagnosis only.
    seconds = window.end_s - window.start_s
    steps_done = window.attempted - failed
    tokens_per_s = steps_done * tokens_per_step / seconds
    flops_per_token = family.flops_per_token(config, seq_len)
    say(f"window: {steps_done} steps of {tokens_per_step} tokens in "
        f"{seconds:.6f} s between the first and the last sync; groups of "
        f"{mix['sync_every']} steps took "
        + " ".join(f"{g:.4f}" for g in window.group_s)
        + f" s, median {statistics.median(window.group_s):.4f}; "
        f"{flops_per_token:.4e} FLOPs a token")
    grew = {k: after[k] - before[k]
            for k in ("built", "step_programs", "retraces")}
    say(f"in the window jax built or loaded {grew['built']} programs, the "
        f"jitted step gained {grew['step_programs']} executables and the "
        f"program counted {grew['retraces']:g} retraces")
    end_to_end = {"tokens_per_s": tokens_per_s}
    if peaks is not None:
        end_to_end["mfu"] = 100.0 * tokens_per_s * flops_per_token / (
            len(devices) * peaks["bf16_flops_per_s"])
    if memory_peak is not None:
        end_to_end["peak_hbm_gb"] = memory_peak / 1e9

    obs = {
        "config": config, "traffic": mix, "family": family, "peaks": peaks,
        "chips": len(devices),
        "window": {"steps": steps_done, "seconds": seconds,
                   "start_s": window.start_s, "end_s": window.end_s},
        "spans": spans,
        "counters": {"before": before, "after": after},
        "setup": {"first_step_s": warm_s[0], "second_step_s": warm_s[1]},
        "compiled_step": step,
        "trace": None,
    }
    if window.xplane is not None:
        obs["trace"] = read_trace(window.xplane, obs, step, say)
    return Record(correct=not check.failed, attempted=window.attempted,
                  failed=failed, window_start_s=window.start_s,
                  end_to_end=end_to_end, devices=list(devices),
                  memory_peak_bytes=memory_peak, obs=obs)


def read_trace(xplane: str, obs: dict, step: dict, say):
    """The reduced trace, or None where it shows no device (a CPU)."""
    trace = trace_reduce.reduce(xplane, chips=obs["chips"])
    if trace is None:
        say("the trace holds no device instruction (a CPU has no device "
            "plane): no device metric can be read from it")
        return None
    ran = trace.kind_count("kernel") / max(trace.steps, 1)
    say(f"trace: {trace.steps} steps in {trace.window_s:.4f} s, {ran:g} "
        f"Mosaic kernels a step on a device")
    if ran != step["kernel_sites"]:
        # the kernels' time would be read wrong: no result at all
        raise BenchmarkError(
            f"the trace shows {ran:g} Mosaic kernels a step and the "
            f"compiled step holds {step['kernel_sites']} tpu_custom_call "
            "sites")
    if ran:
        cost = obs["family"].attention_step_cost(
            obs["config"], obs["traffic"]["batch"],
            obs["traffic"]["seq_len"])
        least, bound = least_seconds(cost["flops"] / obs["chips"],
                                     cost["bytes"] / obs["chips"],
                                     obs["peaks"])
        say(f"  the attention calls of a step need at least "
            f"{1e3 * least:.3f} ms on a device, bound by {bound}")
    return trace
