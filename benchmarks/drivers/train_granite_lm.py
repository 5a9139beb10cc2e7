"""Driver: pretraining of Granite 4.0-H Micro as stage 0 of a pipeline,
built and run the way a user's script does it: ``paddle.seed`` ->
``GraniteHybridForCausalLM`` from its config -> ``optimizer.AdamW`` ->
``amp.decorate`` (bf16 O2, float32 master weights) ->
``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, every layer through ``fleet.recompute``, steps dispatched back
to back, the loss read every ``sync_every`` steps.  The window, the
counting of programs, the compiled step's facts and the memory readings
are ``train_lm.py``'s.

What is decided here: what makes a run of this family ``correct``.  Each
tolerance stands beside its comparison with its reason.
"""

from __future__ import annotations

import importlib
import math
import statistics
import time
from typing import Callable

import numpy as np

from ..harness import trace_reduce, traffic as traffic_gen
from ..harness.cells import BenchmarkError, Cell, load_peaks, sized
from ..harness.report import Record, RunOptions
from ..harness.spans import Spans
from . import train_lm
from .train_lm import Checks, ProgramCounter

# (a) Program logits (bf16 O2) against the reference, rms of the
# difference over the reference's rms, as the GPT check measures it.  The
# residual stream is rounded to bf16 (1.1e-3 of a value a rounding) twice
# a layer, a Mamba layer's projection, convolution, scan and gated norm
# each round once more, and at initialisation a logit is a small product
# of a row of the tied matrix with a stream that is mostly another row.
# Measured on the chip 1.60e-2 to 1.63e-2 over ten seeds; the reference with its
# weights rounded through float8_e4m3fn reads 1.95e-1 (PERF.md section 6).
LOGITS_RTOL = 5e-2
# (b) ssd_scan (chunked, bf16 products, float32 decays and sums) against
# the sequential float32 recurrence on the same bf16 inputs: y and the six
# gradients, largest error over largest value.  One bf16 ulp is 3.9e-3 of
# a value, and the backward pass rounds dy and the scaled x once each.
# Measured at most 5.8e-3 (dx); with x, B and C rounded through
# float8_e4m3fn the recurrence reads 2.8e-2 (dD) to 5.5e-2 (dA).
SCAN_RTOL = 1.5e-2
# (c) flash_attention on bf16 inputs against plain float32 attention at
# scale 1/64, forward and backward, largest error over largest value: the
# flash kernels' own limit in train_lm.py.  Measured 2.3e-3 to 4.0e-3;
# q, k, v through float8_e4m3fn read 3.1e-2 to 4.1e-2 (dv, which no
# rounded input reaches, 3.2e-3).
KERNEL_RTOL = train_lm.KERNEL_RTOL
VOCAB_PARTS = 8


def program_config(config: dict):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import GraniteHybridConfig
    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"] \
            or config["attention_bias"] or config["num_local_experts"] \
            or config["position_embedding_type"] != "nope" \
            or config["normalization_function"] != "rmsnorm":
        raise ValueError("models/granite_hybrid.py has a SiLU-gated MLP and "
                         "no routed experts, RMSNorm, no bias, no positions "
                         "and a tied head")
    return GraniteHybridConfig(
        vocab_size=config["published"]["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        attention_multiplier=config["attention_multiplier"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        shared_intermediate_size=config["shared_intermediate_size"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_chunk_size=config["mamba_chunk_size"],
        mamba_conv_bias=config["mamba_conv_bias"],
        mamba_proj_bias=config["mamba_proj_bias"],
        rms_norm_eps=config["rms_norm_eps"],
        initializer_range=config["initializer_range"],
        recompute=config["recompute"])


def build_runner(config: dict, seed: int, devices):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GraniteHybridForCausalLM,
                                   GraniteHybridPretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = GraniteHybridForCausalLM(program_config(config))
    opt = optimizer.AdamW(
        learning_rate=config["optimizer"]["learning_rate"],
        parameters=net.parameters(), multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh(config["mesh"], devices=devices)
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, GraniteHybridPretrainingCriterion(),
                             mesh=mesh)


def program_counters(layers) -> dict:
    """What the program counted as the step was traced: the chunks x
    heads of its scans over the Mamba layers ``layers``, the bytes of
    states one scan passes on, and the gauge ``recompute_layers``."""
    from paddle_tpu.observability import metrics
    reg = metrics.registry()

    def of(layer):
        return {"layer": str(layer)}

    return {
        "ssm_scan_chunks": sum(reg.counter(
            "ssm_scan_chunks_total", labels=of(i)).collect() for i in layers),
        "ssm_scan_state_bytes": reg.gauge(
            "ssm_scan_state_bytes", labels=of(layers[0])).collect() or 0,
        "recompute_layers": reg.gauge("recompute_layers").collect() or 0}


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def _largest_error(got, want):
    import jax.numpy as jnp
    return float(jnp.abs(got.astype(jnp.float32) - want).max()
                 / jnp.abs(want).max())


def check_logits(check: Checks, runner, family, config: dict, seq_len: int,
                 seed: int):
    """(a) the program's forward pass, as an evaluation calls it, against
    the family's float32 reference on one seeded sequence of the cell's
    length; the reference's head a part of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp
    home = runner.mesh.devices.flat[0]
    vocab = config["vocab_size"]
    ids = np.random.default_rng(seed + 2).integers(
        0, vocab, (1, seq_len), dtype=np.int64)
    got = jax.device_put(runner.predict_step([ids])._value[0], home)
    named = dict(runner.network.named_parameters())

    def param(name, rows=None):
        value = jax.device_put(named[name]._value, home)
        return (value if rows is None else value[rows]).astype(jnp.float32)

    @jax.jit
    def squares(got_, hidden_, rows):
        want = family.reference_logits(hidden_, rows.astype(jnp.float32),
                                       config)
        diff = got_.astype(jnp.float32) - want
        return jnp.sum(diff * diff), jnp.sum(want * want)

    hidden = family.reference_hidden(param, config, jnp.asarray(ids[0]))
    embedding = jax.device_put(named[family.EMBEDDING]._value, home)
    part = -(-vocab // VOCAB_PARTS)
    sums = [squares(got[:, a:a + part], hidden, embedding[a:a + part])
            for a in range(0, vocab, part)]
    err = math.sqrt(sum(float(s[0]) for s in sums)
                    / sum(float(s[1]) for s in sums))
    check(math.isfinite(err) and err < LOGITS_RTOL,
          f"(a) logits {(seq_len, vocab)} of a seeded sequence agree with "
          f"the float32 reference and its sequential recurrence: rms "
          f"difference {err:.2e} of the reference's rms (< {LOGITS_RTOL})")


def check_scan(check: Checks, family, config: dict, seq_len: int, seed: int):
    """(b) ``ssd_scan`` at the cell's shape on seeded bf16 inputs against
    the sequential recurrence in float32: y and the gradients of ``sum(y
    * w)`` by x, dt, A, B, C, D."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    chunk = config["mamba_chunk_size"]

    def draw(key):
        k = jax.random.split(key, 8)
        normal = jax.random.normal
        return (normal(k[0], (seq_len, heads, width), jnp.bfloat16),
                # steps and decays as the model starts with them
                jnp.exp(jax.random.uniform(
                    k[1], (seq_len, heads), jnp.float32, math.log(1e-3),
                    math.log(1e-1))),
                -jax.random.uniform(k[2], (heads,), jnp.float32, 1.0, 16.0),
                normal(k[3], (seq_len, groups, state), jnp.bfloat16),
                normal(k[4], (seq_len, groups, state), jnp.bfloat16),
                normal(k[5], (heads,), jnp.float32),
                normal(k[6], (seq_len, heads, width), jnp.bfloat16))

    *inputs, w = jax.jit(draw)(jax.random.PRNGKey(seed + 3))

    def weighted(x, dt, A, B, C, D, w_):
        y = ssm.ssd_scan(x, dt, A, B, C, D, chunk)
        return (y * w_).astype(jnp.float32).sum(), y

    grads, y = jax.jit(jax.grad(weighted, argnums=tuple(range(6)),
                                has_aux=True))(*inputs, w)
    want = family.reference_scan_grads(
        *(a.astype(jnp.float32) for a in inputs), w.astype(jnp.float32))
    for name, a, r in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                          (y,) + grads, want):
        err = _largest_error(a, r)
        check(math.isfinite(err) and err < SCAN_RTOL,
              f"(b) ssd_scan {name} {tuple(a.shape)} (chunk {chunk}, state "
              f"{state}) agrees with the sequential recurrence: largest "
              f"error {err:.2e} of the largest value (< {SCAN_RTOL})")


def check_attention(check: Checks, family, config: dict, seq_len: int,
                    seed: int):
    """(c) the public ``flash_attention`` as the attention layer calls
    it, q scaled by what the kernels' own scale leaves of
    ``attention_multiplier``, against plain float32 attention at that
    multiplier, forward and backward."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dim = config["hidden_size"] // heads
    left = config["attention_multiplier"] * math.sqrt(dim)
    shapes = ((1, seq_len, heads, dim), (1, seq_len, kv, dim),
              (1, seq_len, kv, dim), (1, seq_len, heads, dim))
    q, k, v, w = jax.jit(lambda key: tuple(
        jax.random.normal(key_, shape, jnp.bfloat16) for key_, shape in zip(
            jax.random.split(key, 4), shapes)))(jax.random.PRNGKey(seed + 1))

    def weighted(q_, k_, v_, w_):
        out = pallas_ops.flash_attention.raw(
            (q_ * left).astype(q_.dtype), k_, v_, causal=True)
        return (out * w_).astype(jnp.float32).sum(), out

    (dq, dk, dv), out = jax.jit(jax.grad(
        weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
    want = family.reference_attention_grads(
        *(x[0].astype(jnp.float32).swapaxes(0, 1) for x in (q, k, v, w)),
        scale=float(config["attention_multiplier"]))
    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, dq, dk, dv), want):
        err = _largest_error(a[0], r.swapaxes(0, 1))
        check(math.isfinite(err) and err < KERNEL_RTOL,
              f"(c) flash_attention {name} {tuple(a.shape)}, q scaled by "
              f"{left:g}, agrees with plain float32 attention at scale "
              f"{config['attention_multiplier']:g}: largest error {err:.2e} "
              f"of the largest value (< {KERNEL_RTOL})")


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------
def run(cell: Cell, options: RunOptions, say: Callable[[str], None]) -> Record:
    import jax
    family = importlib.import_module(
        f"benchmarks.families.{cell.config['family']}")
    config = sized(cell.config, options.rehearse)
    mix = sized(cell.traffic, options.rehearse)
    batch, seq_len = mix["batch"], mix["seq_len"]
    kinds = config["layer_types"]
    mamba_layers = [i for i, kind in enumerate(kinds) if kind == "mamba"]
    attention_layers = len(kinds) - len(mamba_layers)
    tokens_per_step = batch * seq_len
    devices = jax.devices()[:cell.chips]
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
            f"parameters on this chip: {len(mamba_layers)} Mamba-2 layers "
            f"and {attention_layers} attention layer(s), "
            f"{config['vocab_size']} rows of the tied matrix) and "
            f"{len(ring)} batches of b{batch} x s{seq_len} in "
            f"{clock() - t:.1f} s")

        losses, warm_s = [], []
        for i in range(2):
            t = clock()
            losses.append(float(runner.train_step(*ring[i % len(ring)])))
            warm_s.append(clock() - t)
        say(f"first step {warm_s[0]:.2f} s, second {warm_s[1]:.2f} s")

        say("compiled train step:")
        step = train_lm.compiled_step(runner, ring[0], say)
        traced = program_counters(mamba_layers)
        limit = config["step_bytes_limit"]
        check(config["recompute"]
              and traced["recompute_layers"] == len(kinds)
              and step["step_bytes"] < limit,
              f"(d) the step recomputes {traced['recompute_layers']:g} of "
              f"{len(kinds)} layers and needs {step['step_bytes']} bytes on "
              f"a device (< {limit}); its scans were traced over "
              f"{traced['ssm_scan_chunks']:g} chunks x heads, and one passes "
              f"{traced['ssm_scan_state_bytes']:g} bytes of states from "
              f"chunk to chunk")
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites ({attention_layers} attention "
                f"layer(s) x (forward, the forward again, dq, dkv) = "
                f"{4 * attention_layers}; at least {3 * attention_layers})")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        else:
            check(step["kernel_sites"] >= 3 * attention_layers, "(c) " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        check_logits(check, runner, family, config, seq_len, options.seed)
        check_scan(check, family, config, seq_len, options.seed)
        check_attention(check, family, config, seq_len, options.seed)
        say(f"  ({clock() - t:.1f} s)")

        losses.append(float(runner.train_step(*ring[2 % len(ring)])))
        say(f"set-up built or loaded {counter.built} programs; the "
            f"persistent cache did not hold {counter.missed} of them")

        before = train_lm.counters(counter, runner)
        window = train_lm.measure(cell, options, runner, ring,
                                  mix["sync_every"], spans, say)
        after = train_lm.counters(counter, runner)

    window_losses = [float(x) for x in jax.device_get(window.losses)]
    failed = window.raised + sum(
        1 for v in window_losses if not math.isfinite(v))
    say("(d) the loss:")
    train_lm.check_losses(check, losses + window_losses,
                          config["vocab_size"])
    check(failed == 0,
          f"{failed} of {window.attempted} steps of the window failed")

    train_lm.say_memory(say, devices, "at the window's end")
    held = train_lm.memory_readings(devices, "bytes_in_use")
    memory_peak = train_lm.fullest_device_peak(
        setup_peak, held,
        train_lm.memory_readings(devices, "peak_bytes_reserved"))
    if memory_peak is not None:
        say(f"peak on the fullest device: {memory_peak} bytes by the "
            f"runtime; {max(held) + step['beside_arguments_bytes']} by what "
            f"it holds and the compiled step's memory_analysis()")

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
        raise BenchmarkError(
            f"the trace shows {ran:g} Mosaic kernels a step and the "
            f"compiled step holds {step['kernel_sites']} tpu_custom_call "
            "sites")
    return trace
