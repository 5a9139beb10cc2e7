"""Driver: pretraining of Keye-VL-2.0's language model as one rank of an
expert-parallel deployment, built and run the way a user's script does
it: ``paddle.seed`` -> ``KeyeLMForCausalLM`` from its config ->
``optimizer.AdamW`` -> ``amp.decorate`` (bf16 O2, float32 master weights)
-> ``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, steps dispatched back to back, the loss read every
``sync_every`` steps.  The window, the counting of programs, the
compiled step's facts and the memory readings are ``train_lm.py``'s.

What is decided here: what makes a run of this family ``correct``.  The
step's loss is ``L_LM + L_I``; the loss checks read ``L_LM``, which is the
loss less the indexer's loss the same step returned in a buffer.  Each
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

# Program logits (bf16 O2) against the reference given the program's own
# selection and routing, rms of the difference over the reference's rms,
# as the GPT check measures it.  The residual stream is rounded to bf16
# (8 bits: 1.1e-3 of a value a rounding) a few times a layer.  Measured
# on the chip 5.16e-3 to 5.21e-3 over six seeds; the reference with its
# weights rounded through float8_e4m3fn reads 5.4e-2 (PERF.md section 6).
LOGITS_RTOL = 1.5e-2
# ... and against the reference that selects and routes for itself.
# bf16 index scores and router logits flip the keys and the experts at
# the border (the two shares below say how many); a flipped expert held
# here changes a token's expert output by about an eighth of it.
# Measured 1.03e-2 to 1.05e-2, 3 to 8 % of the tokens with another expert
# a layer.  Looser than the limit above, under the 5.4e-2 of 8-bit
# weights, and it holds the whole forward pass: a missing layer moves
# the logits by their own size.
OWN_CHOICE_RTOL = 3e-2
# Index scores, rms difference over rms: bf16 inputs of the indexer's
# products, float32 sums, on a residual stream that is itself bf16.
# Measured 4.5e-3 (layer 0) rising to 8.9e-3 (layer 4), the same to 2 %
# on six seeds; 8-bit weights read 7.3e-2 to 8.0e-2.
SCORES_RTOL = 2.5e-2
# Share of the program's selected keys that the reference selects too,
# from its own float32 scores.  Measured 0.9985 (layer 0) falling to
# 0.9969 (layer 4): of a layer's 14.7 M selected keys 23 to 46 thousand
# sit so near a row's 2048th score that bf16 moves them across.  8-bit
# weights agree on 0.972 to 0.974.
SELECTION_SHARE = 0.988
# The Mosaic kernels of the selected-key attention on bf16 inputs against
# plain float32 attention under the same mask, forward and backward, and
# the head-averaged probabilities: largest error over largest value (one
# bf16 ulp is 3.9e-3; measured 2.6e-3 to 4.9e-3, as the flash kernels;
# the probabilities, float32 out of the kernel, 2e-6 to 5e-6).
KERNEL_RTOL = 2e-2
VOCAB_PARTS = 8


def program_config(config: dict):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import KeyeLMConfig
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"] \
            or config["attention_bias"] or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1:
        raise ValueError("models/keye_lm.py has SiLU-gated experts in "
                         "every layer, no bias and an untied head")
    sa = config["sa_config"]
    return KeyeLMConfig(
        vocab_size=config["published"]["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_local_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        q_chunk_size=sa["q_chunk_size"],
        initializer_range=config["initializer_range"])


def build_runner(config: dict, seed: int, devices):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (KeyeLMForCausalLM,
                                   KeyeLMPretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW" or config["recompute"] \
            or config["experts_held"][1] != config["num_experts"]:
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights, no recomputation, and "
                         "num_experts is the count held")
    paddle.seed(seed)
    net = KeyeLMForCausalLM(program_config(config))
    # a job's first steps: the rate rises to its peak over warmup_steps,
    # and the loop steps the schedule (Observed.train_step)
    peak = config["optimizer"]["learning_rate"]
    opt = optimizer.AdamW(
        learning_rate=optimizer.lr.LinearWarmup(
            peak, config["optimizer"]["warmup_steps"], 0.0, peak),
        parameters=net.parameters(), multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh(config["mesh"], devices=devices)
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, KeyeLMPretrainingCriterion(),
                             mesh=mesh)


class Observed:
    """``runner.train_step`` as the window calls it, with what a user's
    loop does around it: the learning-rate schedule steps, what each
    step returned beside its loss is kept (device scalars, nothing is
    waited for), and the program publishes its counters once a loss has
    been read: at the first dispatch after a sync the last step's
    buffers are on hand."""

    def __init__(self, runner, sync_every: int):
        self.runner, self.net = runner, runner.network
        self.sync_every = sync_every
        self.indexer_losses, self.expert_tokens = [], []
        self.observed = 0

    def observe(self):
        self.net.observe_step()
        self.observed += 1

    def train_step(self, inputs, labels):
        steps = len(self.indexer_losses)
        if steps and steps % self.sync_every == 0:
            self.observe()
        loss = self.runner.train_step(inputs, labels)
        self.runner.optimizer._learning_rate.step()
        self.indexer_losses.append(self.net.indexer_loss._value)
        self.expert_tokens.append(self.net.expert_tokens._value)
        return loss


def moe_pairs(layers: int) -> float:
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    return sum(reg.counter("moe_pairs_total",
                           labels={"layer": str(i)}).collect()
               for i in range(layers))


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def program_trace(runner, ids):
    """The program's forward pass with what it chose: logits, index
    scores and selection by layer, experts chosen, pairs by held expert."""
    import jax
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    net = runner.network

    @jax.jit
    def traced(params, frozen, buffers, ids_):
        out, _ = F.functional_call(net, params, buffers, (Tensor(ids_),),
                                   {"output_selection": True}, frozen=frozen)
        return [o._value for o in out]

    logits, _, scores, masks, chosen, tokens = traced(
        F.param_dict(net), F.frozen_dict(net), F.buffer_dict(net), ids)
    return logits, scores[:, 0], masks[:, 0], chosen, tokens


def relative_rms(got, want):
    import jax.numpy as jnp
    diff = got.astype(jnp.float32) - want
    return float(jnp.sqrt((diff * diff).mean() / (want * want).mean()))


def logits_error(family, hidden, head, got, vocab: int):
    """rms of (program logits - reference logits) over the reference's
    rms, the head a part of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(got_, hidden_, columns):
        want = family.reference_logits(hidden_, columns.astype(jnp.float32))
        diff = got_.astype(jnp.float32) - want
        return jnp.sum(diff * diff), jnp.sum(want * want)

    part = -(-vocab // VOCAB_PARTS)
    sums = [squares(got[:, a:a + part], hidden, head[:, a:a + part])
            for a in range(0, vocab, part)]
    return math.sqrt(sum(float(s[0]) for s in sums)
                     / sum(float(s[1]) for s in sums))


def check_forward(check: Checks, runner, family, config: dict, seq_len: int,
                  seed: int):
    """(a)-(e): one seeded sequence through the program and through the
    reference, first as the program selected and routed, then left to
    itself.  Returns what the kernel check reuses."""
    import jax
    import jax.numpy as jnp
    home = runner.mesh.devices.flat[0]
    vocab, topk = config["vocab_size"], config["sa_config"]["topk"]
    first, held = config["experts_held"]
    ids = np.random.default_rng(seed + 2).integers(
        0, vocab, (1, seq_len), dtype=np.int64)
    logits, scores, masks, chosen, tokens = program_trace(runner, ids)
    named = dict(runner.network.named_parameters())

    def param(name, rows=None):
        value = jax.device_put(named[name]._value, home)
        return (value if rows is None else value[rows]).astype(jnp.float32)

    head = jax.device_put(named[family.HEAD]._value, home)
    ids_d = jnp.asarray(ids[0])
    given = family.reference_forward(param, config, ids_d, selection=masks,
                                     routing=chosen)
    for i in range(config["num_hidden_layers"]):
        err = relative_rms(scores[i], given["scores"][i])
        check(err < SCORES_RTOL,
              f"(a) layer {i}: index scores {scores[i].shape} agree with "
              f"the reference's: rms difference {err:.2e} of its rms "
              f"(< {SCORES_RTOL})")
    shares = []
    for i in range(config["num_hidden_layers"]):
        own = family.selection_of(given["scores"][i], topk)
        mine = masks[i] != 0
        shares.append(float((own & mine).sum() / mine.sum()))
        flipped = int(mine.sum() - (own & mine).sum())
        check(shares[-1] >= SELECTION_SHARE,
              f"(b) layer {i}: the reference selects {shares[-1]:.4f} of "
              f"the program's {int(mine.sum())} keys from its own float32 "
              f"scores ({flipped} differ at the border; >= "
              f"{SELECTION_SHARE})")
    err = logits_error(family, given["hidden"], head, logits[0], vocab)
    check(math.isfinite(err) and err < LOGITS_RTOL,
          f"(c) logits {(seq_len, vocab)} agree with the reference given "
          f"the program's selection and routing: rms difference {err:.2e} "
          f"of the reference's rms (< {LOGITS_RTOL})")
    tokens = np.asarray(tokens)
    for i in range(config["num_hidden_layers"]):
        want = np.asarray(given["counts"][i])
        routed = np.asarray(chosen[i])
        here = int(((routed >= first) & (routed < first + held)).sum())
        check((tokens[i] == want).all() and int(tokens[i].sum()) == here,
              f"(e) layer {i}: the experts held computed {tokens[i].sum()} "
              f"pairs, the {here} of {routed.size} routed here "
              f"({here / routed.size:.4f}), expert by expert as the "
              f"reference counts them: none dropped; fullest "
              f"{tokens[i].max()}, mean {tokens[i].mean():.1f}")
    del given
    own = family.reference_forward(param, config, ids_d)
    err = logits_error(family, own["hidden"], head, logits[0], vocab)
    agree = [float((np.sort(np.asarray(own["experts"][i]), -1) == np.sort(
        np.asarray(chosen[i]), -1)).all(-1).mean())
        for i in range(config["num_hidden_layers"])]
    check(math.isfinite(err) and err < OWN_CHOICE_RTOL,
          f"(d) logits agree with the reference that selects and routes "
          f"for itself: rms difference {err:.2e} (< {OWN_CHOICE_RTOL}); "
          f"tokens with all their experts the same, by layer: "
          + " ".join(f"{a:.3f}" for a in agree))
    return masks[0]


def check_kernels(check: Checks, family, config: dict, seq_len: int,
                  mask, seed: int):
    """(g) the Mosaic kernels of ``ops/sparse_attention.py`` at the cell's
    shape under a selection the program made, against plain float32
    attention under the same mask, forward and backward."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import sparse_attention as dsa
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dim = config["head_dim"]
    if not dsa.kernels_eligible(seq_len, dim):
        check.say(f"  (g) not checked: no kernel is eligible at s{seq_len}, "
                  f"head {dim} here; the core runs in its plain form")
        return
    shapes = ((seq_len, heads, dim), (seq_len, kv, dim), (seq_len, kv, dim),
              (seq_len, heads, dim))
    q, k, v, w = jax.jit(lambda key: tuple(
        jax.random.normal(key_, shape, jnp.bfloat16) for key_, shape in zip(
            jax.random.split(key, 4), shapes)))(jax.random.PRNGKey(seed + 1))

    def weighted(q_, k_, v_, w_, mask_):
        out, lse = dsa.core(q_, k_, v_, mask_)
        return (out * w_).astype(jnp.float32).sum(), (out, lse)

    (dq, dk, dv), (out, lse) = jax.jit(jax.grad(
        weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v, w, mask)
    probs = jax.jit(dsa.mean_head_probs)(q, k, lse, mask)
    want = family.reference_core_grads(*(       # which is head-major
        x.astype(jnp.float32).swapaxes(0, 1) for x in (q, k, v, w)),
        mask != 0)
    want = tuple(x.swapaxes(0, 1) for x in want[:4]) + want[4:]

    @jax.jit
    def error(got_, want_):
        return (jnp.abs(got_.astype(jnp.float32) - want_).max()
                / jnp.abs(want_).max())

    for name, a, r in zip(("out", "dq", "dk", "dv", "mean probabilities"),
                          (out, dq, dk, dv, probs), want):
        err = float(error(a, r))
        check(math.isfinite(err) and err < KERNEL_RTOL,
              f"(g) sparse core {name} {tuple(a.shape)} agrees with plain "
              f"float32 attention under the same mask: largest error "
              f"{err:.2e} of the largest value (< {KERNEL_RTOL})")


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
    layers = config["num_hidden_layers"]
    tokens_per_step = batch * seq_len
    devices = jax.devices()[:cell.chips]
    peaks = None if options.rehearse else load_peaks(
        devices[0].device_kind, cell.root)
    check = Checks(say)
    spans = Spans()
    counter = ProgramCounter()
    clock = time.perf_counter

    def counters(observed):
        return {**train_lm.counters(counter, runner),
                "moe_pairs": moe_pairs(layers),
                "observed": observed.observed}

    with counter.listening():
        t = clock()
        runner = build_runner(config, options.seed, devices)
        ring = traffic_gen.token_batches(mix, config["vocab_size"],
                                         options.seed)
        say(f"built {cell.config_name} ({family.param_count(config)} "
            f"parameters on this chip: {config['num_experts']} of "
            f"{config['num_local_experts']} experts a layer, "
            f"{config['vocab_size']} rows of the vocabulary, {layers} "
            f"layers) and {len(ring)} batches of b{batch} x s{seq_len} in "
            f"{clock() - t:.1f} s")
        observed = Observed(runner, mix["sync_every"])

        losses, warm_s = [], []
        for i in range(2):
            t = clock()
            losses.append(float(observed.train_step(*ring[i % len(ring)])))
            warm_s.append(clock() - t)
        say(f"first step {warm_s[0]:.2f} s, second {warm_s[1]:.2f} s")

        say("compiled train step:")
        step = train_lm.compiled_step(runner, ring[0], say)
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites (the selected-key attention's "
                f"forward, dq, dkv and mean probabilities: 4 x {layers} "
                f"layers = {4 * layers}; the rest are the experts' grouped "
                f"products as XLA lowers jax.lax.ragged_dot)")
        if options.rehearse:
            say("  not checked in a rehearsal (no Mosaic kernel at the toy "
                "head width): " + what)
        else:
            check(step["kernel_sites"] >= 4 * layers, "(g) " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        mask = check_forward(check, runner, family, config, seq_len,
                             options.seed)
        check_kernels(check, family, config, seq_len, mask, options.seed)
        del mask
        say(f"  ({clock() - t:.1f} s)")

        losses.append(float(observed.train_step(*ring[2 % len(ring)])))
        observed.observe()
        say(f"set-up built or loaded {counter.built} programs; the "
            f"persistent cache did not hold {counter.missed} of them")

        before = counters(observed)
        window = train_lm.measure(cell, options, observed, ring,
                                  mix["sync_every"], spans, say)
        observed.observe()
        after = counters(observed)

    # L_LM of every step: the step's loss less the indexer's loss the
    # same step returned
    indexer = [float(x) for x in jax.device_get(observed.indexer_losses)]
    window_total = [float(x) for x in jax.device_get(window.losses)]
    failed = window.raised + sum(
        1 for v in window_total if not math.isfinite(v))
    lm_losses = [a - b for a, b in zip(losses + window_total, indexer)]
    say(f"losses, first and last step: L_LM {lm_losses[0]:.4f} -> "
        f"{lm_losses[-1]:.4f}, L_I {indexer[0]:.4f} -> {indexer[-1]:.4f} "
        f"(the sum over {layers} layers)")
    say("(f) the language-model loss:")
    train_lm.check_losses(check, lm_losses, config["vocab_size"])
    check(all(math.isfinite(v) for v in indexer),
          f"(f) all {len(indexer)} indexer losses are finite")
    check(failed == 0,
          f"{failed} of {window.attempted} steps of the window failed")
    pairs = np.asarray(jax.device_get(observed.expert_tokens))
    say(f"pairs a step on the experts held, over all steps: "
        f"{pairs.sum((1, 2)).min()} to {pairs.sum((1, 2)).max()} of "
        f"{tokens_per_step * config['num_experts_per_tok'] * layers} "
        f"routed; fullest expert {pairs.max()}, mean {pairs.mean():.1f}")

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
    # the compiled step holds the experts' grouped products twice, once
    # for each size of their buffer, and a step runs one of the two
    least = 4 * obs["config"]["num_hidden_layers"]
    if not least <= ran <= step["kernel_sites"]:
        raise BenchmarkError(
            f"the trace shows {ran:g} Mosaic kernels a step; the compiled "
            f"step holds {step['kernel_sites']} tpu_custom_call sites, at "
            f"least {least} of which every step runs")
    return trace
