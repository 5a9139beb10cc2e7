"""Driver: pretraining of LFM2-8B-A1B as stage 0, rank 0 of an EP4
deployment, built and run the way a user's script does it:
``paddle.seed`` -> ``Lfm2MoeForCausalLM`` from its config ->
``optimizer.AdamW`` -> ``amp.decorate`` (bf16 O2, float32 master weights)
-> ``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, the layers the configuration names through ``fleet.recompute``,
steps dispatched back to back, the loss read every ``sync_every`` steps.
The window, the counting of programs, the compiled step's facts and the
memory readings are ``train_lm.py``'s; the loop around a step
(``Observed``), the program's traced forward pass and the check of the
losses are the Nemotron driver's, the reading of a step off the
optimizer's state the SambaY driver's.

What is decided here: what makes a run of this family ``correct``.  Each
tolerance stands beside its comparison with its reason; each lies between
the program's largest reading on the chip and what the float32 reference
reads computed through ``float8_e4m3fn`` (``family.rounded_through``, the
control that tests/benchmarks/test_lfm2_cell.py keeps; PERF.md section 2
has both).
"""

from __future__ import annotations

import importlib
import math
import statistics
import time
from typing import Callable

import numpy as np

from ..harness import traffic as traffic_gen
from ..harness.cells import Cell, load_peaks, sized
from ..harness.report import Record, RunOptions
from ..harness.spans import Spans
from . import train_lm
from .train_granite_lm import _largest_error
from .train_lm import Checks, ProgramCounter
from .train_nemotron_lm import (Observed, check_losses, program_trace,
                                read_trace)
from .train_sambay_lm import _leaf_errors, _producer_state, flash_tiles

# (a) Program logits (bf16 O2) against the reference given the program's
# own routing, rms of the difference over the reference's rms.  The
# residual stream is rounded to bf16 (1.1e-3 of a value a rounding) twice
# a layer; a convolution operator's projection, its gated output, an
# attention layer's q, k, v and output, and an expert's two hidden rows
# and their gated product each round once more.  Measured on the chip
# 1.28e-2 to 1.29e-2 over eleven seeds; the control reads 2.08e-1.
LOGITS_RTOL = 2.5e-2
# (b) ... and against the reference that routes for itself.  The router's
# products are float32 in both, on a stream that is bf16 in one: an expert
# flips where two scores lie within the stream's rounding of each other
# (the share is said), and a flipped expert held here changes the token's
# routed part by about a quarter of it.  With every matrix at 0.02 the
# router's logits have a spread of 0.9 and the stream, 1.3e-2 off by the
# last layer, moves them by a hundredth of that: 2.4 to 3.1 % of the first
# expert layer's tokens and 6.6 to 7.2 % of the last's take another
# expert, and this read 3.47e-2 to 3.68e-2 over eleven seeds; the control
# reads 2.40e-1 with 42 to 61 % of the tokens on another expert.
OWN_CHOICE_RTOL = 6e-2
# (c) gated_short_conv on bf16 inputs (the projection's result and the
# taps as the program holds them) against the float32 loop a position at
# a time: y and the gradients by bcx and by the taps, largest error over
# largest value.  Sums are float32 in both; what differs is one rounding
# of each result to bf16, half an ulp, 2e-3 of a value.  Measured at most
# 3.5e-3 (dbcx); with bcx and the taps rounded through float8_e4m3fn the
# loop reads 5.0e-2 (dweight) to 1.14e-1 (y).
CONV_RTOL = 8e-3
# (d) flash_attention on bf16 inputs, 32 query heads on 8 key/value heads
# of width 64, against plain float32 attention at 1 / sqrt(64), forward
# and backward, largest error over largest value: the flash kernels' own
# limit in train_lm.py.  Measured 2.5e-3 to 5.8e-3; q, k, v through
# float8_e4m3fn read 5.0e-2 to 6.6e-2 (dv, which the rounded q and k reach
# through the probabilities only, 2.1e-2).
KERNEL_RTOL = train_lm.KERNEL_RTOL
# (f) One step of the compiled train step the window times (bf16 O2, the
# kernels', the experts' and the operator's hand-written backward passes,
# AdamW on float32 master weights), for every parameter of the first
# attention layer and the first convolution layer that carry experts.
# The gradient the step took is read off its first moment, (m' - beta1 m)
# / (1 - beta1), and held against jax.grad of the float32 reference given
# the experts that very step chose (the buffer ``experts_chosen``, which
# the step returns): norm of the difference over the reference's norm,
# the worst leaf.  A bf16 gradient is itself rounded (2e-3 rms), as is
# every row it was summed from.  Measured on the chip 2.3e-2 to 2.6e-2 at
# the worst leaf over eleven seeds; the control reads 3.0e-1 to 4.2e-1.
GRADS_RTOL = 8e-2
# ... and the change of the float32 master weights, held against AdamW
# (the family's, from the paper, in float64) applied to that gradient
# from the moments the step started with, at the learning rate the
# schedule gave the step: norm of the difference over the norm of the
# reference's change, the worst leaf.  This is arithmetic that the
# reference's precision hardly moves (the control reads what the program
# does), so the limit stands between the program's reading and 1, which a
# leaf left unmoved reads; a step at twice the rate reads 0.5.  The
# reading is float32's own steps: at a warm-up's first rates (4e-7 at step
# 3) a norm's weight of 1 moves by three to seven of its float32 steps,
# its decay's 4e-9 falls below one, and the worst of the eight norm
# vectors reads 1.09e-1 to 1.15e-1 on every seed of eleven, the matrices
# (0.02, steps of 2e-9) 2e-3.
UPDATE_RTOL = 3e-1
# ... over the first 2048 positions of a sequence: beside the runner's
# state and what the runtime keeps reserved for the step's temporaries
# the chip has little left, and the reference's backward pass is one
# program whose temporaries grow with the positions.
GRADS_POSITIONS = 2048
# (g) One step of the balancing rule moves a bias by the rate, up or
# down: a sign read the other way is a whole rate off, twice where an
# expert's load crossed the mean, and float32 adds the same in both.
BIAS_ATOL_IN_RATES = 0.5
BIAS = "expert_bias"


def program_config(config: dict, routing_kept: int = 0):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import Lfm2MoeConfig
    published = config["published"]
    first, count = config["layers_held"]
    if not config["tie_word_embeddings"] or config["conv_bias"] \
            or config["experts_held"][1] != config["num_experts"] \
            or count != config["num_hidden_layers"] \
            or list(config["layer_types"]) != list(
                published["layer_types"][first:first + count]) \
            or config["num_dense_layers"] != min(count, max(
                0, published["num_dense_layers"] - first)):
        raise ValueError("models/lfm2_moe.py has a tied head and no bias in "
                         "its convolution; num_experts, num_hidden_layers, "
                         "layer_types and num_dense_layers are what is held "
                         "here of the published model (layers_held, "
                         "experts_held)")
    return Lfm2MoeConfig(
        vocab_size=published["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=published["num_hidden_layers"],
        layer_types=tuple(published["layer_types"]),
        num_dense_layers=published["num_dense_layers"],
        layers_held=(first, count),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        conv_L_cache=config["conv_L_cache"], conv_bias=config["conv_bias"],
        num_experts=published["num_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        use_expert_bias=config["use_expert_bias"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=config["norm_eps"], rope_theta=float(config["rope_theta"]),
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config["initializer_range"],
        router_bias_update_rate=config["router_bias"]["update_rate"],
        recompute=tuple(config["recompute"]), routing_kept=routing_kept)


def build_runner(config: dict, seed: int, devices, routing_kept: int = 0):
    """``routing_kept``: the tokens of a step, where the step is to return
    the experts it chose (check (f))."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (Lfm2MoeForCausalLM,
                                   Lfm2MoePretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = Lfm2MoeForCausalLM(program_config(config, routing_kept))
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
    return DistributedRunner(net, opt, Lfm2MoePretrainingCriterion(),
                             mesh=mesh)


def program_counters(kinds, first_layer: int) -> dict:
    """What the program counted: as its passes were traced, the calls of
    the gated short convolution and of its backward pass, the bytes one
    call must move, the layers recomputed by kind and the flash kernels'
    tiles; as steps were observed, the pairs its held experts computed."""
    from paddle_tpu.observability import metrics
    reg = metrics.registry()

    def of(i):
        return {"layer": str(first_layer + i)}

    conv = [i for i, k in enumerate(kinds) if k.startswith("conv_")]
    moe = [i for i, k in enumerate(kinds) if k.endswith("_moe")]
    return {
        "short_conv_calls": {kind: reg.counter(
            "short_conv_calls_total", labels={"kind": kind}).collect()
            for kind in ("forward", "backward")},
        "short_conv_bytes": {str(first_layer + i): reg.gauge(
            "short_conv_bytes", labels=of(i)).collect() or 0 for i in conv},
        "recompute_layers": {kind: int(reg.gauge(
            "recompute_layers", labels={"kind": kind}).collect() or 0)
            for kind in dict.fromkeys(kinds)},
        "flash_tiles": flash_tiles(),
        "moe_pairs": sum(reg.counter(
            "moe_pairs_total", labels=of(i)).collect() for i in moe),
        "moe_expert_tokens_max": [reg.gauge(
            "moe_expert_tokens_max", labels=of(i)).collect() or 0
            for i in moe],
        "moe_expert_tokens_mean": [reg.gauge(
            "moe_expert_tokens_mean", labels=of(i)).collect() or 0
            for i in moe]}


def balance_routers(check: Checks, runner, family, ring, rule: dict, say):
    """A job that has run for a while has balanced routers: ``passes``
    forward passes in training mode over the ring's batches, each moving
    every router's bias by the balancing rule's one step (the same rule
    every training step applies afterwards), before anything is timed or
    checked.  The passes return the biases, the experts chosen and the
    held experts' pairs and nothing else, so the compiler drops the head
    and keeps no activation.  (g): the last pass's step of every bias
    against the reference's rule on the same choices."""
    passes, rate = rule["passes"], rule["update_rate"]
    if not passes:
        return
    import jax
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    net = runner.network
    biases = {n: b for n, b in net.named_buffers() if n.endswith(BIAS)}

    @jax.jit
    def one_pass(params, frozen, buffers, ids):
        out, new = F.functional_call(net, params, buffers, (Tensor(ids),),
                                     {"output_routing": True}, frozen=frozen)
        return ({n: new[n] for n in biases}, out[1]._value,
                new["expert_tokens"])

    params, frozen = F.param_dict(net), F.frozen_dict(net)
    buffers, held = F.buffer_dict(net), []
    for i in range(passes):
        before = {n: buffers[n] for n in biases}
        moved, chosen, tokens = one_pass(params, frozen, buffers,
                                         ring[i % len(ring)][0][0])
        buffers = {**buffers, **moved}
        if i in (0, passes - 1):
            held.append(np.asarray(tokens))
    off = max(float(abs(moved[n] - family.balanced_bias(
        before[n], chosen[at], rate)).max()) for at, n in enumerate(biases))
    check(off < BIAS_ATOL_IN_RATES * rate,
          f"(g) the last pass moved the {len(biases)} routers' biases as the "
          f"reference's rule does from the same choices: largest difference "
          f"{off:.2e} (< {BIAS_ATOL_IN_RATES} of the rate {rate:g})")
    for n, b in biases.items():
        b._value = moved[n]
    say(f"balanced the routers' biases over {passes} forward passes: the "
        f"pairs of the experts held, by expert layer, "
        + " ".join(str(v) for v in held[0].sum(1)) + " (fullest expert "
        f"{held[0].max()}) -> " + " ".join(str(v) for v in held[-1].sum(1))
        + f" (fullest expert {held[-1].max()})")


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def logits_error(family, hidden, embedding, got, vocab: int):
    """rms of (program logits - reference logits) over the reference's
    rms, the tied matrix a part of its rows at a time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(got_, hidden_, rows):
        want = family.reference_logits(hidden_, rows.astype(jnp.float32))
        diff = got_.astype(jnp.float32) - want
        return jnp.sum(diff * diff), jnp.sum(want * want)

    part = -(-vocab // train_lm.VOCAB_PARTS)
    sums = [squares(got[:, a:a + part], hidden, embedding[a:a + part])
            for a in range(0, vocab, part)]
    return math.sqrt(sum(float(s[0]) for s in sums)
                     / sum(float(s[1]) for s in sums))


def check_forward(check: Checks, runner, family, config: dict, seq_len: int,
                  seed: int):
    """(a), (b), (e): one seeded sequence through the program and through
    the reference, first as the program routed, then left to itself."""
    import jax
    import jax.numpy as jnp
    home = runner.mesh.devices.flat[0]
    vocab = config["vocab_size"]
    first, held = config["experts_held"]
    ids = np.random.default_rng(seed + 2).integers(
        0, vocab, (1, seq_len), dtype=np.int64)
    logits, chosen, tokens = program_trace(runner, ids)
    net = runner.network
    named = {**dict(net.named_parameters()), **dict(net.named_buffers())}

    def param(name, rows=None):
        value = jax.device_put(named[name]._value, home)
        return (value if rows is None else value[rows]).astype(jnp.float32)

    embedding = jax.device_put(named[family.EMBEDDING]._value, home)
    ids_d = jnp.asarray(ids[0])
    given = family.reference_forward(param, config, ids_d, routing=chosen)
    err = logits_error(family, given["hidden"], embedding, logits[0], vocab)
    check(math.isfinite(err) and err < LOGITS_RTOL,
          f"(a) logits {(seq_len, vocab)} of a seeded sequence agree with "
          f"the float32 reference given the program's routing: rms "
          f"difference {err:.2e} of the reference's rms (< {LOGITS_RTOL})")
    tokens = np.asarray(tokens)
    for at, layer in enumerate(net.moe_layers()):
        want = np.asarray(given["counts"][at])
        routed = np.asarray(chosen[at])
        here = int(((routed >= first) & (routed < first + held)).sum())
        check((tokens[at] == want).all() and int(tokens[at].sum()) == here,
              f"(e) layer {layer}: the experts held computed "
              f"{tokens[at].sum()} pairs, the {here} of {routed.size} "
              f"routed here ({here / routed.size:.4f}), expert by expert "
              f"as the reference's loop counts them: none dropped; "
              f"fullest {tokens[at].max()}, mean {tokens[at].mean():.1f}")
    del given
    own = family.reference_forward(param, config, ids_d)
    err = logits_error(family, own["hidden"], embedding, logits[0], vocab)
    agree = [float((np.sort(np.asarray(own["experts"][at]), -1) == np.sort(
        np.asarray(chosen[at]), -1)).all(-1).mean())
        for at in range(len(tokens))]
    check(math.isfinite(err) and err < OWN_CHOICE_RTOL,
          f"(b) logits agree with the reference that routes for itself: "
          f"rms difference {err:.2e} (< {OWN_CHOICE_RTOL}); share of tokens "
          f"that take another expert, by expert layer: "
          + " ".join(f"{1 - a:.4f}" for a in agree))


def check_operator(check: Checks, family, config: dict, seq_len: int,
                   seed: int):
    """(c) ``gated_short_conv`` at the cell's shape on seeded bf16 inputs,
    the taps as the model starts them, against the float32 loop a position
    at a time: y and the gradients of ``sum(y * w)`` by bcx and by the
    taps."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import short_conv
    channels, width = config["hidden_size"], config["conv_L_cache"]
    bound = 1.0 / math.sqrt(width)

    def draw(key):
        k = jax.random.split(key, 3)
        return (jax.random.normal(k[0], (seq_len, 3 * channels),
                                  jnp.bfloat16),
                jax.random.uniform(k[1], (channels, width), jnp.float32,
                                   -bound, bound).astype(jnp.bfloat16),
                jax.random.normal(k[2], (seq_len, channels), jnp.bfloat16))

    bcx, taps, w = jax.jit(draw)(jax.random.PRNGKey(seed + 3))

    def weighted(bcx_, taps_, w_):
        y = short_conv.gated_short_conv(bcx_, taps_)
        return (y * w_).astype(jnp.float32).sum(), y

    grads, y = jax.jit(jax.grad(weighted, argnums=(0, 1), has_aux=True))(
        bcx, taps, w)
    want = family.reference_conv_grads(
        *(a.astype(jnp.float32) for a in (bcx, taps, w)))
    form = short_conv.gated_short_conv_form(seq_len, channels, width)
    for name, a, r in zip(("y", "dbcx", "dweight"), (y,) + grads, want):
        err = _largest_error(a, r)
        check(math.isfinite(err) and err < CONV_RTOL,
              f"(c) gated_short_conv {name} {tuple(a.shape)} ({width} taps; "
              f"the {form} form) agrees with the loop a position at a time: "
              f"largest error {err:.2e} of the largest value (< {CONV_RTOL})")


def check_attention(check: Checks, family, config: dict, seq_len: int,
                    seed: int, rehearse: bool):
    """(d) the public ``flash_attention`` as the attention layer calls it
    against plain float32 attention at ``1 / sqrt(head)``, forward and
    backward, and the Mosaic calls the compiled pair holds."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dim = config["hidden_size"] // heads
    shapes = ((1, seq_len, heads, dim), (1, seq_len, kv, dim),
              (1, seq_len, kv, dim), (1, seq_len, heads, dim))
    q, k, v, w = jax.jit(lambda key: tuple(
        jax.random.normal(key_, shape, jnp.bfloat16) for key_, shape in zip(
            jax.random.split(key, 4), shapes)))(jax.random.PRNGKey(seed + 1))

    def weighted(q_, k_, v_, w_):
        out = pallas_ops.flash_attention.raw(q_, k_, v_, causal=True)
        return (out * w_).astype(jnp.float32).sum(), out

    compiled = jax.jit(jax.grad(
        weighted, argnums=(0, 1, 2), has_aux=True)).lower(q, k, v, w).compile()
    (dq, dk, dv), out = compiled(q, k, v, w)
    want = family.reference_attention_grads(
        *(x[0].astype(jnp.float32).swapaxes(0, 1) for x in (q, k, v, w)),
        scale=1.0 / math.sqrt(dim))
    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, dq, dk, dv), want):
        err = _largest_error(a[0], r.swapaxes(0, 1))
        check(math.isfinite(err) and err < KERNEL_RTOL,
              f"(d) flash_attention {name} {tuple(a.shape)}, {heads} query "
              f"heads on {kv}, agrees with plain float32 attention at "
              f"1/sqrt({dim}): largest error {err:.2e} of the largest value "
              f"(< {KERNEL_RTOL})")
    sites = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    form = pallas_ops._attention_form(heads, dim, seq_len, seq_len)
    what = (f"the pair, forward and backward, holds {sites} tpu_custom_call "
            f"sites (forward, dq, dkv: 3; the {form} form)")
    if rehearse:
        check.say("  not checked in a rehearsal (the interpreter lowers "
                  "kernels to plain HLO): " + what)
    else:
        check(sites == 3 and form == "packed", "(d) " + what)


def check_step(check: Checks, observed: Observed, family, config: dict,
               batch, step: int):
    """(f) the runner's own ``train_step``, the executable the window
    times, run once as step ``step`` on the ring's first batch with the
    loss taken over each sequence's first GRADS_POSITIONS positions (the
    labels after them are ParallelCrossEntropy's ``ignore_index``: every
    operator is causal, so the reference runs on those positions alone).
    For the first attention layer and the first convolution layer with
    experts: the gradient the step took, read off its first moment,
    against the reference's given the experts the step chose; and the
    master weights' change against the family's AdamW on that gradient."""
    import jax
    import jax.numpy as jnp
    runner, net = observed.runner, observed.net
    ids, labels = (np.asarray(x[0]) for x in batch)
    seq = ids.shape[1]
    positions = min(seq, GRADS_POSITIONS)
    layers = family.checked_layers(config)
    names = [n for l in layers for n in family.layer_parameters(config, l)]
    masked = labels.copy()
    masked[:, positions:] = runner.loss_fn.loss_fn.ignore_index

    def reference(values, ids_, labels_, routing):
        def param(name, rows=None):
            value = values[name] if rows is None else values[name][rows]
            return value.astype(jnp.float32)

        total = None
        for b in range(ids_.shape[0]):
            part = family.reference_layer_grads(
                param, config, ids_[b, :positions], labels_[b, :positions],
                [r[b * seq:b * seq + positions] for r in routing], layers)
            total = part if total is None else {
                n: total[n] + part[n] for n in part}
        # the program's mean is over every position of the batch
        return {n: g * (positions / seq / ids_.shape[0])
                for n, g in total.items()}

    # the values the step starts from: copies, since it donates them
    values = {n: jnp.array(v._value) for n, v in (
        *net.named_parameters(), *net.named_buffers())
        if n.startswith("model.")}
    before = _producer_state(runner, names)
    programs = train_lm.step_programs(runner)
    lr = runner.optimizer.get_lr()
    loss = float(observed.train_step([ids], [masked]))
    chosen = net.experts_chosen._value
    after = _producer_state(runner, names)
    want = jax.jit(reference)(values, jnp.asarray(ids), jnp.asarray(labels),
                              list(chosen))
    want = {n: np.asarray(g) for n, g in want.items()}
    del values
    rule = family.ADAMW
    grads, moves, rounded = {}, {}, True
    for n in names:
        grads[n], moves[n], same = _leaf_errors(
            family, before.pop(n), after.pop(n),
            np.asarray(runner._name_to_param[n]._value), want.pop(n), step,
            lr)
        rounded &= same
    short = lambda n: n.split("layers.")[-1]        # noqa: E731
    kinds = family.kinds(config)
    worst = max(grads, key=grads.get)
    check(train_lm.step_programs(runner) == programs
          and math.isfinite(grads[worst]) and grads[worst] < GRADS_RTOL,
          f"(f) step {step} of the compiled train step, on the loss over "
          f"the first {positions} of {seq} positions ({loss:.4f}; the "
          f"executable the window times: the jitted step gained "
          f"{train_lm.step_programs(runner) - programs} for it): the "
          f"gradients it took, (m' - beta1 m) / (1 - beta1), for the "
          f"{len(names)} parameters of layers "
          + " and ".join(f"{l} ({kinds[l]})" for l in layers)
          + f" agree with jax.grad of the float32 reference given the "
          f"experts the step chose: norm of the difference over the "
          f"reference's norm at most {grads[worst]:.2e} ({short(worst)}; < "
          f"{GRADS_RTOL}); by parameter "
          + " ".join(f"{short(n)} {e:.1e}" for n, e in grads.items()))
    worst = max(moves, key=moves.get)
    check(rounded and math.isfinite(moves[worst])
          and moves[worst] < UPDATE_RTOL,
          f"(f) the step's change of their float32 weights agrees with "
          f"AdamW (learning rate {lr:.3g}, {rule}) on those gradients from "
          f"the moments the step started with: norm of the difference over "
          f"the norm of the reference's change at most {moves[worst]:.2e} "
          f"({short(worst)}; < {UPDATE_RTOL}; a leaf left unmoved reads 1), "
          f"and the weight the next step reads is that weight rounded: "
          f"{rounded}; by parameter "
          + " ".join(f"{short(n)} {e:.1e}" for n, e in moves.items()))


def _as_i(check: Checks):
    """The Nemotron driver's check of the losses says (g); here it is
    (i)."""
    return lambda ok, what: check(ok, what.replace("(g)", "(i)", 1))


def kernel_sites(kinds, recomputed) -> int:
    """The Mosaic calls every step runs beside the experts' grouped
    products: an attention layer's forward, dq and dkv, and the forward
    once more where the layer is recomputed."""
    return sum(3 + (i in recomputed) for i, kind in enumerate(kinds)
               if kind.startswith("attention_"))


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
    kinds = family.kinds(config)
    first_layer = config["layers_held"][0]
    moe_layers = sum(k.endswith("_moe") for k in kinds)
    recomputed = set(config["recompute"])
    tokens_per_step = batch * seq_len
    devices = jax.devices()[:cell.chips]
    peaks = None if options.rehearse else load_peaks(
        devices[0].device_kind, cell.root)
    check = Checks(say)
    spans = Spans()
    counter = ProgramCounter()
    clock = time.perf_counter

    def counters(observed):
        counted = program_counters(kinds, first_layer)
        return {**train_lm.counters(counter, runner),
                "moe_pairs": counted["moe_pairs"],
                "observed": observed.observed, "program": counted}

    with counter.listening():
        t = clock()
        runner = build_runner(config, options.seed, devices, tokens_per_step)
        ring = traffic_gen.token_batches(mix, config["vocab_size"],
                                         options.seed)
        say(f"built {cell.config_name} ({family.param_count(config)} "
            f"parameters on this chip: layers {' '.join(kinds)}, "
            f"{config['num_experts']} of {family.router_width(config)} "
            f"experts an expert layer, {config['vocab_size']} rows of the "
            f"tied matrix) and {len(ring)} batches of b{batch} x s{seq_len} "
            f"in {clock() - t:.1f} s")
        observed = Observed(runner, mix["sync_every"])
        t = clock()
        balance_routers(check, runner, family, ring, config["router_bias"],
                        say)
        say(f"  ({clock() - t:.1f} s)")

        losses, warm_s = [], []
        for i in range(2):
            t = clock()
            losses.append(float(observed.train_step(*ring[i % len(ring)])))
            warm_s.append(clock() - t)
        say(f"first step {warm_s[0]:.2f} s, second {warm_s[1]:.2f} s")

        say("compiled train step:")
        step = train_lm.compiled_step(runner, ring[0], say)
        traced = program_counters(kinds, first_layer)
        say("counters: " + "; ".join(f"{k} {v}" for k, v in traced.items()))
        limit = config["step_bytes_limit"]
        want = {kind: sum(1 for i, k in enumerate(kinds)
                          if k == kind and i in recomputed)
                for kind in dict.fromkeys(kinds)}
        check(traced["recompute_layers"] == want
              and step["step_bytes"] < limit,
              f"(h) the step recomputes the layers the file names "
              f"({sorted(recomputed)}: by kind {traced['recompute_layers']}) "
              f"and needs {step['step_bytes']} bytes on a device (< {limit})")
        least = kernel_sites(kinds, recomputed)
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites: at least {least} of its own (an "
                f"attention layer's forward, dq and dkv, one more where it "
                f"is recomputed); the rest are the experts' grouped "
                f"products, of which a step runs the first window's")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        else:
            check(step["kernel_sites"] >= least, "(h) " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        check_forward(check, runner, family, config, seq_len, options.seed)
        check_operator(check, family, config, seq_len, options.seed)
        check_attention(check, family, config, seq_len, options.seed,
                        options.rehearse)
        # a step of the job like any other, but its loss is over a quarter
        # of the positions and is no point of the curve (i) reads
        check_step(check, observed, family, config, ring[0], len(losses) + 1)
        say(f"  ({clock() - t:.1f} s)")

        losses.append(float(observed.train_step(*ring[3 % len(ring)])))
        observed.observe()
        say(f"set-up built or loaded {counter.built} programs; the "
            f"persistent cache did not hold {counter.missed} of them")

        before = counters(observed)
        window = train_lm.measure(cell, options, observed, ring,
                                  mix["sync_every"], spans, say)
        observed.observe()
        after = counters(observed)

    window_losses = [float(x) for x in jax.device_get(window.losses)]
    failed = window.raised + sum(
        1 for v in window_losses if not math.isfinite(v))
    check_losses(_as_i(check), losses + window_losses, config)
    check(failed == 0,
          f"{failed} of {window.attempted} steps of the window failed")
    pairs = np.asarray(jax.device_get(observed.expert_tokens))
    routed = tokens_per_step * config["num_experts_per_tok"] * moe_layers
    from paddle_tpu.incubate.distributed.models.moe import grouped
    rows = grouped.usual_rows(tokens_per_step, config["num_experts_per_tok"],
                              config["num_experts"],
                              family.router_width(config))
    program = after["program"]
    say(f"the fullest expert layer held {pairs.sum(2).max()} pairs in a "
        f"step; a window is {rows} rows, and the later windows ran in "
        f"{int((pairs.sum(2) > rows).any(1).sum())} of {len(pairs)} steps")
    say(f"pairs a step on the experts held, over all steps: "
        f"{pairs.sum((1, 2)).min()} to {pairs.sum((1, 2)).max()} of "
        f"{routed} routed; fullest expert {pairs.max()}, mean "
        f"{pairs.mean():.1f}; the program counted moe_pairs_total "
        f"{after['moe_pairs']:g} over {after['observed']} observed steps, "
        f"moe_expert_tokens_max "
        + " ".join(f"{v:g}" for v in program["moe_expert_tokens_max"])
        + ", moe_expert_tokens_mean "
        + " ".join(f"{v:g}" for v in program["moe_expert_tokens_mean"]))

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
        obs["trace"] = read_trace(window.xplane, obs, step, least, say)
    return Record(correct=not check.failed, attempted=window.attempted,
                  failed=failed, window_start_s=window.start_s,
                  end_to_end=end_to_end, devices=list(devices),
                  memory_peak_bytes=memory_peak, obs=obs)
