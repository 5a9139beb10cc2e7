"""Driver: pretraining of Solar-Open2-250B as stage 0, rank 0 of a stage
of 40 chips, built and run the way a user's script does it:
``paddle.seed`` -> ``SolarOpen2ForCausalLM`` from its config ->
``optimizer.AdamW`` -> ``amp.decorate`` (bf16 O2, float32 master weights)
-> ``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, the mixers the configuration names through ``fleet.recompute``,
steps dispatched back to back, the loss read every ``sync_every`` steps.
The window, the counting of programs, the compiled step's facts and the
memory readings are ``train_lm.py``'s; the loop around a step
(``Observed``), the program's traced forward pass, the routers' balancing
passes and the check of the losses are the Nemotron driver's, the reading
of a step off the optimizer's state the SambaY driver's.

What is decided here: what makes a run of this family ``correct``.  Each
tolerance stands beside its comparison with its reason; each lies between
the program's largest reading on the chip and what the float32 reference
reads computed through ``float8_e4m3fn`` (``family.rounded_through``, the
control that tests/benchmarks/test_solar_open2_cell.py keeps; PERF.md
section 2 has both).
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
from .train_lfm2_lm import logits_error
from .train_lm import Checks, ProgramCounter
from .train_nemotron_lm import (Observed, balance_routers, check_losses,
                                program_trace, read_trace)
from .train_sambay_lm import _producer_state, flash_tiles

# (a) Program logits (bf16 O2) against the reference given the program's
# own routing, rms of the difference over the reference's rms.  The
# residual stream is rounded to bf16 (1.1e-3 of a value a rounding) twice
# a layer; a mixer's projections, its gates and its output, the delta
# rule's v and output, and an expert's two hidden rows and their product
# each round once more.
LOGITS_RTOL = 2.5e-2
# (b) ... and against the reference that routes for itself: the router's
# products are float32 in both, on a stream that is bf16 in one, so an
# expert flips where two scores lie within the stream's rounding of each
# other (the share is said), and a flipped expert held here changes the
# token's routed part.  The stream starts at the embedding's N(0, 1), which
# the layers' outputs move by little, so few tokens flip.
OWN_CHOICE_RTOL = 3e-2
# (c) gated_delta_rule at the cell's shape, q, k, log alpha and beta in
# float32 and v in bf16 as the layer hands them over, against the float32
# recurrence a position at a time: o and the gradients by q, k, v,
# log alpha and beta, largest error over largest value.  Both sum in
# float32; what differs is the order of the sums (a chunk's triangle
# solved against a state carried from chunk to chunk) and one rounding of
# o and of dv to bf16, half an ulp, 2e-3 of a value.
RULE_RTOL = 1.5e-2
# (d) flash_attention on bf16 inputs, 8 query heads on 1 key/value head
# of width 128, against plain float32 attention at 1 / sqrt(128), forward
# and backward, largest error over largest value: the flash kernels' own
# limit in train_lm.py.
KERNEL_RTOL = train_lm.KERNEL_RTOL
# (f) One step of the compiled train step the window times (bf16 O2, the
# kernels', the experts' and the delta rule's backward passes, AdamW on
# float32 master weights), for every parameter of the GQA layer and of
# the first KDA layer: the gradient the step took, read off its first
# moment, (m' - beta1 m) / (1 - beta1), against jax.grad of the float32
# reference given the experts that very step chose (the buffer
# ``experts_chosen``, which the step returns), norm of the difference over
# the reference's norm, the worst leaf.  A bf16 gradient is itself
# rounded (2e-3 rms), as is every row it was summed from.
GRADS_RTOL = 8e-2
# ... and the change of the float32 master weights, held against AdamW
# (the family's, from the paper, in float64) applied to that gradient
# from the moments the step started with, at the learning rate the
# schedule gave the step.  At a warm-up's first rates (4e-7 at step 3) a
# weight of 1 to 2.8 (a norm's, A_log's) moves by one to seven of its
# float32 steps, so the new weight's own rounding is up to a third of its
# change: the first chip runs read 0.22 and 0.41 on A_log's eight values
# by the plain norm of the difference, 0.1 on the norms (the LFM2 cell's
# 1.1e-1 is the same rounding).  So an element's difference counts only
# beyond one float32 step of the new weight (the program rounds twice:
# the decay, then the step), and the norm of what is left, over the norm
# of the reference's change, the worst leaf, is held under the limit.
# Precision of the reference hardly moves it (the control reads what the
# program does), so the limit stands between the program's reading and
# 1, which a leaf left unmoved reads, with the more room above it; a step
# at twice the rate reads 0.5 on every matrix.
UPDATE_RTOL = 1e-1
# ... over the first 2048 positions of a sequence, on the host's CPU: the
# runner's state fills the chip but for the temporaries the runtime keeps
# reserved for the step, and the reference's float32 gradients of two
# layers (8 held experts each) are 1.3e9 bytes alone.
GRADS_POSITIONS = 2048
# (g) One step of the balancing rule moves a bias by the rate, up or
# down: a sign read the other way is a whole rate off.
BIAS_ATOL_IN_RATES = 0.5


def program_config(config: dict, routing_kept: int = 0):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import SolarOpen2Config
    published = config["published"]
    first, count = config["layers_held"]
    heads_first, heads = config["heads_held"]
    group = published["num_attention_heads"] \
        // published["num_key_value_heads"]
    if config["tie_word_embeddings"] \
            or config["experts_held"][1] != config["n_routed_experts"] \
            or count != config["num_hidden_layers"] \
            or heads != config["num_attention_heads"] \
            or heads // group != config["num_key_value_heads"]:
        raise ValueError("models/solar_open2.py has an untied head; "
                         "n_routed_experts, num_hidden_layers, "
                         "num_attention_heads and num_key_value_heads are "
                         "what is held here of the published model "
                         "(experts_held, layers_held, heads_held)")
    return SolarOpen2Config(
        vocab_size=published["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=published["num_hidden_layers"],
        num_attention_heads=published["num_attention_heads"],
        num_key_value_heads=published["num_key_value_heads"],
        head_dim=config["head_dim"],
        linear_attn_config=dict(config["linear_attn_config"]),
        gqa_layers=tuple(config["gqa_layers"]),
        use_rope=config["use_rope"], use_gqa_gate=config["use_gqa_gate"],
        kda_use_full_proj=config["kda_use_full_proj"],
        kda_allow_neg_eigval=config["kda_allow_neg_eigval"],
        kda_gate_rank=config["kda_gate_rank"],
        first_k_dense_replace=config["first_k_dense_replace"],
        n_routed_experts=published["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["initializer_range"],
        embedding_range=config["embedding_range"],
        router_bias_update_rate=config["router_bias"]["update_rate"],
        layers_held=(first, count), experts_held=tuple(config["experts_held"]),
        heads_held=(heads_first, heads),
        recompute=tuple(config["recompute"]), routing_kept=routing_kept)


def build_runner(config: dict, seed: int, devices, routing_kept: int = 0):
    """``routing_kept``: the tokens of a step, where the step is to return
    the experts it chose (check (f))."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (SolarOpen2ForCausalLM,
                                   SolarOpen2PretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = SolarOpen2ForCausalLM(program_config(config, routing_kept))
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
    return DistributedRunner(net, opt, SolarOpen2PretrainingCriterion(),
                             mesh=mesh)


def program_counters(kinds, first_layer: int) -> dict:
    """What the program counted: as its passes were traced, the calls of
    the delta rule, the mixers recomputed by kind and the flash kernels'
    tiles; as steps were observed, the pairs its held experts
    computed."""
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    layers = [str(first_layer + i) for i in range(len(kinds))]
    return {
        "delta_rule_calls": reg.counter("delta_rule_calls_total").collect(),
        "recompute_layers": {kind: int(reg.gauge(
            "recompute_layers", labels={"kind": kind}).collect() or 0)
            for kind in dict.fromkeys(kinds)},
        "flash_tiles": flash_tiles(),
        "moe_pairs": sum(reg.counter(
            "moe_pairs_total", labels={"layer": l}).collect()
            for l in layers),
        "moe_expert_tokens_max": [reg.gauge(
            "moe_expert_tokens_max", labels={"layer": l}).collect() or 0
            for l in layers],
        "moe_expert_tokens_mean": [reg.gauge(
            "moe_expert_tokens_mean", labels={"layer": l}).collect() or 0
            for l in layers]}


def host_device():
    """The host's CPU, where a reference too large to sit beside the
    runner on the chip runs; the default device where jax was started
    without a CPU backend."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def _as_g(check: Checks):
    """The Nemotron driver's balancing check says (i); here it is (g)."""
    return lambda ok, what: check(ok, what.replace("(i)", "(g)", 1))


def _as_i(check: Checks):
    """The Nemotron driver's check of the losses says (g); here it is
    (i)."""
    return lambda ok, what: check(ok, what.replace("(g)", "(i)", 1))


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def check_forward(check: Checks, runner, family, config: dict, seq_len: int,
                  seed: int):
    """(a), (b), (e): one seeded sequence through the program and through
    the reference, first as the program routed, then left to itself.  The
    reference reads the program's parameters where they lie, in bf16, and
    widens a layer's as it runs it."""
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
        return value if rows is None else value[rows]

    head = jax.device_put(named[family.HEAD]._value, home).T
    ids_d = jnp.asarray(ids[0])
    given = family.reference_forward(param, config, ids_d, routing=chosen)
    err = logits_error(family, given["hidden"], head, logits[0], vocab)
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
    err = logits_error(family, own["hidden"], head, logits[0], vocab)
    agree = [float((np.sort(np.asarray(own["experts"][at]), -1) == np.sort(
        np.asarray(chosen[at]), -1)).all(-1).mean())
        for at in range(len(tokens))]
    check(math.isfinite(err) and err < OWN_CHOICE_RTOL,
          f"(b) logits agree with the reference that routes for itself: "
          f"rms difference {err:.2e} (< {OWN_CHOICE_RTOL}); share of tokens "
          f"that take another expert, by layer: "
          + " ".join(f"{1 - a:.4f}" for a in agree))


def rule_inputs(config: dict, seq_len: int, seed: int):
    """Seeded inputs of one KDA layer's delta rule at the cell's shape, as
    the layer starts: q and k unit rows (q over sqrt(d)), v bf16, log
    alpha from A uniform in [1, 16] on softplus of a unit normal, beta
    2 sigmoid of a unit normal; and a weight for the output's sum."""
    import jax
    import jax.numpy as jnp
    heads, dim = config["num_attention_heads"], config["head_dim"]

    def draw(key):
        k = jax.random.split(key, 7)
        shape = (seq_len, heads, dim)
        unit = lambda a: a * jax.lax.rsqrt(                     # noqa: E731
            jnp.sum(a * a, -1, keepdims=True))
        a = jax.random.uniform(k[3], (heads,), jnp.float32, 1.0, 16.0)
        return (unit(jax.random.normal(k[0], shape)) * dim ** -0.5,
                unit(jax.random.normal(k[1], shape)),
                jax.random.normal(k[2], shape, jnp.bfloat16),
                -a[:, None] * jax.nn.softplus(jax.random.normal(k[4], shape)),
                2.0 * jax.nn.sigmoid(jax.random.normal(k[5], shape[:2])),
                jax.random.normal(k[6], shape, jnp.float32))

    return jax.jit(draw)(jax.random.PRNGKey(seed + 3))


def check_rule(check: Checks, family, config: dict, seq_len: int, seed: int):
    """(c) ``gated_delta_rule`` at the cell's shape against the float32
    recurrence a position at a time: o and the gradients of ``sum(o *
    w)`` by q, k, v, log alpha and beta."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_rule
    *xs, w = rule_inputs(config, seq_len, seed)

    def weighted(*xs_):
        o = delta_rule.gated_delta_rule(*xs_, family.CHUNK)
        return (o * w).sum(), o

    grads, o = jax.jit(jax.grad(weighted, argnums=tuple(range(5)),
                                has_aux=True))(*xs)
    want = family.reference_kda_grads(
        *(x.astype(jnp.float32) for x in xs), w)
    heads, dim = config["num_attention_heads"], config["head_dim"]
    for name, a, r in zip(("o", "dq", "dk", "dv", "dlog_alpha", "dbeta"),
                          (o,) + grads, want):
        err = _largest_error(a, r)
        check(math.isfinite(err) and err < RULE_RTOL,
              f"(c) gated_delta_rule {name} {tuple(a.shape)} ({heads} heads "
              f"of {dim}, chunk {family.CHUNK}) agrees with the recurrence a "
              f"position at a time: largest error {err:.2e} of the largest "
              f"value (< {RULE_RTOL})")


def check_attention(check: Checks, family, config: dict, seq_len: int,
                    seed: int, rehearse: bool):
    """(d) the public ``flash_attention`` as the GQA layer calls it against
    plain float32 attention at ``1 / sqrt(head)`` (on the host's CPU),
    forward and backward, and the Mosaic calls the compiled pair holds."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dim = config["head_dim"]
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
    # the reference's score maps, [S, S] float32 a head with their
    # gradients, would not fit beside the runner: on the host's CPU
    cpu = host_device()
    with jax.default_device(cpu):
        want = family.reference_attention_grads(
            *(jax.device_put(x[0], cpu).astype(jnp.float32).swapaxes(0, 1)
              for x in (q, k, v, w)), scale=1.0 / math.sqrt(dim))
        want = [np.asarray(r) for r in want]
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
        check(sites == 3, "(d) " + what)


def check_step(check: Checks, observed: Observed, family, config: dict,
               batch, step: int):
    """(f) the runner's own ``train_step``, the executable the window
    times, run once as step ``step`` on the ring's first batch with the
    loss taken over each sequence's first GRADS_POSITIONS positions (the
    labels after them are ParallelCrossEntropy's ``ignore_index``: every
    mixer is causal, so the reference runs on those positions alone).
    For the GQA layer and the first KDA layer: the gradient the step took,
    read off its first moment, against the reference's given the experts
    the step chose; and the master weights' change against the family's
    AdamW on that gradient: :func:`compare_step` on
    :func:`step_readings`."""
    compare_step(check, family, config,
                 step_readings(observed, family, config, batch, step))


def step_readings(observed: Observed, family, config: dict, batch,
                  step: int) -> dict:
    """(f)'s half on the chip: the step run, and host copies of what the
    comparison reads (the parameters the step started from, the checked
    leaves' state before and after it, the experts it chose)."""
    runner, net = observed.runner, observed.net
    ids, labels = (np.asarray(x[0]) for x in batch)
    seq = ids.shape[1]
    positions = min(seq, GRADS_POSITIONS)
    names = [n for l in family.checked_layers(config)
             for n in family.layer_parameters(config, l)]
    masked = labels.copy()
    masked[:, positions:] = runner.loss_fn.loss_fn.ignore_index
    # the values the step starts from, on the host: the step donates them
    values = {n: np.asarray(v._value) for n, v in (
        *net.named_parameters(), *net.named_buffers())
        if n.startswith(("model.", "lm_head."))}
    before = _producer_state(runner, names)
    programs = train_lm.step_programs(runner)
    lr = runner.optimizer.get_lr()
    loss = float(observed.train_step([ids], [masked]))
    return {"step": step, "ids": ids, "labels": labels, "names": names,
            "positions": positions, "values": values, "before": before,
            "lr": lr, "loss": loss,
            "gained": train_lm.step_programs(runner) - programs,
            "chosen": np.asarray(net.experts_chosen._value),
            "after": _producer_state(runner, names),
            "held": {n: np.asarray(runner._name_to_param[n]._value)
                     for n in names}}


def compare_step(check: Checks, family, config: dict, read: dict):
    """(f)'s half on the host's CPU: the reference from the host copies
    of :func:`step_readings`, and the comparison."""
    import jax
    import jax.numpy as jnp
    cpu = host_device()
    ids, labels, names = read["ids"], read["labels"], read["names"]
    step, lr, positions = read["step"], read["lr"], read["positions"]
    seq = ids.shape[1]
    layers = family.checked_layers(config)

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

    with jax.default_device(cpu):
        want = jax.jit(reference)(
            jax.device_put(read.pop("values"), cpu), jnp.asarray(ids),
            jnp.asarray(labels), list(jax.device_put(read["chosen"], cpu)))
        want = {n: np.asarray(g) for n, g in want.items()}
    rule = family.ADAMW
    grads, moves, rounded = {}, {}, True
    for n in names:
        grads[n], moves[n], same = leaf_errors(
            family, read["before"].pop(n), read["after"].pop(n),
            read["held"].pop(n), want.pop(n), step, lr)
        rounded &= same
    short = lambda n: n.split("layers.")[-1]        # noqa: E731
    kinds = family.kinds(config)
    worst = max(grads, key=grads.get)
    check(read["gained"] == 0
          and math.isfinite(grads[worst]) and grads[worst] < GRADS_RTOL,
          f"(f) step {step} of the compiled train step, on the loss over "
          f"the first {positions} of {seq} positions ({read['loss']:.4f}; "
          f"the executable the window times: the jitted step gained "
          f"{read['gained']} for it): the "
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
          f"the norm of the reference's change, each element beyond one "
          f"float32 step of the new weight, at most {moves[worst]:.2e} "
          f"({short(worst)}; < {UPDATE_RTOL}; a leaf left unmoved reads 1), "
          f"and the weight the next step reads is that weight rounded: "
          f"{rounded}; by parameter "
          + " ".join(f"{short(n)} {e:.1e}" for n, e in moves.items()))


def leaf_errors(family, was: dict, now: dict, held, want, step: int,
                lr: float):
    """Of one leaf: (the gradient the step took, read off its first
    moment, against ``want``; the weight's change against the family's
    AdamW on that gradient, each element's difference beyond one float32
    step of the new weight; whether ``held``, the parameter the next step
    reads, is the new weight rounded), the first two as the norm of the
    difference over the reference's norm.  A million elements at a
    time."""
    beta1 = family.ADAMW["beta1"]
    flat = {k: np.reshape(v, -1) for k, v in (
        ("w", was["weight"]), ("m", was["moment1"]), ("v", was["moment2"]),
        ("w'", now["weight"]), ("m'", now["moment1"]), ("held", held),
        ("want", want))}
    sums, rounded = np.zeros(4), True
    for a in range(0, flat["w"].size, 1 << 20):
        w, m, v, w_, m_, held_, want_ = (
            x[a:a + (1 << 20)] for x in flat.values())
        took = (m_ - beta1 * m) / (1.0 - beta1)
        move = family.reference_adamw(w, m, v, took, step, lr) - w
        beyond = np.maximum(np.abs((w_ - w) - move) - np.spacing(
            np.abs(w + move).astype(np.float32)), 0.0)
        pairs = ((took - want_, want_), (beyond, move))
        sums += [np.dot(x, x) for pair in pairs
                 for x in (np.asarray(y, np.float64) for y in pair)]
        rounded &= bool((held_ == w_.astype(held_.dtype)).all())
    return math.sqrt(sums[0] / sums[1]), math.sqrt(sums[2] / sums[3]), rounded


def kernel_sites(kinds, recomputed) -> int:
    """The Mosaic calls every step runs beside the experts' grouped
    products and their way back: the GQA layer's forward, dq and dkv, and
    the forward once more where its mixer is recomputed."""
    return sum(3 + (i in recomputed) for i, kind in enumerate(kinds)
               if kind == "gqa")


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
            f"{config['num_attention_heads']} heads of "
            f"{config['published']['num_attention_heads']}, "
            f"{config['n_routed_experts']} of {family.router_width(config)} "
            f"experts a layer, {config['vocab_size']} rows of the embedding "
            f"and of the head) and {len(ring)} batches of b{batch} x "
            f"s{seq_len} in {clock() - t:.1f} s")
        observed = Observed(runner, mix["sync_every"])
        t = clock()
        balance_routers(_as_g(check), runner, family, ring,
                        config["router_bias"], say)
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
              f"(h) the step recomputes the mixers the file names "
              f"({sorted(recomputed)}: by kind {traced['recompute_layers']}) "
              f"and needs {step['step_bytes']} bytes on a device (< {limit})")
        least = kernel_sites(kinds, recomputed)
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites: at least {least} of its own (the "
                f"GQA layer's forward, dq and dkv, one more where it is "
                f"recomputed); the rest are the experts' grouped products, "
                f"their way back and the KDA convolutions'")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        else:
            check(step["kernel_sites"] >= least, "(h) " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        # (f)'s step: a step of the job like any other, but its loss is
        # over a quarter of the positions and is no point of the curve (i)
        # reads; what it compares with runs after the window, as do the
        # other checks, whose seconds are no part of set-up
        t = clock()
        step_read = step_readings(observed, family, config, ring[0],
                                  len(losses) + 1)
        say(f"(f)'s step and its readings ({clock() - t:.1f} s)")

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
    routed = tokens_per_step * config["num_experts_per_tok"] * len(kinds)
    from paddle_tpu.incubate.distributed.models.moe import grouped
    rows = grouped.usual_rows(tokens_per_step, config["num_experts_per_tok"],
                              config["n_routed_experts"],
                              family.router_width(config))
    program = after["program"]
    say(f"the fullest layer held {pairs.sum(2).max()} pairs in a step; a "
        f"window is {rows} rows, and the later windows ran in "
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

    # the checks run once the window's trace is written, (a)-(c) on the
    # chip, (d) and (f)'s references on the host's CPU: the profiler lists
    # the CPU's programs and the chip's by number, each runtime counting
    # its own from 1, so a CPU program alive while the trace is taken can
    # hide the step's module from the reader of the blocks
    say("reference:")
    t = clock()
    check_forward(check, runner, family, config, seq_len, options.seed)
    check_rule(check, family, config, seq_len, options.seed)
    check_attention(check, family, config, seq_len, options.seed,
                    options.rehearse)
    compare_step(check, family, config, step_read)
    say(f"  ({clock() - t:.1f} s)")

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
