"""Driver: pretraining of Nemotron 3 Nano 30B-A3B as stage 0, rank 0 of
an EP16 deployment, built and run the way a user's script does it:
``paddle.seed`` -> ``NemotronHForCausalLM`` from its config ->
``optimizer.AdamW`` -> ``amp.decorate`` (bf16 O2, float32 master weights)
-> ``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, the blocks the configuration names through ``fleet.recompute``,
steps dispatched back to back, the loss read every ``sync_every`` steps.
The window, the counting of programs, the compiled step's facts and the
memory readings are ``train_lm.py``'s.

What is decided here: what makes a run of this family ``correct``.  Each
tolerance stands beside its comparison with its reason; each lies between
the program's largest reading on the chip and what the float32 reference
reads with its weights (or a kernel's inputs) rounded through
``float8_e4m3fn`` (PERF.md section 2).
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
from .train_granite_lm import _largest_error
from .train_keye_lm import logits_error
from .train_lm import Checks, ProgramCounter

# (a) Program logits (bf16 O2) against the reference given the program's
# own routing, rms of the difference over the reference's rms, as the GPT
# check measures it.  The residual stream is rounded to bf16 (1.1e-3 of a
# value a rounding) once a block; a Mamba-2 block's projection,
# convolution, scan and gated norm each round once more, an expert's
# hidden rows once before they are squared and once after.  Measured on
# the chip 6.1e-3 over eleven seeds (5.7e-3 over eighteen with the
# embedding at N(0, 2^2)); the reference with its weights rounded through
# float8_e4m3fn reads 1.2e-1 (7.3e-2 there; PERF.md section 2).
LOGITS_RTOL = 2.5e-2
# (b) ... and against the reference that routes for itself.  The router's
# products are float32 in both, on a stream that is bf16 in one: an
# expert flips where two scores lie within the stream's rounding of each
# other (1.4 to 4.5 % of the tokens an expert block; the share is said),
# and a flipped expert held here changes the token's routed part by about
# a sixth of it.  Measured 9.0e-3 to 9.6e-3 over eleven seeds; 8-bit
# weights read 1.3e-1 with 34 to 59 % of the tokens on another expert.
# (At N(0, 2^2): 6.5e-3 to 6.9e-3, and 7.4e-2 with 29 to 41 %.  At 0.02
# the scores lie so close that 3 to 11 % take another expert and this
# reads 3.3e-2 on every seed: such a start is not correct by this limit.)
# It holds the whole forward pass: a missing block moves the logits by 8
# to 11 %.
OWN_CHOICE_RTOL = 3e-2
# (c) ssd_scan (chunks of 128, eight groups of B and C, bf16 products,
# float32 decays and sums) against the sequential float32 recurrence on
# the same bf16 inputs: y and the six gradients, largest error over
# largest value.  One bf16 ulp is 3.9e-3 of a value, and the backward pass
# rounds dy and the scaled x once each.  Measured at most 7.3e-3
# through the kernels; with x, B and C rounded through float8_e4m3fn the
# recurrence reads 2.3e-2 (dD) to 9.9e-2 (dA).
SCAN_RTOL = 1.5e-2
# (d) flash_attention on bf16 inputs, 32 query heads on 2 key/value heads
# of width 128, against plain float32 attention at 1 / sqrt(128), forward
# and backward, largest error over largest value: the flash kernels' own
# limit in train_lm.py.  Measured 2.3e-3 to 5.0e-3; q, k, v through
# float8_e4m3fn read 4.0e-2 to 6.7e-2 (dv, which the rounded q and k
# reach through the probabilities only, 1.7e-2 to 2.2e-2).
KERNEL_RTOL = train_lm.KERNEL_RTOL
# (g) An untrained model's first loss on uniform ids is ln(vocabulary)
# plus half the variance of its logits, hidden x initializer_range^2
# (0.54 at the published sizes): measured from there, within the GPT
# check's 0.5.
FIRST_LOSS_ATOL = train_lm.FIRST_LOSS_ATOL
# (h) The gradients of the step's loss as the compiled step differentiates
# it (bf16 O2, the kernels' and the experts' hand-written backward passes,
# the blocks the configuration names recomputed) for every parameter of
# the blocks from the last Mamba-2 block on (at the published sizes blocks
# 7 and 8: a Mamba-2 block and an expert block with its router, its held
# experts' two matrices and its shared expert), against jax.grad of the
# float32 reference given the experts that very pass chose: norm of the
# difference over the reference's norm, the worst leaf.  A bf16 gradient
# is itself rounded (2e-3 rms), as is every row it was summed from.
# Measured on the chip 7.9e-3 to 1.5e-2 on every leaf over three seeds
# (dt_bias and A_log, 64 numbers each, the worst); the reference with its
# weights rounded through float8_e4m3fn reads 1.4e-1 (an expert's second
# matrix) to 3.1e-1.  Given the experts an evaluation pass chose instead,
# which differ for up to 2.9 % of an expert block's tokens, the held
# experts' and the router's leaves read 7e-2 to 2.2e-1 (PERF.md section 2).
GRADS_RTOL = 5e-2
# ... over the first 2048 positions of a sequence: beside the runner's
# state (9.5e9 bytes) and what the runtime keeps reserved for the step's
# temporaries (4.74e9) the chip has 2.6e9 left, and the reference's
# backward pass, compiled as one program for a described v5e, needs
# 6.6e9 of temporaries over 8192 positions, 4.0e9 over 4096 and 2.3e9
# over 2048 (PERF.md section 2).
GRADS_POSITIONS = 2048
# (i) One step of the balancing rule moves a bias by the rate, up or
# down: a sign read the other way is a whole rate off, twice where an
# expert's load crossed the mean, and float32 adds the same in both.
BIAS_ATOL_IN_RATES = 0.5
KINDS = ("mamba", "moe", "attention")


def program_config(config: dict):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import NemotronHConfig
    if config["mlp_hidden_act"] != "relu2" or config["mlp_bias"] \
            or config["mamba_hidden_act"] != "silu" \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["use_bias"] or config["residual_in_fp32"] \
            or config["norm_eps"] != config["layer_norm_epsilon"] \
            or config["experts_held"][1] != config["n_routed_experts"] \
            or config["blocks_held"][1] != config["num_hidden_layers"]:
        raise ValueError("models/nemotron_h.py has squared-ReLU experts, "
                         "SiLU in its Mamba-2 blocks, no bias, an untied "
                         "head and a bf16 stream; n_routed_experts and "
                         "num_hidden_layers are the counts held")
    published = config["published"]
    return NemotronHConfig(
        vocab_size=published["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=published["num_hidden_layers"],
        hybrid_override_pattern=published["hybrid_override_pattern"],
        blocks_held=tuple(config["blocks_held"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], n_groups=config["n_groups"],
        chunk_size=config["chunk_size"],
        use_conv_bias=config["use_conv_bias"],
        mamba_proj_bias=config["mamba_proj_bias"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        n_routed_experts=published["n_routed_experts"],
        experts_held=tuple(config["experts_held"]),
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        initializer_range=config["initializer_range"],
        embedding_range=config["embedding_range"],
        rescale_prenorm_residual=config["rescale_prenorm_residual"],
        router_bias_update_rate=config["router_bias"]["update_rate"],
        recompute=tuple(config["recompute"]))


def build_runner(config: dict, seed: int, devices):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (NemotronHForCausalLM,
                                   NemotronHPretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = NemotronHForCausalLM(program_config(config))
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
    return DistributedRunner(net, opt, NemotronHPretrainingCriterion(),
                             mesh=mesh)


class Observed:
    """``runner.train_step`` as the window calls it, with what a user's
    loop does around it: the learning-rate schedule steps, the pairs each
    step's held experts computed are kept (a device array, nothing is
    waited for), and the program
    publishes its counters once a loss has been read: at the first
    dispatch after a sync the last step's buffer is on hand."""

    def __init__(self, runner, sync_every: int):
        self.runner, self.net = runner, runner.network
        self.sync_every = sync_every
        self.expert_tokens = []
        self.observed = 0

    def observe(self):
        self.net.observe_step()
        self.observed += 1

    def train_step(self, inputs, labels):
        steps = len(self.expert_tokens)
        if steps and steps % self.sync_every == 0:
            self.observe()
        loss = self.runner.train_step(inputs, labels)
        self.runner.optimizer._learning_rate.step()
        self.expert_tokens.append(self.net.expert_tokens._value)
        return loss


def balance_routers(check: Checks, runner, family, ring, rule: dict, say):
    """A job that has run for a while has balanced routers: ``passes``
    forward passes in training mode over the ring's batches, each moving
    every router's bias by the balancing rule's one step (the same rule
    every training step applies afterwards), before anything is timed or
    checked.  The passes return the biases, the experts chosen and the
    held experts' pairs and nothing else, so the compiler drops the head
    and keeps no activation.  (i): the last pass's step of every bias
    against the reference's rule on the same choices."""
    passes, rate = rule["passes"], rule["update_rate"]
    if not passes:
        return
    import jax
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    net = runner.network
    biases = {n: b for n, b in net.named_buffers()
              if n.endswith("e_score_correction_bias")}

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
          f"(i) the last pass moved the {len(biases)} routers' biases as the "
          f"reference's rule does from the same choices: largest difference "
          f"{off:.2e} (< {BIAS_ATOL_IN_RATES} of the rate {rate:g})")
    for n, b in biases.items():
        b._value = moved[n]
    say(f"balanced the routers' biases over {passes} forward passes: the "
        f"pairs of the experts held, by expert block, "
        + " ".join(str(v) for v in held[0].sum(1)) + " (fullest expert "
        f"{held[0].max()}) -> " + " ".join(str(v) for v in held[-1].sum(1))
        + f" (fullest expert {held[-1].max()})")


def program_counters(mamba_blocks, moe_blocks) -> dict:
    """What the program counted: as the step was traced, the chunks x
    heads of its scans, the bytes of states one scan passes on, the visits
    of the scan's kernels and the blocks recomputed, by kind; as steps
    were observed, the pairs its held experts computed."""
    from paddle_tpu.observability import metrics
    reg = metrics.registry()

    def of(layer):
        return {"layer": str(layer)}

    return {
        "ssm_scan_chunks": sum(reg.counter(
            "ssm_scan_chunks_total", labels=of(i)).collect()
            for i in mamba_blocks),
        "ssm_scan_state_bytes": reg.gauge(
            "ssm_scan_state_bytes", labels=of(mamba_blocks[0])).collect()
        or 0,
        "ssm_scan_kernel_visits": {kind: reg.counter(
            "ssm_scan_kernel_visits_total", labels={"kind": kind}).collect()
            for kind in ("fwd", "bwd")},
        "recompute_layers": {kind: reg.gauge(
            "recompute_layers", labels={"kind": kind}).collect() or 0
            for kind in KINDS},
        "moe_pairs": sum(reg.counter(
            "moe_pairs_total", labels=of(i)).collect() for i in moe_blocks),
        "moe_expert_tokens_max": [reg.gauge(
            "moe_expert_tokens_max", labels=of(i)).collect() or 0
            for i in moe_blocks],
        "moe_expert_tokens_mean": [reg.gauge(
            "moe_expert_tokens_mean", labels=of(i)).collect() or 0
            for i in moe_blocks]}


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def program_trace(runner, ids):
    """The program's forward pass with what it chose: logits, the experts
    chosen and the pairs by held expert, an expert block at a time."""
    import jax
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    net = runner.network

    @jax.jit
    def traced(params, frozen, buffers, ids_):
        out, _ = F.functional_call(net, params, buffers, (Tensor(ids_),),
                                   {"output_routing": True}, frozen=frozen)
        return [o._value for o in out]

    return traced(F.param_dict(net), F.frozen_dict(net), F.buffer_dict(net),
                  ids)


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

    head = jax.device_put(named[family.HEAD]._value, home)
    ids_d = jnp.asarray(ids[0])
    given = family.reference_forward(param, config, ids_d, routing=chosen)
    err = logits_error(family, given["hidden"], head, logits[0], vocab)
    check(math.isfinite(err) and err < LOGITS_RTOL,
          f"(a) logits {(seq_len, vocab)} of a seeded sequence agree with "
          f"the float32 reference given the program's routing: rms "
          f"difference {err:.2e} of the reference's rms (< {LOGITS_RTOL})")
    tokens = np.asarray(tokens)
    for at, block in enumerate(net.moe_blocks()):
        want = np.asarray(given["counts"][at])
        routed = np.asarray(chosen[at])
        here = int(((routed >= first) & (routed < first + held)).sum())
        check((tokens[at] == want).all() and int(tokens[at].sum()) == here,
              f"(e) block {block}: the experts held computed "
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
          f"that take another expert, by expert block: "
          + " ".join(f"{1 - a:.4f}" for a in agree))


def check_scan(check: Checks, family, config: dict, seq_len: int, seed: int):
    """(c) ``ssd_scan`` at the cell's shape on seeded bf16 inputs against
    the sequential recurrence in float32: y and the gradients of ``sum(y
    * w)`` by x, dt, A, B, C, D."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    chunk = config["chunk_size"]

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
    form = ssm.scan_form(seq_len, heads, width, groups, state, chunk)
    for name, a, r in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                          (y,) + grads, want):
        err = _largest_error(a, r)
        check(math.isfinite(err) and err < SCAN_RTOL,
              f"(c) ssd_scan {name} {tuple(a.shape)} ({groups} groups, "
              f"chunk {chunk}, state {state}; the {form} form) agrees with "
              f"the sequential recurrence: largest error {err:.2e} of the "
              f"largest value (< {SCAN_RTOL})")


def check_attention(check: Checks, family, config: dict, seq_len: int,
                    seed: int):
    """(d) the public ``flash_attention`` as the attention block calls it
    against plain float32 attention at ``1 / sqrt(head_dim)``, forward and
    backward."""
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

    (dq, dk, dv), out = jax.jit(jax.grad(
        weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
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


def step_gradients(runner, batch, names):
    """(the loss, its gradients by the parameters ``names``, the experts
    chosen) of the runner's network on ``batch`` as the compiled step
    differentiates it (``DistributedRunner._grad_math``'s loss: the
    network in training mode under the runner's amp context, so with the
    blocks the configuration names recomputed, its criterion, float32
    loss), at the runner's parameters and without the update.  The
    forward pass that is differentiated also says which experts it chose:
    a second pass compiled otherwise rounds the stream otherwise and
    sends up to 3 % of an expert block's tokens to another expert, which
    an expert's gradient feels (PERF.md section 2)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from paddle_tpu.amp import auto_cast
    from paddle_tpu.autograd import tape
    from paddle_tpu.distributed import collective
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    net, criterion = runner.network, runner.loss_fn
    (ids,), (labels,) = runner._prep_step_args(*batch)
    params, frozen, buffers = runner._sync_val_cache()

    def loss_of(asked, rest, frozen_, buffers_, ids_, labels_):
        amp = (auto_cast(level=runner.amp_level, dtype=runner.amp_dtype)
               if runner.amp_level else contextlib.nullcontext())
        with F.bind(net, {**rest, **asked}, buffers_, frozen_), \
                tape.no_grad_ctx():
            with amp:
                logits, chosen, _ = net(Tensor(ids_), output_routing=True)
            loss = criterion(logits, Tensor(labels_))
        return loss._value.astype(jnp.float32), chosen._value

    before = collective.get_mesh()
    collective.set_mesh(runner.mesh)
    try:
        (loss, chosen), grads = jax.jit(jax.value_and_grad(
            loss_of, has_aux=True))(
                {n: params[n] for n in names},
                {n: v for n, v in params.items() if n not in names},
                frozen, buffers, ids, labels)
    finally:
        collective.set_mesh(before)
    return loss, grads, chosen


def reference_gradients(family, config: dict, first: int, positions: int):
    """``(values, ids, labels, routing) -> gradients``, to be jitted: the
    reference's gradients of the mean cross-entropy over the first
    ``positions`` positions of each sequence of ``ids [B, S]``, batch
    mean, by every parameter of the blocks from ``first`` on, from the
    program's parameters and buffers ``values`` as they are stored (each
    made float32 inside).  As one program, what its backward pass keeps
    are temporaries, and not buffers beside the runner's state."""
    import jax.numpy as jnp

    def gradients(values, ids, labels, routing):
        def param(name, rows=None):
            value = values[name] if rows is None else values[name][rows]
            return value.astype(jnp.float32)

        total, seq = None, ids.shape[1]
        for b in range(ids.shape[0]):
            part = family.reference_tail_grads(
                param, config, ids[b, :positions], labels[b, :positions],
                [r[b * seq:b * seq + positions] for r in routing], first)
            total = part if total is None else {
                n: total[n] + part[n] for n in part}
        return {n: g / ids.shape[0] for n, g in total.items()}

    return gradients


def gradient_errors(got: dict, want: dict, scale: float = 1.0) -> dict:
    """By parameter, the norm of ``got - scale * want`` over the norm of
    ``scale * want``."""
    import jax.numpy as jnp
    return {n: jnp.linalg.norm((got[n].astype(jnp.float32)
                                - scale * want[n]).ravel())
            / jnp.linalg.norm(scale * want[n].ravel()) for n in want}


def tail_of(family, config: dict):
    """(the first block, the parameters' names) of check (h): the blocks
    from the last Mamba-2 block or the last expert block on, whichever
    comes first."""
    kinds = family.kinds(config)
    first = min(len(kinds) - 1 - kinds[::-1].index(kind)
                for kind in ("mamba", "moe"))
    return first, [n for i in range(first, len(kinds))
                   for n in family.block_parameters(config, i)]


def check_gradients(check: Checks, runner, family, config: dict, batch):
    """(h) the step's gradients for the blocks from the last Mamba-2 block
    on against the reference's, on the ring's first batch at its timed
    shape, the loss taken over each sequence's first GRADS_POSITIONS
    positions (the labels after them are ParallelCrossEntropy's
    ``ignore_index``): every mixer is causal, so the reference runs on
    those positions alone, given the experts the program chose for
    them."""
    import jax
    import jax.numpy as jnp
    ids, labels = (np.asarray(x[0]) for x in batch)
    seq = ids.shape[1]
    positions = min(seq, GRADS_POSITIONS)
    first, names = tail_of(family, config)
    kinds = family.kinds(config)
    masked = labels.copy()
    masked[:, positions:] = runner.loss_fn.loss_fn.ignore_index
    loss, got, chosen = step_gradients(runner, ([ids], [masked]), names)
    net = runner.network
    values = {n: v._value for n, v in (*net.named_parameters(),
                                       *net.named_buffers())}
    reference = reference_gradients(family, config, first, positions)
    errs = jax.jit(lambda got_, *args: gradient_errors(
        got_, reference(*args), positions / seq))(
            got, values, jnp.asarray(ids), jnp.asarray(labels), list(chosen))
    errs = {n: float(e) for n, e in errs.items()}
    worst = max(errs, key=errs.get)
    short = lambda n: n.split("layers.")[-1]        # noqa: E731
    check(math.isfinite(errs[worst]) and errs[worst] < GRADS_RTOL,
          f"(h) the step's gradients of the loss over the first {positions} "
          f"of {seq} positions ({float(loss):.4f}) for the {len(names)} "
          f"parameters of blocks {first} to {len(kinds) - 1} "
          f"({' '.join(kinds[first:])}) agree with the float32 reference's "
          f"given the program's routing: norm of the difference over the "
          f"reference's norm at most {errs[worst]:.2e} ({short(worst)}; < "
          f"{GRADS_RTOL}); by parameter "
          + " ".join(f"{short(n)} {e:.1e}" for n, e in errs.items()))


def check_losses(check: Checks, losses, config: dict):
    """(g) every loss finite, the first where an untrained model starts,
    the last ten below the first ten."""
    start = math.log(config["vocab_size"]) + 0.5 * (
        config["hidden_size"] * config["initializer_range"] ** 2)
    check(bool(losses) and all(math.isfinite(v) for v in losses),
          f"(g) all {len(losses)} losses are finite")
    if not losses:
        return
    check(abs(losses[0] - start) < FIRST_LOSS_ATOL,
          f"(g) the first loss {losses[0]:.4f} is within {FIRST_LOSS_ATOL} "
          f"of ln(vocabulary) + half the logits' variance at the start = "
          f"{start:.4f} (ln(vocabulary) = "
          f"{math.log(config['vocab_size']):.4f})")
    n = min(10, len(losses) // 2)
    first, last = sum(losses[:n]) / max(n, 1), sum(losses[-n:]) / max(n, 1)
    check(n > 0 and last < first,
          f"(g) the mean of the last {n} losses {last:.4f} is below the "
          f"mean of the first {n}, {first:.4f}")


def kernel_sites(kinds, recomputed) -> int:
    """The Mosaic calls the step holds beside the experts' grouped
    products: a Mamba-2 block's scan forward and the walk back, an
    attention block's forward, dq and dkv, and the forward once more
    where the block is recomputed."""
    own = {"mamba": 2, "attention": 3, "moe": 0}
    return sum(own[kind] + (i in recomputed and kind != "moe")
               for i, kind in enumerate(kinds))


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
    first_block = config["blocks_held"][0]
    blocks = {kind: [first_block + i for i, k in enumerate(kinds)
                     if k == kind] for kind in KINDS}
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
        counted = program_counters(blocks["mamba"], blocks["moe"])
        return {**train_lm.counters(counter, runner),
                "moe_pairs": counted["moe_pairs"],
                "observed": observed.observed, "program": counted}

    with counter.listening():
        t = clock()
        runner = build_runner(config, options.seed, devices)
        ring = traffic_gen.token_batches(mix, config["vocab_size"],
                                         options.seed)
        say(f"built {cell.config_name} ({family.param_count(config)} "
            f"parameters on this chip: {len(blocks['mamba'])} Mamba-2, "
            f"{len(blocks['moe'])} expert and {len(blocks['attention'])} "
            f"attention block(s), {config['n_routed_experts']} of "
            f"{family.router_width(config)} experts an expert block, "
            f"{config['vocab_size']} rows of the vocabulary) and "
            f"{len(ring)} batches of b{batch} x s{seq_len} in "
            f"{clock() - t:.1f} s")
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
        traced = program_counters(blocks["mamba"], blocks["moe"])
        limit = config["step_bytes_limit"]
        want = {kind: sum(kinds[i] == kind for i in recomputed)
                for kind in KINDS}
        visits = traced["ssm_scan_kernel_visits"]
        check(traced["recompute_layers"] == want
              and step["step_bytes"] < limit,
              f"(f) the step recomputes blocks {sorted(recomputed)} of "
              f"{len(kinds)} ("
              + ", ".join(f"{traced['recompute_layers'][k]:g} {k}"
                          for k in KINDS)
              + f") and needs {step['step_bytes']} bytes on a device (< "
              f"{limit})")
        say(f"  the program counted: ssm_scan_chunks_total "
            f"{traced['ssm_scan_chunks']:g} chunks x heads, "
            f"ssm_scan_state_bytes {traced['ssm_scan_state_bytes']:g} a "
            f"scan, ssm_scan_kernel_visits_total fwd {visits['fwd']:g} bwd "
            f"{visits['bwd']:g}, recompute_layers "
            + " ".join(f"{k} {traced['recompute_layers'][k]:g}"
                       for k in KINDS))
        least = kernel_sites(kinds, recomputed)
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites: at least {least} of its own (a "
                f"Mamba-2 block's scan 2, an attention block 3, one more "
                f"where the block is recomputed); the rest are the experts' "
                f"grouped products as XLA lowers jax.lax.ragged_dot")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        else:
            check(step["kernel_sites"] >= least and visits["fwd"] > 0
                  and visits["bwd"] > 0,
                  "(f) the scans run through the Mosaic kernels, and " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        check_forward(check, runner, family, config, seq_len, options.seed)
        check_scan(check, family, config, seq_len, options.seed)
        check_attention(check, family, config, seq_len, options.seed)
        check_gradients(check, runner, family, config, ring[0])
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

    window_losses = [float(x) for x in jax.device_get(window.losses)]
    failed = window.raised + sum(
        1 for v in window_losses if not math.isfinite(v))
    check_losses(check, losses + window_losses, config)
    check(failed == 0,
          f"{failed} of {window.attempted} steps of the window failed")
    pairs = np.asarray(jax.device_get(observed.expert_tokens))
    routed = (tokens_per_step * config["num_experts_per_tok"]
              * len(blocks["moe"]))
    from paddle_tpu.incubate.distributed.models.moe import grouped
    rows = grouped.usual_rows(tokens_per_step, config["num_experts_per_tok"],
                              config["n_routed_experts"],
                              family.router_width(config))
    say(f"the fullest expert block held {pairs.sum(2).max()} pairs in a "
        f"step; a window is {rows} rows, and the later windows ran in "
        f"{int((pairs.sum(2) > rows).any(1).sum())} of {len(pairs)} steps")
    say(f"pairs a step on the experts held, over all steps: "
        f"{pairs.sum((1, 2)).min()} to {pairs.sum((1, 2)).max()} of "
        f"{routed} routed; fullest expert {pairs.max()}, mean "
        f"{pairs.mean():.1f}; the program counted moe_pairs_total "
        f"{after['moe_pairs']:g} over {after['observed']} observed steps, "
        f"moe_expert_tokens_max "
        + " ".join(f"{v:g}" for v in after["program"][
            "moe_expert_tokens_max"])
        + ", moe_expert_tokens_mean "
        + " ".join(f"{v:g}" for v in after["program"][
            "moe_expert_tokens_mean"]))

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


def read_trace(xplane: str, obs: dict, step: dict, least: int, say):
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
    if not least <= ran <= step["kernel_sites"]:
        raise BenchmarkError(
            f"the trace shows {ran:g} Mosaic kernels a step; the compiled "
            f"step holds {step['kernel_sites']} tpu_custom_call sites, at "
            f"least {least} of which every step runs")
    return trace
