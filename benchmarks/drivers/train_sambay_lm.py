"""Driver: pretraining of Phi-4-mini-flash-reasoning (SambaY) as rank 0 of
a pipeline stage with the depth cut to one layer of each kind, built and
run the way a user's script does it: ``paddle.seed`` ->
``SambaYForCausalLM`` from its config -> ``optimizer.AdamW`` ->
``amp.decorate`` (bf16 O2, float32 master weights) ->
``collective.build_mesh`` -> ``DistributedRunner.train_step`` on numpy
batches, the layers the configuration names through ``fleet.recompute``,
steps dispatched back to back, the loss read every ``sync_every`` steps.
The window, the counting of programs, the compiled step's facts and the
memory readings are ``train_lm.py``'s.

What is decided here: what makes a run of this family ``correct``.  Each
tolerance stands below with its reason.  The check of the logits is the
Granite driver's and the check of the losses the Nemotron driver's.
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
from . import train_granite_lm, train_lm
from .train_granite_lm import _largest_error, read_trace
from .train_lm import Checks, ProgramCounter
from .train_nemotron_lm import check_losses

# Every limit lies between the program's largest reading on the chip and
# what the same check reads with the family's reference computed through
# float8_e4m3fn (``family.rounded_through``, the control that
# tests/benchmarks/test_sambay_cell.py keeps): PERF.md section 2 has both.
#
# (a) Program logits (bf16 O2) against the reference, rms of the
# difference over the reference's rms: the Granite driver's check and its
# limit, 5e-2.  The residual stream is rounded to bf16 (1.1e-3 of a value
# a rounding) twice a layer; a Mamba layer's projections, convolution and
# scan output, an attention layer's four calls and their difference each
# round once more.  Measured on the chip 1.68e-2 to 1.98e-2 over eleven
# seeds; the control reads 2.7e-1.
LOGITS_RTOL = train_granite_lm.LOGITS_RTOL
# (b) selective_scan (chunks of 64, float32 state and sums) against the
# sequential float32 recurrence on the same inputs (x, B, C bf16): y and
# the six gradients, largest error over largest value.  What differs is
# the order of float32 sums and the rounding of y, dx, dB and dC to bf16
# (half an ulp, 2e-3 of a value).  Measured at most 2.9e-3 (y); with x,
# B and C rounded through float8_e4m3fn the recurrence reads 2.1e-2 (dx)
# to 5.6e-2 (ddt).
SCAN_RTOL = 8e-3
# (c) flash_attention on bf16 inputs with the window, 20 query heads on
# 10 key/value heads of 64 (one of a layer's four calls), against plain
# float32 banded attention, forward and backward, largest error over
# largest value: the flash kernels' own limit in train_lm.py.  Measured
# 2.4e-3 to 5.8e-3; q, k, v through float8_e4m3fn read 5.0e-2 to 5.6e-2
# (dv, which the rounded q and k reach through the probabilities only,
# 2.1e-2).
KERNEL_RTOL = train_lm.KERNEL_RTOL
# (d) One step of the compiled train step the window times (bf16 O2, the
# kernels' and the scan's hand-written backward passes, the layers the
# configuration names recomputed, AdamW on float32 master weights), for
# every parameter of the two producer layers (the Mamba layer whose
# memory the gated memory unit reads, the full-attention layer whose K
# and V the cross layer reads: their gradients collect from their
# readers).  The gradient the step took is read off its first moment,
# (m' - beta1 m) / (1 - beta1), and held against jax.grad of the float32
# reference: norm of the difference over the reference's norm, the worst
# leaf.  A bf16 gradient is itself rounded (2e-3 rms), as is every row it
# was summed from.  Measured on the chip 2.7e-2 to 3.6e-2 at the worst
# leaf over eleven seeds (dt_proj's weight, whose input is the low-rank
# projection's bf16 result; the four lambda vectors 1.4e-3 to 1.7e-3);
# the control reads 2.6e-1 to 7.0e-1 on every leaf but the lambda
# vectors' (2.8e-2 to 3.6e-2: not by each leaf).
GRADS_RTOL = 1e-1
# ... and the change of the float32 master weights, held against AdamW
# (the family's, from the paper, in float64) applied to that gradient
# from the moments the step started with: norm of the difference over the
# norm of the reference's change, the worst leaf.  The reference's own
# gradient will not do here: where a gradient is nothing but rounding (a
# key's bias moves no softmax) Adam divides the rounding by its own size
# and steps a full learning rate either way.  What is left is arithmetic
# that precision hardly moves, so the limit stands between the program's
# reading and 1, which a leaf left unmoved reads (PERF.md section 2 has
# the readings).
UPDATE_RTOL = 5e-2
# ... over the first 2048 positions of a sequence: beside the runner's
# state and what the runtime keeps reserved for the step's temporaries
# the chip has little left, and the reference's backward pass is one
# program whose temporaries grow with the positions.
GRADS_POSITIONS = 2048


def program_config(config: dict):
    """The program's config object from the configuration file's keys."""
    from paddle_tpu.models import SambaYConfig
    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"] \
            or config["mlp_bias"] or config["lm_head_bias"] \
            or config["embd_pdrop"] or config["resid_pdrop"] \
            or config["layers"]["n_self"] + 2 + config["layers"]["n_cross"] \
            != config["num_hidden_layers"]:
        raise ValueError("models/sambay.py has a SiLU-gated MLP without "
                         "bias, a tied head without bias and no dropout; "
                         "num_hidden_layers is n_self + 2 + n_cross")
    return SambaYConfig(
        vocab_size=config["published"]["vocab_size"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        n_self=config["layers"]["n_self"],
        n_cross=config["layers"]["n_cross"],
        sliding_window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        layer_norm_eps=config["layer_norm_eps"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_dt_rank=config["mamba_dt_rank"],
        initializer_range=config["initializer_range"],
        lambda_std=config["lambda_std"],
        recompute=tuple(config["recompute"]))


def build_runner(config: dict, seed: int, devices):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (SambaYForCausalLM,
                                   SambaYPretrainingCriterion)
    if config["precision"] != {"level": "O2", "dtype": "bfloat16",
                               "master_weights": True} or \
            config["optimizer"]["name"] != "AdamW":
        raise ValueError("this driver builds AdamW under bf16 O2 with "
                         "float32 master weights only")
    paddle.seed(seed)
    net = SambaYForCausalLM(program_config(config))
    opt = optimizer.AdamW(
        learning_rate=config["optimizer"]["learning_rate"],
        parameters=net.parameters(), multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh(config["mesh"], devices=devices)
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, SambaYPretrainingCriterion(),
                             mesh=mesh)


def flash_tiles() -> dict:
    from paddle_tpu.observability import metrics
    return {kind: metrics.registry().counter(
        "flash_tiles_total", labels={"kind": kind}).collect()
        for kind in ("square", "visited", "masked")}


def program_counters(kinds) -> dict:
    """What the program counted as the step was traced: the chunks of its
    selective scans and the bytes of chunk starts one keeps, the bytes
    one layer hands on to the cross-decoder, the layers recomputed by
    kind, and the flash kernels' tiles."""
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    scans = [str(i) for i, kind in enumerate(kinds)
             if kind in ("mamba", "mamba_memory")]
    return {
        "s6_scan_chunks": {i: reg.counter(
            "s6_scan_chunks_total", labels={"layer": i}).collect()
            for i in scans},
        "s6_scan_state_bytes": {i: reg.gauge(
            "s6_scan_state_bytes", labels={"layer": i}).collect() or 0
            for i in scans},
        "yoco_shared_kv_bytes": reg.gauge(
            "yoco_shared_kv_bytes").collect() or 0,
        "gmu_memory_bytes": reg.gauge("gmu_memory_bytes").collect() or 0,
        "recompute_layers": {kind: int(reg.gauge(
            "recompute_layers", labels={"kind": kind}).collect() or 0)
            for kind in dict.fromkeys(kinds)},
        "flash_tiles": flash_tiles()}


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------
def scan_inputs(config: dict, seq_len: int, seed: int):
    """Seeded inputs of one selective scan at the cell's shape, as the
    model starts with them: x, B, C bf16, the steps log-uniform in [1e-3,
    1e-1], A the negative of 1..N in every channel, and the weights of
    ``sum(y * w)``."""
    import jax
    import jax.numpy as jnp
    inner = config["mamba_expand"] * config["hidden_size"]
    state = config["mamba_d_state"]

    def draw(key):
        k = jax.random.split(key, 6)
        normal = jax.random.normal
        return (normal(k[0], (seq_len, inner), jnp.bfloat16),
                jnp.exp(jax.random.uniform(
                    k[1], (seq_len, inner), jnp.float32, math.log(1e-3),
                    math.log(1e-1))),
                -jnp.broadcast_to(jnp.arange(1, state + 1,
                                             dtype=jnp.float32),
                                  (inner, state)),
                normal(k[2], (seq_len, state), jnp.bfloat16),
                normal(k[3], (seq_len, state), jnp.bfloat16),
                normal(k[4], (inner,), jnp.float32),
                normal(k[5], (seq_len, inner), jnp.bfloat16))

    return jax.jit(draw)(jax.random.PRNGKey(seed + 3))


def check_scan(check: Checks, family, config: dict, seq_len: int, seed: int):
    """(b) ``selective_scan`` at the cell's shape on seeded inputs
    against the sequential recurrence in float32: y and the gradients of
    ``sum(y * w)`` by x, dt, A, B, C, D."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm
    chunk = ssm.selective_chunk(seq_len)
    *inputs, w = scan_inputs(config, seq_len, seed)

    def weighted(x, dt, A, B, C, D, w_):
        y = ssm.selective_scan(x, dt, A, B, C, D)
        return (y * w_).astype(jnp.float32).sum(), y

    grads, y = jax.jit(jax.grad(weighted, argnums=tuple(range(6)),
                                has_aux=True))(*inputs, w)
    want = family.reference_scan_grads(
        *(a.astype(jnp.float32) for a in inputs), w.astype(jnp.float32))
    form = ssm.selective_scan_form(seq_len, chunk)
    for name, a, r in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                          (y,) + grads, want):
        err = _largest_error(a, r)
        check(math.isfinite(err) and err < SCAN_RTOL,
              f"(b) selective_scan {name} {tuple(a.shape)} ({form}, chunk "
              f"{chunk}, state {config['mamba_d_state']}) agrees with the "
              f"recurrence a position at a time: largest error {err:.2e} "
              f"of the largest value (< {SCAN_RTOL})")


def attention_inputs(config: dict, seq_len: int, seed: int):
    import jax
    import jax.numpy as jnp
    heads = config["num_attention_heads"] // 2
    kv = config["num_key_value_heads"] // 2
    dim = config["hidden_size"] // config["num_attention_heads"]
    shapes = ((1, seq_len, heads, dim), (1, seq_len, kv, dim),
              (1, seq_len, kv, dim), (1, seq_len, heads, dim))
    return jax.jit(lambda key: tuple(
        jax.random.normal(key_, shape, jnp.bfloat16) for key_, shape in zip(
            jax.random.split(key, 4), shapes)))(jax.random.PRNGKey(seed + 1))


def check_attention(check: Checks, family, config: dict, seq_len: int,
                    seed: int):
    """(c) the public ``flash_attention`` with the window as a window
    layer calls it (one of its four calls: half the query heads on half
    the key/value heads), against plain float32 banded attention, forward
    and backward; and the tiles the calls counted against the band's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops
    window = config["sliding_window"]
    q, k, v, w = attention_inputs(config, seq_len, seed)

    def weighted(q_, k_, v_, w_):
        out = pallas_ops.flash_attention.raw(q_, k_, v_, causal=True,
                                             window=window)
        return (out * w_).astype(jnp.float32).sum(), out

    before = flash_tiles()
    (dq, dk, dv), out = jax.jit(jax.grad(
        weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
    tiles = {kind: n - before[kind] for kind, n in flash_tiles().items()}
    want = family.reference_attention_grads(
        *(x[0].astype(jnp.float32).swapaxes(0, 1) for x in (q, k, v, w)),
        window=window)
    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, dq, dk, dv), want):
        err = _largest_error(a[0], r.swapaxes(0, 1))
        check(math.isfinite(err) and err < KERNEL_RTOL,
              f"(c) flash_attention {name} {tuple(a.shape)}, window "
              f"{window}, agrees with plain float32 banded attention: "
              f"largest error {err:.2e} of the largest value (< "
              f"{KERNEL_RTOL})")
    heads, dim = q.shape[2], q.shape[3]
    form = pallas_ops._attention_form(heads, dim, seq_len, seq_len)
    what = (f"the three calls (forward, dq, dkv) visited "
            f"{tiles['visited']:g} compute tiles, {tiles['masked']:g} of "
            f"them masked, of the squares' {tiles['square']:g}")
    if form != "packed":
        check(True, f"(c) {what} (form {form}: the band is masked, not "
              "walked, at this shape)")
        return
    # the shape's own tile: what _compute_tile gives the causal walk
    tile = math.gcd(seq_len, 512)
    _, band, _ = pallas_ops._tile_counts(seq_len, seq_len, tile, tile, True,
                                         False, window)
    _, triangle, _ = pallas_ops._tile_counts(seq_len, seq_len, tile, tile,
                                             True, False)
    check(tiles["visited"] == 3 * heads * band and band <= triangle,
          f"(c) {what}: 3 calls x {heads} heads x the band's {band} tiles "
          f"of {tile} x {tile} = {3 * heads * band}; the triangle has "
          f"{triangle} a head ({3 * heads * triangle})")


def _producer_state(runner, names) -> dict:
    """Host copies of what the optimizer holds for ``names``: the float32
    weight it updates (the master weight of a bf16 parameter) and the
    two moments.  Copies: the step donates the buffers."""
    held = dict(runner.network.named_parameters())
    return {n: {"weight": np.asarray(runner._opt_state[n].get(
                    "master_weight", held[n]._value)),
                "moment1": np.asarray(runner._opt_state[n]["moment1"]),
                "moment2": np.asarray(runner._opt_state[n]["moment2"])}
            for n in names}


def _leaf_errors(family, was: dict, now: dict, held, want, step: int,
                 lr: float):
    """Of one leaf: (the gradient the step took, read off its first
    moment, against ``want``; the weight's change against the family's
    AdamW on that gradient; whether ``held``, the parameter the next step
    reads, is the new weight rounded), the first two as the norm of the
    difference over the reference's norm.  A million elements at a time:
    numpy's float64 temporaries of a whole 26 M-element leaf cost ten
    times their arithmetic in page faults."""
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
        pairs = ((took - want_, want_), ((w_ - w) - move, move))
        sums += [np.dot(x, x) for pair in pairs
                 for x in (np.asarray(y, np.float64) for y in pair)]
        rounded &= bool((held_ == w_.astype(held_.dtype)).all())
    return math.sqrt(sums[0] / sums[1]), math.sqrt(sums[2] / sums[3]), rounded


def check_step(check: Checks, runner, family, config: dict, batch,
               step: int):
    """(d) the runner's own ``train_step``, the executable the window
    times, run once as step ``step`` on the ring's first batch with the
    loss taken over each sequence's first GRADS_POSITIONS positions (the
    labels after them are ParallelCrossEntropy's ``ignore_index``: every
    mixer is causal, so the reference runs on those positions alone).
    For the two producer layers: the gradient the step took, read off its
    first moment, against the reference's; and the master weights' change
    against the family's AdamW on that gradient."""
    import jax
    import jax.numpy as jnp
    ids, labels = (np.asarray(x[0]) for x in batch)
    seq = ids.shape[1]
    positions = min(seq, GRADS_POSITIONS)
    layers = family.producers(config)
    names = [n for l in layers for n in family.layer_parameters(config, l)]
    masked = labels.copy()
    masked[:, positions:] = runner.loss_fn.loss_fn.ignore_index

    def reference(values, ids_, labels_):
        def param(name, rows=None):
            value = values[name] if rows is None else values[name][rows]
            return value.astype(jnp.float32)

        total = None
        for b in range(ids_.shape[0]):
            part = family.reference_producer_grads(
                param, config, ids_[b, :positions], labels_[b, :positions])
            total = part if total is None else {
                n: total[n] + part[n] for n in part}
        # the program's mean is over every position of the batch
        return {n: g * (positions / seq / ids_.shape[0])
                for n, g in total.items()}

    want = jax.jit(reference)(
        {n: v._value for n, v in runner.network.named_parameters()},
        jnp.asarray(ids), jnp.asarray(labels))
    want = {n: np.asarray(g) for n, g in want.items()}
    before = _producer_state(runner, names)
    programs = train_lm.step_programs(runner)
    loss = float(runner.train_step([ids], [masked]))
    after = _producer_state(runner, names)
    rule = family.ADAMW
    lr = config["optimizer"]["learning_rate"]
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
          f"(d) step {step} of the compiled train step, on the loss over "
          f"the first {positions} of {seq} positions ({loss:.4f}; the "
          f"executable the window times: the jitted step gained "
          f"{train_lm.step_programs(runner) - programs} for it): the "
          f"gradients it took, (m' - beta1 m) / (1 - beta1), for the "
          f"{len(names)} parameters of layers {layers[0]} "
          f"({kinds[layers[0]]}: its memory is read by the gated memory "
          f"units) and {layers[1]} ({kinds[layers[1]]}: its K, V are read "
          f"by the cross layers) agree with jax.grad of the float32 "
          f"reference: norm of the difference over the reference's norm "
          f"at most {grads[worst]:.2e} ({short(worst)}; < {GRADS_RTOL}); "
          f"by parameter " + " ".join(f"{short(n)} {e:.1e}"
                                      for n, e in grads.items()))
    worst = max(moves, key=moves.get)
    check(rounded and math.isfinite(moves[worst])
          and moves[worst] < UPDATE_RTOL,
          f"(d) the step's change of their float32 weights agrees with "
          f"AdamW (learning rate {lr}, {rule}) on those gradients from "
          f"the moments the step started with: norm of the "
          f"difference over the norm of the reference's change at most "
          f"{moves[worst]:.2e} ({short(worst)}; < {UPDATE_RTOL}; a leaf "
          f"left unmoved reads 1), and the weight the next step reads is "
          f"that weight rounded: {rounded}; by parameter "
          + " ".join(f"{short(n)} {e:.1e}" for n, e in moves.items()))


def _as_f(check: Checks):
    """The Nemotron driver's check of the losses says (g); here it is
    (f)."""
    return lambda ok, what: check(ok, what.replace("(g)", "(f)", 1))


def kernel_sites(kinds, recomputed) -> int:
    """The Mosaic calls the step holds at least: an attention-kind
    layer's four calls of ``flash_attention``, each forward, dq and dkv,
    and the four forward calls once more where the layer is recomputed."""
    return sum(12 + 4 * (i in recomputed) for i, kind in enumerate(kinds)
               if kind in ("swa", "full_kv", "cross"))


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
            f"parameters on this chip: layers {' '.join(kinds)}, "
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
        traced = program_counters(kinds)
        say("counters: " + "; ".join(f"{k} {v}" for k, v in traced.items()))
        limit = config["step_bytes_limit"]
        recomputed = set(config["recompute"])
        want = {kind: sum(1 for i, k in enumerate(kinds)
                          if k == kind and i in recomputed)
                for kind in dict.fromkeys(kinds)}
        check(traced["recompute_layers"] == want
              and step["step_bytes"] < limit,
              f"(e) the step recomputes the layers the file names "
              f"({sorted(recomputed)}: by kind {traced['recompute_layers']})"
              f" and needs {step['step_bytes']} bytes on a device (< "
              f"{limit}); its scans were traced over "
              f"{sum(traced['s6_scan_chunks'].values()):g} chunks, one "
              f"keeps {max(traced['s6_scan_state_bytes'].values()):g} "
              f"bytes of chunk starts; one layer hands on "
              f"{traced['gmu_memory_bytes']:g} bytes of memory and "
              f"{traced['yoco_shared_kv_bytes']:g} of keys and values")
        least = kernel_sites(kinds, recomputed)
        what = (f"the compiled step holds {step['kernel_sites']} "
                f"tpu_custom_call sites (an attention-kind layer's four "
                f"calls x (forward, dq, dkv), the forward again where "
                f"recomputed: at least {least})")
        if options.rehearse:
            say("  not checked in a rehearsal (the interpreter lowers "
                "kernels to plain HLO): " + what)
        else:
            check(step["kernel_sites"] >= least, "(e) " + what)

        train_lm.say_memory(say, devices, "after the program's set-up")
        setup_peak = train_lm.memory_readings(devices, "peak_bytes_in_use")

        say("reference:")
        t = clock()
        train_granite_lm.check_logits(check, runner, family, config,
                                      seq_len, options.seed)
        check_scan(check, family, config, seq_len, options.seed)
        check_attention(check, family, config, seq_len, options.seed)
        check_step(check, runner, family, config, ring[0], len(losses) + 1)
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
    say("(f) the loss:")
    check_losses(_as_f(check), losses + window_losses, config)
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
