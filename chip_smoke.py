"""chip_smoke.py — does the system still start on the chip?

Trains GPT-2-small (124M parameters, published widths and depth, random
weights from ``--seed``) for a few steps on a TPU through the entry
points a user would call — ``collective.build_mesh`` →
``DistributedRunner.train_step``, the engine ``paddle.Model.fit`` and
the Fleet loops delegate to — and checks what comes out.  One process;
nothing else here touches jax.

    python chip_smoke.py             one chip: 5 steps at b8 x s1024
    python chip_smoke.py --chips 4   only the dp2 x mp2 path and the
                                     one-device run it is compared with

It refuses; it does not fall back.  No TPU, or one of the switches that
take the kernels off the device set in the environment: non-zero exit
and no result line.  Any failed check or exception in any phase ends
the run non-zero.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse-cpu`` is the guide's first two rehearsals (on-chip-
measurement §2): the same control flow at toy width on the CPU, kernels
in the Pallas interpreter, with ``--chips 4`` on four virtual devices.
It is asked for, never detected, and never prints the result line.
"""

import argparse
import json
import math
import os
import sys
import time

# switches that would keep the kernels off the device (or the run off
# the chip) while everything still "works"
REFUSED_ENV = ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_DISABLE_PALLAS",
               "GRAFT_BENCH_FORCE_CPU")

# flash vs composed attention, and dp2 x mp2 vs one device: relative
# tolerance on a bf16 training loss (~10.8 here).  Both pairs compute
# the same math in different summation orders on bf16 operands.
LOSS_RTOL = 2e-2
# kernel vs composed attention outputs/gradients on seeded bf16 inputs,
# relative to the largest reference magnitude (bf16 has 8 bits of
# mantissa: one ulp is 2^-8 = 3.9e-3 of the value)
KERNEL_RTOL = 2e-2


def say(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)
    say(f"  ok: {what}")


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy width on the CPU with interpreted kernels; "
                         "prints no result line")
    return ap.parse_args()


def configure_environment(args):
    """Everything that must be decided before jax is imported."""
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
        return
    bad = [k for k in REFUSED_ENV if os.environ.get(k)]
    if bad:
        sys.exit(f"chip_smoke: refusing to run with {', '.join(bad)} set: "
                 "the smoke proves the kernels run on the chip")


def report_installation(jax, args):
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    devs = jax.devices()
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}")
    say(f"devices: {len(devs)} x {devs[0].device_kind} "
        f"(platform {devs[0].platform})")
    if args.rehearse_cpu:
        say("REHEARSAL on the CPU at toy width, kernels interpreted: "
            "not a chip run, no number below is a device number")
    elif devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform "
                 f"{devs[0].platform!r}); this smoke runs on the chip only")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, jax reports {len(devs)}")
    return devs


def report_native():
    import shutil
    from paddle_tpu import native
    prebuilt = os.path.exists(native._SO)
    if native.available():
        say("paddle_tpu.native: loaded "
            + ("(already built)" if prebuilt else "(built by this run)"))
    else:
        say("paddle_tpu.native: absent — "
            + ("g++ not found" if shutil.which(
                os.environ.get("CXX", "g++")) is None
               else "build or load failed")
            + "; pure-Python fallbacks in use (not on the train path)")


def model_shape(args):
    """(config kwargs, batch, seq): GPT-2-small at b8 x s1024 as
    bench.py builds it, or the rehearsal's toy."""
    if args.rehearse_cpu:
        return dict(vocab_size=1024, hidden_size=256, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=512,
                    max_position_embeddings=128), 4, 128
    return dict(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                num_attention_heads=12, intermediate_size=3072,
                max_position_embeddings=1024), 8, 1024


def build_runner(args, degrees, use_flash=True):
    """Seeded model + AdamW(master weights) + bf16 O2 + criterion on a
    mesh of ``degrees``, the sequence __graft_entry__ and bench.py use."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    cfg_kw, _, _ = model_shape(args)
    paddle.seed(args.seed)
    cfg = GPTConfig(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=use_flash, **cfg_kw)
    net = GPTForCausalLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    n = math.prod(degrees.values()) if degrees else 1
    mesh = collective.build_mesh(degrees, devices=jax.devices()[:n])
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, GPTPretrainingCriterion(), mesh=mesh)


def batches(args, n):
    """n seeded (inputs, labels) token batches."""
    import numpy as np
    cfg_kw, batch, seq = model_shape(args)
    rng = np.random.RandomState(args.seed)
    out = []
    for _ in range(n):
        x = rng.randint(0, cfg_kw["vocab_size"], (batch, seq)).astype(
            np.int64)
        out.append(([x], [np.roll(x, -1, axis=1)]))
    return out


def timed_steps(runner, data, label):
    """train_step over ``data``; float(loss) waits for the device.
    Prints compile seconds and per-step milliseconds as smoke readings."""
    import jax
    kind = jax.devices()[0].device_kind
    losses, times = [], []
    for inputs, labels in data:
        t0 = time.perf_counter()
        losses.append(float(runner.train_step(inputs, labels)))
        times.append(time.perf_counter() - t0)
    say(f"  {label}: losses " + " ".join(f"{v:.4f}" for v in losses))
    say(f"  {label}: first step (compile + run) {times[0]:.1f} s; later "
        f"steps " + " ".join(f"{t * 1e3:.1f}" for t in times[1:])
        + f" ms — smoke reading on {kind}, not a metric")
    return losses


def custom_call_count(runner, batch, args):
    """tpu_custom_call sites in the compiled train step: each layer's
    forward kernel and its two backward kernels."""
    if args.rehearse_cpu:
        say("  skipped: the interpreter lowers kernels to plain HLO, "
            "there is no tpu_custom_call to count on the CPU")
        return
    cfg_kw, _, _ = model_shape(args)
    want = 3 * cfg_kw["num_hidden_layers"]
    text = runner.lower_step(*batch).compile().as_text()
    n = text.count("tpu_custom_call")
    check(n >= want, f"compiled train step holds {n} tpu_custom_call "
                     f"sites (>= {want}: {cfg_kw['num_hidden_layers']} "
                     f"layers x (forward + dq + dkv))")


def kernel_parity(args, sharding=None):
    """The public flash_attention op, forward and backward, against the
    repo's composed reference on seeded bf16 inputs at the model's
    attention shape.  At initialisation a language-model loss barely
    depends on attention, so the loss checks alone would pass with a
    wrong kernel; this one would not."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops
    cfg_kw, b, s = model_shape(args)
    h = cfg_kw["num_attention_heads"]
    d = cfg_kw["hidden_size"] // h
    rng = np.random.RandomState(args.seed + 1)
    q, k, v, w = (jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
                  for _ in range(4))
    if sharding is not None:
        q, k, v, w = (jax.device_put(x, sharding) for x in (q, k, v, w))

    def flash(q_, k_, v_):
        out = pallas_ops.flash_attention.raw(q_, k_, v_, causal=True)
        return (out * w).astype(jnp.float32).sum(), out

    def composed(q_, k_, v_):
        bh = pallas_ops._heads_to_batch
        out = pallas_ops._batch_to_heads(
            pallas_ops._flash_reference(bh(q_), bh(k_), bh(v_), True), b)
        return (out * w).astype(jnp.float32).sum(), out

    got, want = (jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(
        q, k, v) for f in (flash, composed))
    for name, a, r in zip(("out", "dq", "dk", "dv"),
                          (got[1],) + got[0], (want[1],) + want[0]):
        a, r = (np.asarray(x, np.float32) for x in (a, r))
        err = float(np.abs(a - r).max() / np.abs(r).max())
        check(np.isfinite(a).all() and a.shape == (b, s, h, d)
              and err < KERNEL_RTOL,
              f"flash_attention {name} {a.shape} agrees with the "
              f"composed reference: max error {err:.2e} of its largest "
              f"value (< {KERNEL_RTOL})")


def on_device_check(runner, devices, what):
    name, p = next(iter(runner.network.named_parameters()))
    held = p._value.devices()
    check(held <= set(devices),
          f"{what}: parameter {name} lives on "
          f"{sorted(str(d) for d in held)}")


def half_shards_check(array, devices, what):
    """Each of ``devices`` holds a shard of ``array`` of half its size
    (sharded over 'mp', replicated over 'dp')."""
    shards = array.addressable_shards
    check({s.device for s in shards} == set(devices)
          and all(2 * s.data.size == array.size for s in shards),
          f"{what} {array.shape}: all {len(devices)} devices hold a "
          f"half-size shard")


def one_chip(args, devices):
    cfg_kw, batch, seq = model_shape(args)
    say(f"== one chip: GPT {cfg_kw['num_hidden_layers']} layers x "
        f"{cfg_kw['hidden_size']}, b{batch} x s{seq}, bf16 O2, AdamW ==")
    fresh = batches(args, 5)

    say("composed attention (use_flash_attention=False), forward only:")
    t0 = time.perf_counter()
    ref = build_runner(args, {}, use_flash=False)
    composed_loss = float(ref.eval_step(*fresh[0]))
    say(f"  loss {composed_loss:.4f} on the first training batch "
        f"({time.perf_counter() - t0:.1f} s with build and compile)")
    del ref

    say("flash attention, the default path:")
    runner = build_runner(args, {})
    losses = timed_steps(runner, fresh, "train")
    again = float(runner.eval_step(*fresh[0]))

    uniform = math.log(cfg_kw["vocab_size"])
    check(all(math.isfinite(v) for v in losses), "every loss is finite")
    check(abs(losses[0] - uniform) < 0.5,
          f"first loss {losses[0]:.4f} is within 0.5 of ln(vocab) = "
          f"{uniform:.4f}")
    check(again < losses[0],
          f"loss on the first batch, repeated after the steps, fell: "
          f"{losses[0]:.4f} -> {again:.4f}")
    rel = abs(losses[0] - composed_loss) / abs(composed_loss)
    check(rel < LOSS_RTOL,
          f"first-step loss with flash ({losses[0]:.4f}) and composed "
          f"({composed_loss:.4f}) attention agree: relative difference "
          f"{rel:.2e} (< {LOSS_RTOL})")
    on_device_check(runner, devices[:1], "after training")
    say("compiled train step:")
    custom_call_count(runner, fresh[0], args)
    say("kernel against the composed reference:")
    kernel_parity(args)


def four_chips(args, devices):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg_kw, batch, seq = model_shape(args)
    degrees = {"dp": 2, "mp": 2}
    say(f"== four chips: GPT {cfg_kw['num_hidden_layers']} layers x "
        f"{cfg_kw['hidden_size']}, b{batch} x s{seq}, mesh {degrees} "
        f"against one device ==")
    data = batches(args, 3)

    say("one-device mesh:")
    single = build_runner(args, {})
    want = timed_steps(single, data, "1 device")
    del single

    say("dp2 x mp2 mesh:")
    runner = build_runner(args, degrees)
    mesh = runner.mesh
    for idx in np.ndindex(mesh.devices.shape):
        place = {a: i for a, i in zip(mesh.axis_names, idx)
                 if mesh.shape[a] > 1}
        dev = mesh.devices[idx]
        say(f"  mesh {place}: {dev} "
            f"(chip coords {getattr(dev, 'coords', 'n/a')})")
    got = timed_steps(runner, data, "dp2 x mp2")

    check(all(math.isfinite(v) for v in got), "every loss is finite")
    for i, (a, r) in enumerate(zip(got, want)):
        rel = abs(a - r) / abs(r)
        check(rel < LOSS_RTOL,
              f"step {i + 1}: dp2 x mp2 loss {a:.4f} agrees with one "
              f"device {r:.4f}: relative difference {rel:.2e} "
              f"(< {LOSS_RTOL})")

    name, param = next(
        (n, p) for n, p in runner.network.named_parameters()
        if "mp" in tuple(getattr(p, "dist_spec", None) or ()))
    half_shards_check(param._value, devices[:4], f"parameter {name}")
    for slot, leaf in runner._opt_state[name].items():
        if getattr(leaf, "ndim", 0):
            half_shards_check(leaf, devices[:4],
                              f"optimizer state {name}/{slot}")
    for d in devices[:4]:
        stats = d.memory_stats() or {}
        say(f"  {d}: bytes_in_use {stats.get('bytes_in_use', 'n/a')}")
    say("compiled train step:")
    custom_call_count(runner, data[0], args)
    say("kernel against the composed reference, inputs sharded "
        "(dp, -, mp, -):")
    kernel_parity(args, NamedSharding(mesh, P("dp", None, "mp", None)))


def main():
    args = parse_args()
    configure_environment(args)
    t_start = time.perf_counter()
    import jax
    devices = report_installation(jax, args)

    from paddle_tpu.framework import compile_cache
    cache = compile_cache.enable_compilation_cache()

    def entries():
        return set(os.listdir(cache)) if os.path.isdir(cache) else set()
    entries_before = entries()
    say(f"compile cache: {cache} ({len(entries_before)} entries; "
        + ("placed by JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "the fixed path in the checkout") + ")")
    report_native()

    (four_chips if args.chips == 4 else one_chip)(args, devices)

    # new names, not the net count: a cache with a size limit evicts
    say(f"compile cache: this run added "
        f"{len(entries() - entries_before)} entries")
    say(f"wall time {time.perf_counter() - t_start:.1f} s")
    if args.rehearse_cpu:
        say("rehearsal passed on the CPU — not a chip run, no result line")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
