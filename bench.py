"""Benchmark: GPT-2-small causal-LM training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Workload: the ERNIE/GPT class of baseline configs (BASELINE.json:9-10)
reduced to one chip — bf16 train step (fwd+bwd+AdamW) of a 124M-param
GPT-2-small at batch 8 × seq 1024, compiled to a single XLA program.
A ResNet-50 images/s figure (BASELINE.json:8) is reported as an extra
field when time allows.

vs_baseline: BASELINE.md records no published reference numbers
("published": {} — empty reference mount), so the denominator is the
community-typical per-A100 figure for GPT-2-small-class training used
as the provisional bar: 25k tokens/s/GPU.  Replace when real reference
numbers exist.

Robustness (round-1 failure mode, VERDICT.md weak #2): the TPU backend
can fail or hang during init (`jax.devices()` never returns).  The
parent process therefore runs each workload in a child with a
backend-init watchdog and an overall deadline, retries once when the
failure was early (init-class), and always emits a parseable JSON line.
"""

import json
import os
import subprocess
import sys
import threading
import time

BASELINE_TOKENS_PER_SEC = 25_000.0
BASELINE_RESNET50_IMG_PER_SEC = 400.0   # community per-A100 fp16 figure

INIT_DEADLINE_S = 150     # child must report `devices-ok` within this
GPT_DEADLINE_S = 480      # full GPT bench wall-clock cap
GLOBAL_DEADLINE_S = 900   # parent never runs longer than this
RETRY_ONLY_BEFORE_S = 240  # retry only if attempt 1 failed early

# Peak dense bf16 matmul rate by ``jax.devices()[0].device_kind``, for
# the MFU fields.  A kind that is not here is an error, not a default.
PEAK_BF16_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip;
    # "TPU v5 lite" is what jax 0.9.0 reports for that chip
    "TPU v5 lite": 197.0,
}


def _peak_tflops() -> float:
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no published peak for device_kind {kind!r}: add it to "
            "PEAK_BF16_TFLOPS with its source before reporting MFU")
    return PEAK_BF16_TFLOPS[kind]


def _emit_result(mode: str, out: dict):
    """Print the child's RESULT record with the process-wide
    observability snapshot attached (ROADMAP observability follow-up):
    instead of each workload hand-rolling its own stats dict, the full
    metrics registry + trace summary land in one
    ``observability.export.dump_json`` file per child, and the RESULT
    record carries its path — so a bench round's record can answer
    anything the registry can (dispatch counts, checkpoint IO,
    serving histograms), not just the headline numbers."""
    try:
        from paddle_tpu.observability import export as _obs_export
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".bench_obs", f"{mode}.json")
        out[f"obs_snapshot_{mode}"] = _obs_export.dump_json(path)
    except Exception as e:  # a metrics failure must not eat the result
        out[f"obs_snapshot_{mode}_error"] = f"{type(e).__name__}: {e}"
    print("RESULT " + json.dumps(out), flush=True)


def _maybe_force_cpu():
    # Testing hook: exercise the bench mechanics without TPU hardware.
    # Must run before any backend init.
    if os.environ.get("GRAFT_BENCH_FORCE_CPU"):
        import jax
        jax.config.update("jax_platforms", "cpu")


def _timed_bench(build, steps, pipeline_steps=0, batch_gen=None,
                 runner_kwargs=None, timings=None):
    """Shared scaffold: build (model, opt, loss, data) then time steps.

    `build` returns (net, opt, loss_fn, inputs, labels, units_per_step).
    Returns (units/sec, step_ms[, pipeline_units/sec]) over `steps`
    timed steps after compile + warmup.  The base measurement stages
    inputs once; when `batch_gen` is given, a second loop feeds FRESH
    host batches through the DataLoader's device double-buffer
    (_DevicePrefetcher) so the number includes real input-pipeline
    overlap (VERDICT r3 next #8).  ``timings`` (optional dict) receives
    ``train_compile_s`` — model build + placement + first compiled
    step, the per-process cold-start cost the training rounds record
    every round like serving records ``serving_compile_warmup_s``
    (ROADMAP "compile-time as a product metric")."""
    _maybe_force_cpu()
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner

    print("devices-ok", jax.devices(), flush=True)
    t_build0 = time.perf_counter()
    paddle.seed(0)
    net, opt, loss_fn, inputs, labels, units = build()
    mesh = collective.build_mesh({})
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, loss_fn, mesh=mesh,
                               **(runner_kwargs or {}))
    inputs = [Tensor(jax.device_put(v)) for v in inputs]
    labels = [Tensor(jax.device_put(v)) for v in labels]

    float(runner.train_step(inputs, labels))   # compile
    if timings is not None:
        timings["train_compile_s"] = round(
            time.perf_counter() - t_build0, 2)
    print("compiled", flush=True)
    float(runner.train_step(inputs, labels))   # warmup

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = runner.train_step(inputs, labels)
    jax.block_until_ready(runner._opt_state)
    float(loss)
    dt = time.perf_counter() - t0
    if not (pipeline_steps and batch_gen):
        return units * steps / dt, dt / steps * 1000.0

    # input-pipeline overlap: fresh batches, host gen + H2D double
    # buffered ahead of the consuming step
    from paddle_tpu.io.dataloader import _DevicePrefetcher

    def gen():
        for i in range(pipeline_steps):
            xs, ys = batch_gen(i)
            yield ([Tensor(v) for v in xs], [Tensor(v) for v in ys])

    it = _DevicePrefetcher(gen(), depth=2)
    first = next(it)
    runner.train_step(*first)   # same shapes — no recompile
    jax.block_until_ready(runner._opt_state)   # sync before timing
    t0 = time.perf_counter()
    n = 0
    for batch_in, batch_lb in it:
        loss = runner.train_step(batch_in, batch_lb)
        n += 1
    jax.block_until_ready(runner._opt_state)
    float(loss)
    dt2 = time.perf_counter() - t0
    return (units * steps / dt, dt / steps * 1000.0,
            units * n / dt2 if n else 0.0)


def bench_gpt():
    import numpy as np
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))  # mechanics smoke

    def build():
        if tiny:
            cfg = GPTConfig(vocab_size=1024, hidden_size=64,
                            num_hidden_layers=2, num_attention_heads=4,
                            intermediate_size=128,
                            max_position_embeddings=128,
                            hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0,
                            use_flash_attention=False)
            batch, seq = 2, 64
        else:
            cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                            num_hidden_layers=12, num_attention_heads=12,
                            intermediate_size=3072,
                            max_position_embeddings=1024,
                            hidden_dropout_prob=0.0,
                            attention_probs_dropout_prob=0.0,
                            use_flash_attention=True)
            batch, seq = 8, 1024
        net = GPTForCausalLM(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=net.parameters(),
                              multi_precision=True)
        # O2: bf16 params + fp32 master weights in the optimizer
        amp.decorate(net, opt, level="O2", dtype="bfloat16")
        crit = GPTPretrainingCriterion()
        rng = np.random.RandomState(0)
        x = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        y = np.roll(x, -1, axis=1)
        return (net, opt, crit, [x], [y], batch * seq)

    def batch_gen(i):
        rng = np.random.RandomState(1000 + i)
        vocab = 1024 if tiny else 50304
        b, s = (2, 64) if tiny else (8, 1024)
        x = rng.randint(0, vocab, (b, s)).astype(np.int64)
        return [x], [np.roll(x, -1, axis=1)]

    timings = {}
    res = _timed_bench(build, steps=2 if tiny else 15,
                       pipeline_steps=3 if tiny else 10,
                       batch_gen=batch_gen, timings=timings)
    tps, step_ms = res[0], res[1]
    tps_pipe = res[2] if len(res) > 2 else None

    # model flops per token (matmul-only, PaLM-style accounting):
    # 6*N for the dense/embedding matmuls + 6*L*d*S for causal
    # attention (12*L*d*S non-causal halved)
    if tiny:
        n_params, L, d, S = 0, 0, 0, 0
        flops_tok = 0.0
    else:
        n_params = 124_439_808          # GPT-2-small incl. tied embed
        L, d, S = 12, 768, 1024
        flops_tok = 6.0 * n_params + 6.0 * L * d * S
    out = {"tokens_per_sec": tps, "step_ms": round(step_ms, 2)}
    out.update(timings)        # train_compile_s: cold-start on record
    if tps_pipe:
        out["tokens_per_sec_pipeline"] = round(tps_pipe, 1)
        out["pipeline_overlap_ratio"] = round(tps_pipe / tps, 3)
    if flops_tok:
        out["model_tflops_per_sec"] = round(tps * flops_tok / 1e12, 2)
        out["mfu"] = round(
            tps * flops_tok / (_peak_tflops() * 1e12), 4)
        out["flops_per_token_m"] = round(flops_tok / 1e6, 1)
    _emit_result("gpt", out)


def bench_resnet():
    import numpy as np
    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.vision import models as vmodels

    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))  # mechanics smoke
    batch, size, classes = (4, 32, 10) if tiny else (64, 224, 1000)

    def build():
        net = vmodels.resnet18(num_classes=classes) if tiny \
            else vmodels.resnet50()
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=net.parameters(),
                                 multi_precision=True)
        amp.decorate(net, opt, level="O2", dtype="bfloat16")
        rng = np.random.RandomState(0)
        x = rng.rand(batch, 3, size, size).astype(np.float32)
        y = rng.randint(0, classes, (batch,)).astype(np.int64)
        return (net, opt, nn.CrossEntropyLoss(), [x], [y], batch)

    # conv needs the auto_cast hook under O2: BN outputs stay fp32,
    # the hook casts conv inputs back to bf16 (upstream O2 forward
    # runs inside auto_cast)
    ips, step_ms = _timed_bench(
        build, steps=2 if tiny else 10,
        runner_kwargs={"amp_level": "O2", "amp_dtype": "bfloat16"})
    out = {"images_per_sec": ips, "step_ms": round(step_ms, 2)}
    if not tiny:
        # ResNet-50 fwd flops ~4.1 GFLOP/image at 224x224; train ~3x
        flops_img = 3.0 * 4.1e9
        out["mfu"] = round(
            ips * flops_img / (_peak_tflops() * 1e12), 4)
    _emit_result("resnet", out)


def bench_ernie():
    """ERNIE-3.0-base-class MLM pretrain throughput (the second half of
    the north-star primary metric, BASELINE.json:2)."""
    import numpy as np
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models import (BertForPretraining,
                                   BertPretrainingCriterion, ernie_3_base)

    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    if tiny:
        from paddle_tpu.models import BertConfig
        cfg = BertConfig(vocab_size=1024, hidden_size=64,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=128,
                         max_position_embeddings=128,
                         hidden_dropout_prob=0.0,
                         attention_probs_dropout_prob=0.0)
        batch, seq = 2, 64
    else:
        cfg = ernie_3_base(hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0)
        batch, seq = 16, 512

    def build():
        net = BertForPretraining(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=net.parameters(),
                              multi_precision=True)
        amp.decorate(net, opt, level="O2", dtype="bfloat16")
        rng = np.random.RandomState(0)
        x = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        # 15% MLM positions; the rest ignore_index=-100
        labels = np.where(rng.rand(batch, seq) < 0.15, x, -100)
        return (net, opt, BertPretrainingCriterion(cfg.vocab_size),
                [x], [labels.astype(np.int64)], batch * seq)

    timings = {}
    tps, step_ms = _timed_bench(build, steps=2 if tiny else 10,
                                timings=timings)
    _emit_result("ernie", {
        "tokens_per_sec": tps, "step_ms": round(step_ms, 2),
        **timings})


def bench_detector():
    """PP-YOLOE-s-class detector train throughput on BUCKETED dynamic
    shapes (config 5's detector half, BASELINE.json:11): one compiled
    program per image-size bucket, alternating buckets per step —
    exactly the dynamic-shape story the upstream detector stresses."""
    import numpy as np
    import jax
    from paddle_tpu import optimizer
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.vision.models.ppyoloe import (ppyoloe_crn_s,
                                                  ppyoloe_tiny)
    import paddle_tpu as paddle

    _maybe_force_cpu()
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    paddle.seed(0)
    if tiny:
        net, batch, sizes, steps = ppyoloe_tiny(num_classes=4), 2, \
            (64,), 2
    else:
        net, batch, sizes, steps = ppyoloe_crn_s(num_classes=80), 8, \
            (640, 512), 10
    net.train()
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=net.parameters())
    params = F.param_dict(net)
    frozen = F.frozen_dict(net)
    buffers = F.buffer_dict(net)
    state = opt.init_state_tree(params)

    @jax.jit
    def step(p, st, imgs, boxes, labels, mask):
        def loss_fn(pp):
            with F.bind(net, pp, buffers, frozen):
                out = net(Tensor(imgs), gt_boxes=Tensor(boxes),
                          gt_labels=Tensor(labels), gt_mask=Tensor(mask))
            return out["loss"]._value
        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_s = opt.apply_gradients_tree(p, grads, st, 1e-3)
        return loss, new_p, new_s

    rng = np.random.RandomState(0)
    gmax = 8

    def batch_for(size):
        imgs = rng.rand(batch, 3, size, size).astype(np.float32)
        boxes = rng.rand(batch, gmax, 4).astype(np.float32) * size
        boxes = np.concatenate([np.minimum(boxes[..., :2],
                                           boxes[..., 2:]),
                                np.maximum(boxes[..., :2],
                                           boxes[..., 2:]) + 4], -1)
        labels = rng.randint(0, 4, (batch, gmax)).astype(np.int64)
        mask = (rng.rand(batch, gmax) < 0.5).astype(np.float32)
        mask[:, 0] = 1.0
        return imgs, boxes, labels, mask

    data = {s: batch_for(s) for s in sizes}
    for s in sizes:                       # compile each bucket
        loss, params, state = step(params, state, *data[s])
    float(loss)
    t0 = time.perf_counter()
    n = 0
    for i in range(steps):
        s = sizes[i % len(sizes)]
        loss, params, state = step(params, state, *data[s])
        n += batch
    float(loss)
    dt = time.perf_counter() - t0
    _emit_result("detector", {
        "images_per_sec": n / dt,
        "step_ms": round(dt / steps * 1000.0, 2),
        "buckets": list(sizes)})


def bench_vit():
    """ViT-B/16 train throughput on BUCKETED multi-resolution input
    (config 5's ViT half, BASELINE.json:11): position embeddings
    interpolate per bucket, one compiled program per bucket,
    alternating buckets per step."""
    import numpy as np
    import jax
    from paddle_tpu import optimizer, nn
    from paddle_tpu.nn import functional_call as F
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.vision.models import VisionTransformer
    import paddle_tpu as paddle

    _maybe_force_cpu()
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    paddle.seed(0)
    if tiny:
        net = VisionTransformer(img_size=32, patch_size=8, in_chans=3,
                                num_classes=4, embed_dim=64, depth=2,
                                num_heads=4)
        batch, sizes, steps = 2, (32, 48), 2   # 48 exercises pos-embed
        # interpolation even in the tiny smoke
    else:
        net = VisionTransformer(img_size=224, patch_size=16,
                                num_classes=1000)   # ViT-B/16
        batch, sizes, steps = 32, (224, 192), 10
    net.train()
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=net.parameters(),
                          multi_precision=True)
    from paddle_tpu import amp
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    lossf = nn.CrossEntropyLoss()
    params = F.param_dict(net)
    frozen = F.frozen_dict(net)
    buffers = F.buffer_dict(net)
    state = opt.init_state_tree(params)

    @jax.jit
    def step(p, st, imgs, labels):
        def loss_fn(pp):
            # O2 forward runs inside auto_cast (upstream contract; the
            # hook casts f32 inputs to the bf16 params' dtype)
            from paddle_tpu.amp import auto_cast
            with F.bind(net, pp, buffers, frozen):
                with auto_cast(level="O2", dtype="bfloat16"):
                    out = net(Tensor(imgs))
                return lossf(out, Tensor(labels))._value
        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_s = opt.apply_gradients_tree(p, grads, st, 1e-3)
        return loss, new_p, new_s

    rng = np.random.RandomState(0)
    data = {}
    for s in sizes:
        imgs = rng.rand(batch, 3, s, s).astype(np.float32)
        labels = rng.randint(0, 4 if tiny else 1000,
                             (batch,)).astype(np.int64)
        data[s] = (imgs, labels)
    for s in sizes:                       # compile each bucket
        loss, params, state = step(params, state, *data[s])
    float(loss)
    t0 = time.perf_counter()
    n = 0
    for i in range(steps):
        s = sizes[i % len(sizes)]
        loss, params, state = step(params, state, *data[s])
        n += batch
    float(loss)
    dt = time.perf_counter() - t0
    _emit_result("vit", {
        "images_per_sec": n / dt,
        "step_ms": round(dt / steps * 1000.0, 2),
        "buckets": list(sizes)})


def bench_hapi():
    """Model.fit loop-overhead microbench — CPU by DESIGN: it needs no
    chip and says nothing about device time.  A deliberately tiny
    fixed-shape MLP makes the compiled step ~free; steps/s then tracks
    the HOST side of the hot loop: dispatch, train-state plumbing,
    metric and logging syncs (DESIGN-PERF.md).

    Fold sweep (ISSUE 5): GRAFT_BENCH_HAPI_FOLDS (default "1,8") lists
    the ``steps_per_dispatch`` values to measure.  All folds run
    back-to-back inside ONE child, interleaved rep by rep, so the
    medians-of-3 stay comparable on this noisy shared container.
    Fold 1 doubles as the no-regression guard against the PR-4 loop."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer

    print("devices-ok", jax.devices(), flush=True)
    folds = [int(f) for f in os.environ.get(
        "GRAFT_BENCH_HAPI_FOLDS", "1,8").split(",")]
    reps = int(os.environ.get("GRAFT_BENCH_HAPI_REPS", "3"))
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                        nn.Linear(32, 10))
    model = paddle.Model(net)
    model.prepare(optimizer.Adam(1e-3, parameters=model.parameters()),
                  nn.CrossEntropyLoss(), paddle.metric.Accuracy())
    rng = np.random.RandomState(0)
    batches = [[rng.rand(16, 16).astype(np.float32),
                rng.randint(0, 10, (16,)).astype(np.int64)]
               for _ in range(48)]
    steps = len(batches)
    epochs = 8
    t_compile0 = time.perf_counter()
    for f in folds:   # compile + warmup epoch per fold entry
        model.fit(batches, epochs=1, verbose=0, steps_per_dispatch=f)
    # cold-start on record every round, like serving_compile_warmup_s
    # (ROADMAP "compile-time as a product metric"): first-epoch wall
    # time across the fold sweep = trace + compile + warmup
    hapi_compile_warmup_s = round(time.perf_counter() - t_compile0, 2)
    # tracing overhead (ISSUE 8 acceptance: < 2% on this microbench):
    # the LARGEST fold also runs with the observability span recorder
    # armed, INTERLEAVED with the untraced reps so the paired medians
    # see the same container noise/drift
    from paddle_tpu.observability import trace as _obs_trace
    ftr = max(folds)
    samples = {f: [] for f in folds}
    traced = []
    n_trace_events = 0
    for _ in range(reps):
        for f in folds:   # interleaved: back-to-back medians
            t0 = time.perf_counter()
            model.fit(batches, epochs=epochs, verbose=0,
                      steps_per_dispatch=f)
            jax.block_until_ready(
                [p._value for p in model.network.parameters()])
            dt = time.perf_counter() - t0
            samples[f].append(steps * epochs / dt)
        _obs_trace.clear()
        _obs_trace.enable()
        try:
            t0 = time.perf_counter()
            model.fit(batches, epochs=epochs, verbose=0,
                      steps_per_dispatch=ftr)
            jax.block_until_ready(
                [p._value for p in model.network.parameters()])
            traced.append(steps * epochs / (time.perf_counter() - t0))
        finally:
            _obs_trace.disable()
        n_trace_events = len(_obs_trace.events())
        _obs_trace.clear()
    out = {"hapi_compile_warmup_s": hapi_compile_warmup_s}
    for f in folds:
        med = sorted(samples[f])[len(samples[f]) // 2]
        key = ("hapi_fit_steps_per_sec" if f == 1
               else f"hapi_fit_steps_per_sec_fold{f}")
        out[key] = round(med, 1)
        if f == 1:
            out["hapi_fit_step_ms"] = round(1000.0 / med, 3)
    if 1 in folds:
        base = out["hapi_fit_steps_per_sec"]
        for f in folds:
            if f != 1 and base:
                out[f"hapi_fold{f}_speedup"] = round(
                    out[f"hapi_fit_steps_per_sec_fold{f}"] / base, 3)
    med_tr = sorted(traced)[len(traced) // 2]
    key_off = ("hapi_fit_steps_per_sec" if ftr == 1
               else f"hapi_fit_steps_per_sec_fold{ftr}")
    out[f"hapi_fit_steps_per_sec_fold{ftr}_traced"] = round(med_tr, 1)
    out["hapi_trace_overhead_pct"] = round(
        100.0 * (1.0 - med_tr / out[key_off]), 2)
    out["hapi_trace_events"] = n_trace_events
    # auto-K (ISSUE 7): unasked, the tuner must land K>1 on this
    # host-bound microbench; record the decision alongside the sweep
    model.fit(batches, epochs=2, verbose=0)
    if model._fold_tuner is not None and model._fold_tuner.decided:
        out["hapi_auto_fold"] = model._fold
        d = model._fold_tuner.decision
        out["hapi_auto_host_ms_per_step"] = d["host_ms_per_step"]
        out["hapi_auto_device_ms_per_step"] = d["device_ms_per_step"]
    _emit_result("hapi", out)


def bench_mesh_fold():
    """DistributedRunner fold sweep on a CPU dp mesh (ISSUE 7): the
    mesh half of the unified dispatch engine, measured the same way
    bench_hapi measures the single-chip half.  CPU by DESIGN — 8 fake
    host devices stand in for a multichip slice; what folding removes
    is HOST dispatch overhead, which this measures directly.

    fold=1 dispatches scan-of-1 through the unified engine; fold=K
    dispatches scan-of-K; ``legacy`` is the pre-unification per-step
    ``train_step`` entry, the no-regression guard.  All variants run
    interleaved rep by rep in ONE child for comparable medians."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner

    print("devices-ok", jax.devices(), flush=True)
    folds = [int(f) for f in os.environ.get(
        "GRAFT_BENCH_MESH_FOLDS", "1,8").split(",")]
    reps = int(os.environ.get("GRAFT_BENCH_MESH_REPS", "3"))
    dp = int(os.environ.get("GRAFT_BENCH_MESH_DP", "2"))
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                        nn.Linear(32, 10))
    opt = optimizer.Adam(1e-3, parameters=net.parameters())
    mesh = collective.build_mesh({"dp": dp})
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, nn.CrossEntropyLoss(),
                               mesh=mesh)
    rng = np.random.RandomState(0)
    batches = [([rng.rand(16, 16).astype(np.float32)],
                [rng.randint(0, 10, (16,)).astype(np.int64)])
               for _ in range(48)]
    steps, rounds = len(batches), 4

    def run_epoch(f):
        if f == 0:                       # legacy per-step entry
            for ins, lbs in batches:
                runner.train_step(ins, lbs)
            return
        for i in range(0, steps, f):
            runner.train_steps_folded(batches[i:i + f])

    variants = [0] + folds               # 0 = legacy baseline
    t_compile0 = time.perf_counter()
    for f in variants:                   # compile + warmup epoch each
        run_epoch(f)
    mesh_compile_warmup_s = round(time.perf_counter() - t_compile0, 2)
    samples = {f: [] for f in variants}
    for _ in range(reps):
        for f in variants:               # interleaved medians
            t0 = time.perf_counter()
            for _ in range(rounds):
                run_epoch(f)
            jax.block_until_ready(runner._opt_state)
            dt = time.perf_counter() - t0
            samples[f].append(steps * rounds / dt)
    out = {"mesh_dp": dp,
           "mesh_compile_warmup_s": mesh_compile_warmup_s}
    for f in variants:
        med = sorted(samples[f])[len(samples[f]) // 2]
        key = ("mesh_fit_steps_per_sec_legacy" if f == 0 else
               "mesh_fit_steps_per_sec" if f == 1 else
               f"mesh_fit_steps_per_sec_fold{f}")
        out[key] = round(med, 1)
    base = out.get("mesh_fit_steps_per_sec")
    for f in folds:
        if f != 1 and base:
            out[f"mesh_fold{f}_speedup"] = round(
                out[f"mesh_fit_steps_per_sec_fold{f}"] / base, 3)
    _emit_result("mesh_fold", out)


def bench_pp_fold():
    """Pipeline-engine fold sweep on a CPU pp=2 mesh (ISSUE 15): the
    pipeline half of the unified dispatch engine, measured like
    --mesh-fold measures the dp half.  CPU by DESIGN — what folding
    removes is HOST work per train batch, which this measures
    directly.

    ``legacy`` is the pre-unification per-batch entry (host-drawn key,
    per-batch stacked-leaf wrapper commit); fold=1 dispatches the
    whole stages×microbatches schedule as scan-of-1 through the
    unified engine; fold=K covers K whole batches per dispatch with
    the wrapper sync deferred to the epoch boundary.  Host-dispatch
    accounting per batch rides the engine's own registry counters
    (``pp_dispatches_total`` = compiled dispatches,
    ``pp_commit_ops_total`` = stacked-leaf wrapper slice ops): the
    ISSUE 15 acceptance — O(1) compiled dispatches per batch at
    fold=1, O(1/K) at fold K — is read straight off the record."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \
        import PipelineParallel
    from paddle_tpu.framework.dispatch import (AutoFoldTuner,
                                               GroupDispatcher)
    from paddle_tpu.observability import metrics as obs_metrics

    print("devices-ok", jax.devices(), flush=True)
    folds = [int(f) for f in os.environ.get(
        "GRAFT_BENCH_PP_FOLDS", "1,8").split(",")]
    reps = int(os.environ.get("GRAFT_BENCH_PP_REPS", "3"))
    micro = int(os.environ.get("GRAFT_BENCH_PP_MICRO", "4"))

    class Block(nn.Layer):
        def __init__(self, d):
            super().__init__()
            self.fc = nn.Linear(d, d)

        def forward(self, x):
            return nn.functional.relu(self.fc(x))

    paddle.seed(0)
    net = PipelineLayer(
        [nn.Linear(16, 32)] + [Block(32) for _ in range(4)] +
        [nn.Linear(32, 10)],
        num_stages=2, loss_fn=nn.CrossEntropyLoss())
    opt = optimizer.Adam(1e-3, parameters=net.parameters())
    mesh = collective.build_mesh({"pp": 2},
                                 devices=jax.devices()[:2])
    collective.set_mesh(mesh)

    class _Strat:
        pipeline_configs = {"accumulate_steps": micro}

    eng = PipelineParallel(net, None, _Strat(), optimizer=opt)
    rng = np.random.RandomState(0)
    batches = [([rng.rand(16, 16).astype(np.float32)],
                [rng.randint(0, 10, (16,)).astype(np.int64)])
               for _ in range(48)]
    steps, rounds = len(batches), 4
    reg = obs_metrics.registry()

    def counters():
        return {name: reg.counter(name).collect()
                for name in ("pp_dispatches_total",
                             "pp_commit_ops_total")}

    def run_epoch(f):
        if f == 0:                       # legacy per-batch entry
            eng.dispatch_mode = "legacy"
            try:
                for ins, lbs in batches:
                    eng.train_batch((ins[0], lbs[0]), opt)
            finally:
                eng.dispatch_mode = "unified"
            return
        # unified fold path, wrapper sync deferred to the epoch
        # boundary exactly like Model.fit defers it
        eng._defer_wrapper_sync = True
        try:
            for i in range(0, steps, f):
                eng.train_steps_folded(batches[i:i + f])
        finally:
            eng._defer_wrapper_sync = False
            eng.sync_to_layers()

    variants = [0] + folds               # 0 = legacy baseline
    t_compile0 = time.perf_counter()
    for f in variants:                   # compile + warmup epoch each
        run_epoch(f)
    pp_compile_warmup_s = round(time.perf_counter() - t_compile0, 2)
    samples = {f: [] for f in variants}
    dispatch_rec = {}
    for r in range(reps):
        for f in variants:               # interleaved medians
            c0 = counters()
            t0 = time.perf_counter()
            for _ in range(rounds):
                run_epoch(f)
            jax.block_until_ready(eng._opt_tree)
            dt = time.perf_counter() - t0
            samples[f].append(steps * rounds / dt)
            if r == 0:
                c1 = counters()
                n = steps * rounds
                dispatch_rec[f] = {
                    "dispatches_per_batch": round(
                        (c1["pp_dispatches_total"]
                         - c0["pp_dispatches_total"]) / n, 4),
                    "commit_ops_per_batch": round(
                        (c1["pp_commit_ops_total"]
                         - c0["pp_commit_ops_total"]) / n, 4),
                }
    out = {"pp_degree": 2, "pp_microbatches": micro,
           "pp_compile_warmup_s": pp_compile_warmup_s}
    for f in variants:
        med = sorted(samples[f])[len(samples[f]) // 2]
        key = ("pp_fit_steps_per_sec_legacy" if f == 0 else
               "pp_fit_steps_per_sec" if f == 1 else
               f"pp_fit_steps_per_sec_fold{f}")
        out[key] = round(med, 1)
        tag = ("legacy" if f == 0 else
               "fold1" if f == 1 else f"fold{f}")
        for k, v in dispatch_rec.get(f, {}).items():
            out[f"pp_{k}_{tag}"] = v
    base = out.get("pp_fit_steps_per_sec")
    for f in folds:
        if f != 1 and base:
            out[f"pp_fold{f}_speedup"] = round(
                out[f"pp_fit_steps_per_sec_fold{f}"] / base, 3)
    # auto-K through the SAME GroupDispatcher/AutoFoldTuner machinery
    # Model.fit drives: the tuner watches the first dispatches and
    # freezes K from the measured host/device ratio
    tuner = AutoFoldTuner()
    eng._defer_wrapper_sync = True
    try:
        disp = GroupDispatcher(
            lambda groups: (eng.train_steps_folded(groups)[0], []),
            lambda *a: None, fold=1, tuner=tuner)
        for i, (ins, lbs) in enumerate(batches * 2):
            disp.feed(i, ins, lbs)
        disp.flush()
    finally:
        eng._defer_wrapper_sync = False
        eng.sync_to_layers()
    if tuner.decided:
        out["pp_auto_fold"] = tuner.fold
        out["pp_auto_host_ms_per_step"] = \
            tuner.decision["host_ms_per_step"]
        out["pp_auto_device_ms_per_step"] = \
            tuner.decision["device_ms_per_step"]
    _emit_result("pp_fold", out)


def _hlo_dp_collective_bytes(hlo_text, mesh):
    """Bytes-moved proxy from the COMPILED program: per-device WIRE
    bytes of every collective whose replica group spans the dp axis.
    A collective's result size is not its wire cost, so each opcode is
    normalized to the ring/tiled wire volume for its group size W:
    all-reduce = 2*(W-1)/W * result, all-gather = (W-1)/W * result,
    reduce-scatter = (W-1) * result (the per-device result is 1/W of
    the input), collective-permute = result (one hop's payload).
    With that normalization every variant cross-checks the analytic
    `dp_comm_bytes_per_step` model within a few percent;
    `tests/test_hlo_collective_audit` asserts it under pytest."""
    import re
    import numpy as np

    dtype_bytes = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                   "s32": 4, "u64": 8, "u32": 4, "s8": 1, "u8": 1,
                   "pred": 1, "s16": 2, "u16": 2}

    def decode_groups(attr):
        attr = attr.strip()
        m = re.match(r"\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                     r"(?:T\(([\d,]+)\))?", attr)
        if m:
            g, s = int(m.group(1)), int(m.group(2))
            dims = [int(x) for x in m.group(3).split(",")]
            x = np.arange(int(np.prod(dims))).reshape(dims)
            if m.group(4):
                x = x.transpose([int(p) for p in m.group(4).split(",")])
            return x.reshape(g, s).tolist()
        if attr.startswith("{"):
            return [[int(v) for v in grp.split(",")]
                    for grp in re.findall(r"\{([\d,\s]+)\}", attr)
                    if grp.strip()]
        raise ValueError(f"unparsed replica_groups: {attr!r}")

    def result_bytes(line):
        m = re.search(
            r"=\s*(.*?)\s*(?:all-reduce|reduce-scatter|all-gather|"
            r"collective-permute|all-to-all)(?:-start|-done)?\(", line)
        if not m:
            return 0
        total = 0
        for dt, shp in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
            if dt not in dtype_bytes:
                continue
            n = 1
            for d in shp.split(","):
                if d:
                    n *= int(d)
            total += n * dtype_bytes[dt]
        return total

    axis_names = list(mesh.axis_names)
    dp_axis = axis_names.index("dp")
    coord_of = {i: np.unravel_index(i, mesh.devices.shape)
                for i in range(mesh.devices.size)}

    def spans_dp(device_ids):
        coords = [coord_of[d] for d in device_ids]
        return len({c[dp_axis] for c in coords}) > 1

    def wire_factor(line, group_size):
        w = max(group_size, 2)
        if "all-reduce" in line:
            return 2.0 * (w - 1) / w
        if "all-gather" in line:
            return (w - 1) / w
        if "reduce-scatter" in line:
            return float(w - 1)
        return 1.0                        # collective-permute: one hop

    total = 0.0
    for line in hlo_text.splitlines():
        if "replica_groups=" in line:
            mg = re.search(
                r"replica_groups=(\{\{[^}]*\}[^)]*\}|\[[^ ]+)", line)
            if not mg:
                continue
            try:
                groups = decode_groups(mg.group(1))
            except ValueError:
                continue
            if spans_dp(groups[0]):
                total += result_bytes(line) * wire_factor(
                    line, len(groups[0]))
        elif "source_target_pairs=" in line:
            # the explicit ring's hops: one collective-permute per hop,
            # its result IS the wire payload of that hop
            pairs = re.findall(r"\{(\d+),(\d+)\}", line)
            if pairs and any(spans_dp([int(a), int(b)])
                             for a, b in pairs):
                total += result_bytes(line)
    return int(total)


def bench_dp_compressed():
    """Compressed + sharded dp gradient path on the CPU mesh
    (ISSUE 11 / DESIGN-DCN.md §Strategy knobs): sweep
    {off, bits=16, bits=8} x {sharded update on/off}, recording per
    variant: steps/s (interleaved medians, like the mesh-fold sweep),
    the modeled per-device dp wire bytes per step AND the compiled-HLO
    bytes-moved proxy that cross-checks it, per-replica opt_state
    bytes (the 1/dp memory win), a bits=16-vs-off end-loss parity bit,
    and the DESIGN-DCN simulated scaling efficiency at 256 chips for
    each wire format (the >=90% north-star gate)."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner

    print("devices-ok", jax.devices(), flush=True)
    dp = int(os.environ.get("GRAFT_BENCH_DP", "2"))
    reps = int(os.environ.get("GRAFT_BENCH_DP_REPS", "3"))
    # leaves >> the 256-elt quantization block so block padding is
    # negligible and the HLO bytes proxy is comparable to the model
    def build():
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(256, 512), nn.ReLU(),
                            nn.Linear(512, 64))
        opt = optimizer.Adam(1e-3, parameters=net.parameters())
        return net, opt

    rng = np.random.RandomState(0)
    batches = [([rng.rand(16, 256).astype(np.float32)],
                [rng.randint(0, 64, (16,)).astype(np.int64)])
               for _ in range(24)]
    variants = [(0, False), (16, False), (8, False),
                (0, True), (16, True), (8, True)]
    runners, final_loss, audits = {}, {}, {}
    mesh = collective.build_mesh({"dp": dp})
    collective.set_mesh(mesh)
    t0 = time.perf_counter()
    for bits, shard in variants:
        net, opt = build()
        r = DistributedRunner(net, opt, nn.CrossEntropyLoss(),
                              mesh=mesh, dp_compress_bits=bits,
                              dp_shard_update=shard)
        hlo = r.lower_step(*batches[0]).compile().as_text()
        audits[(bits, shard)] = _hlo_dp_collective_bytes(hlo, mesh)
        for ins, lbs in batches:                  # warmup epoch
            loss = r.train_step(ins, lbs)
        final_loss[(bits, shard)] = float(loss)
        runners[(bits, shard)] = r
    compile_warmup_s = round(time.perf_counter() - t0, 2)

    samples = {v: [] for v in variants}
    for _ in range(reps):
        for v in variants:                        # interleaved medians
            r = runners[v]
            t0 = time.perf_counter()
            for ins, lbs in batches:
                r.train_step(ins, lbs)
            jax.block_until_ready(r._opt_state)
            samples[v].append(len(batches) /
                              (time.perf_counter() - t0))

    # simulated scaling efficiency (scripts/scaling_projection.py's
    # grounded model, GPT-2-small measured step time) per wire format
    import importlib.util as _ilu
    spec = _ilu.spec_from_file_location(
        "scaling_projection",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "scripts", "scaling_projection.py"))
    proj = _ilu.module_from_spec(spec)
    spec.loader.exec_module(proj)

    grad_elems = sum(int(np.prod(p.shape))
                     for p in runners[(0, False)].network.parameters()
                     if not p.stop_gradient)
    out = {"dp_compressed_dp": dp,
           "dp_compressed_compile_warmup_s": compile_warmup_s,
           "dp_compressed_grad_elems": grad_elems,
           "dp_compressed_bits16_end_loss_parity": (
               final_loss[(16, False)] == final_loss[(0, False)]),
           "dp_compressed_bits8_end_loss_delta": round(
               abs(final_loss[(8, False)] - final_loss[(0, False)]),
               6)}
    for wire, label in (("f32", "off"), ("int8", "int8")):
        out[f"dp_sim_scaling_eff_256chips_{label}"] = round(
            proj.efficiency(132.0, 124e6, 256, wire), 4)
    for (bits, shard), vals in samples.items():
        tag = f"b{bits}_{'sharded' if shard else 'replicated'}"
        med = sorted(vals)[len(vals) // 2]
        out[f"dp_steps_per_sec_{tag}"] = round(med, 1)
        out[f"dp_hlo_bytes_{tag}"] = audits[(bits, shard)]
        # the runner's own per-leaf model (replicated-fallback leaves
        # modeled as the full all-reduce they actually run)
        r = runners[(bits, shard)]
        out[f"dp_model_bytes_{tag}"] = \
            r._dp_comm_info["bytes_per_step"]
        st_bytes = 0
        for st in r._opt_state.values():
            for v in st.values():
                st_bytes += max(
                    s.data.nbytes for s in v.addressable_shards)
        out[f"dp_opt_state_bytes_per_rank_{tag}"] = st_bytes
    _emit_result("dp_compressed", out)


def bench_serving():
    """Continuous-batching decode server under Poisson arrivals
    (ISSUE 6) — CPU by DESIGN like bench_hapi: the number tracks the
    HOST side of the serving loop (admission, prefill bucketing,
    page-table staging, dispatch, lazy streaming) and says nothing
    about device time.

    Reports generated tokens/s, request-latency p50/p99 and TTFT under
    a Poisson open-loop arrival process on a tiny GPT config, plus the
    compile/warmup wall-time breakdown — cold-start is a product
    metric (ROADMAP): a serving fleet redeploying under traffic pays
    it on every process, so it is recorded every round exactly like
    steps/s.  ``PADDLE_TPU_COMPILE_CACHE`` (persistent XLA cache)
    shows up directly in these numbers on a second run."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.inference.serving import LLMServer

    print("devices-ok", jax.devices(), flush=True)
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    n_requests = 8 if tiny else int(
        os.environ.get("GRAFT_BENCH_SERVING_REQUESTS", "48"))
    mean_interarrival_s = 0.004    # Poisson open loop, ~250 req/s
    max_tokens = 4 if tiny else 16

    paddle.seed(0)
    net = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
    net.eval()
    t0 = time.perf_counter()
    server = LLMServer(net, max_batch=8, block_size=8, num_blocks=256,
                       max_queue=max(64, n_requests),
                       auto_start=False)
    warm = server.warmup()          # every prefill bucket + decode
    compile_warmup_s = time.perf_counter() - t0
    server.start()

    rng = np.random.RandomState(0)
    gaps = rng.exponential(mean_interarrival_s, size=n_requests)
    lengths = rng.randint(4, 49, size=n_requests)
    futs = []
    t_start = time.perf_counter()
    for i in range(n_requests):
        time.sleep(float(gaps[i]))
        prompt = rng.randint(0, 256, size=int(lengths[i])).tolist()
        futs.append(server.submit(prompt, max_tokens=max_tokens))
    results = [f.result(timeout=300) for f in futs]
    wall = time.perf_counter() - t_start
    stats = server.stats()
    server.close()

    total_tokens = sum(len(r.tokens) for r in results)
    lats = sorted(r.stats.latency for r in results)
    ttfts = sorted(r.stats.ttft for r in results)

    def pct(sorted_vals, q):
        # exact percentile over this run's request list (PR 8 removed
        # the server's private _percentile ring when stats() re-backed
        # onto registry histograms; the bench keeps exact per-run
        # numbers from the futures it already holds)
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1,
                max(0, int(round(q / 100 * (len(sorted_vals) - 1)))))
        return float(sorted_vals[i])

    _emit_result("serving", {
        "serving_tokens_per_sec": round(total_tokens / wall, 1),
        "serving_requests_per_sec": round(n_requests / wall, 1),
        "serving_p50_latency_ms": round(pct(lats, 50) * 1e3, 1),
        "serving_p99_latency_ms": round(pct(lats, 99) * 1e3, 1),
        "serving_p50_ttft_ms": round(pct(ttfts, 50) * 1e3, 1),
        "serving_p99_ttft_ms": round(pct(ttfts, 99) * 1e3, 1),
        "serving_compile_warmup_s": round(compile_warmup_s, 2),
        "serving_decode_compile_s": warm["decode_compile_s"],
        "serving_requests": n_requests,
        "serving_max_tokens": max_tokens,
        "serving_dispatches": stats["dispatches"],
        "serving_decode_traces": stats["decode_traces"],
        "serving_kv_fragmentation": round(
            stats["kv"]["fragmentation"], 3),
    })


def bench_spec():
    """Speculative decoding (ISSUE 19) — CPU host-loop proxy.

    On a TPU deployment the decode loop is HOST-bound: the per-token
    device forward is microseconds while Python dispatch, streaming,
    and the done-poll sync cost milliseconds — speculation's whole win
    is doing that host round-trip once per k+1 tokens.  This bench
    reproduces that regime on CPU with a deliberately tiny model
    (device forward ~1 ms) and a LIVE streaming consumer that reads
    each token as it arrives (the SSE-server pattern: one lazy-stack
    materialization per dispatch) — so tokens/s tracks host
    round-trips per token, exactly what speculation collapses.

    Matrix: k in {2, 4, 8} x {self-draft (accept ~1, the headline),
    adversarial draft (sign-flipped weights, accept ~0, the floor)}
    against the non-speculative engine on the same closed-loop load.
    Every leg is steady-state (a full warm round first, so compile
    time never pollutes the ratio) and token-identical to the
    baseline by the exactness contract (tests/test_serving_spec.py).
    Reports tokens/s per request, speedup, lane-normalized
    dispatches/token (from serving_spec_dispatches_total), and the
    measured accept rate."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.inference.serving import (DecodeEngine,
                                              extract_decode_params,
                                              filter_spec_stream)

    print("devices-ok", jax.devices(), flush=True)
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    B = 4
    max_tokens = 16 if tiny else 96
    ks = (2,) if tiny else (2, 4, 8)

    paddle.seed(0)
    # host-loop proxy config: 1 layer / hidden 32 keeps the device
    # forward ~1 ms so the host round-trip dominates, as on TPU
    cfg = gpt_tiny(use_flash_attention=False, num_hidden_layers=1,
                   hidden_size=32, num_attention_heads=2,
                   intermediate_size=64)
    net = GPTForCausalLM(cfg)
    net.eval()
    params = extract_decode_params(net)
    # adversarial draft: sign-flipped weights share the geometry but
    # never agree with the target's argmax — the accept ~0 floor
    neg = jax.tree_util.tree_map(lambda a: -a, params)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 256, (12,)).tolist() for _ in range(B)]

    def mkcb(spec):
        raw = lambda rid, idx, tok: int(tok)       # live consumer
        return (filter_spec_stream(raw, max_tokens=max_tokens)
                if spec else raw)

    def warm(eng, spec):
        for p in prompts:                  # warm round: every program
            eng.submit(p, max_tokens=max_tokens, stream_cb=mkcb(spec))
        eng.run_until_idle()

    def timed(eng, spec):
        d0 = eng._dispatch_count
        futs = [eng.submit(p, max_tokens=max_tokens,
                           stream_cb=mkcb(spec)).future
                for p in prompts]
        t0 = time.perf_counter()
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        toks = sum(len(f.result(timeout=0).tokens) for f in futs)
        return wall, toks, eng._dispatch_count - d0

    # the baseline engine stays alive the whole matrix and every leg
    # re-times it back-to-back with its spec rounds (best of 3 each):
    # single-core wall noise drifts over the minutes this bench runs,
    # and pairing the rounds in time cancels it in the RATIO — an
    # up-front baseline against a late leg does not
    base_eng = DecodeEngine(net, max_batch=B, block_size=8,
                            num_blocks=256)
    warm(base_eng, False)
    out = {"spec_max_tokens": max_tokens, "spec_batch": B}
    base_best = None
    best = 0.0
    for k in ks:
        for name, dp in (("self", params), ("adv", neg)):
            eng = DecodeEngine(net, max_batch=B, block_size=8,
                               num_blocks=256, draft_params=dp,
                               spec_k=k)
            warm(eng, True)
            wb = ws = None
            for _ in range(3):
                b = timed(base_eng, False)
                s = timed(eng, True)
                if wb is None or b[0] < wb[0]:
                    wb = b
                if ws is None or s[0] < ws[0]:
                    ws = s
            if base_best is None or wb[0] < base_best[0]:
                base_best = wb
            w, t, d = ws
            sp = eng.stats()["spec"]
            speedup = (t / w) / (wb[1] / wb[0])
            # lane-normalized dispatches per committed token over the
            # timed round (the delta of serving_spec_dispatches_total
            # across it): all B lanes run the whole closed-loop round,
            # so lanes = dispatches * B
            dpt = d * B / t
            key = f"spec_k{k}_{name}"
            out[f"{key}_tokens_per_sec_per_request"] = round(
                t / w / B, 1)
            out[f"{key}_speedup"] = round(speedup, 2)
            out[f"{key}_dispatches_per_token"] = round(dpt, 3)
            out[f"{key}_accept_rate"] = round(sp["accept_rate"], 3)
            if name == "self":
                best = max(best, speedup)
    out["spec_baseline_tokens_per_sec_per_request"] = round(
        base_best[1] / base_best[0] / B, 1)
    out["spec_baseline_dispatches_per_token"] = round(
        base_best[2] * B / base_best[1], 3)
    out["spec_best_self_speedup"] = round(best, 2)
    _emit_result("spec", out)


def bench_longcontext():
    """Long-context serving tier (ISSUE 14) — CPU by design like the
    serving bench.  Three sub-rounds:

    (a) a ~32k-token prompt admitted through CHUNKED prefill and
        decoded through the fused paged-attention kernel (Pallas,
        interpret mode on this container) — the round that cannot
        exist on the gather composition's memory story: the analytic
        per-layer attention working set of gather
        (``[B, MAXNB*BS, H, Dh]`` K+V) vs the kernel's
        one-block-per-request residency is recorded as the ratio;
    (b) a shared-system-prompt request mix: prefix-cache hit rate and
        prompt tokens whose prefill was skipped outright;
    (c) chunked-prefill tail impact: p99 inter-token gap of a RUNNING
        decode stream while a long prompt admits, chunked vs
        whole-prompt — the latency cliff chunking exists to remove.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.inference.serving import DecodeEngine
    from paddle_tpu.inference.serving.paged_attention_kernel import (
        attention_working_set_bytes)

    print("devices-ok", jax.devices(), flush=True)
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    CTX = 2048 if tiny else int(
        os.environ.get("GRAFT_BENCH_LONGCONTEXT", "32768"))
    BS = 64 if tiny else 256            # KV block size
    CHUNK = 256 if tiny else 1024       # prefill admission unit
    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False, hidden_size=32,
                   num_attention_heads=2, num_hidden_layers=2,
                   intermediate_size=64,
                   max_position_embeddings=CTX + 2 * BS)
    net = GPTForCausalLM(cfg)
    net.eval()
    out = {"longcontext_context_tokens": CTX,
           "longcontext_block_size": BS,
           "longcontext_prefill_chunk": CHUNK}

    # -- (a) the 32k round: chunked admission + fused-kernel decode --
    eng = DecodeEngine(net, max_batch=2, block_size=BS,
                       num_blocks=CTX // BS + 8, prefill_chunk=CHUNK,
                       prefix_cache=True, attention="pallas")
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (CTX - BS,)).tolist()
    t0 = time.perf_counter()
    fut = eng.submit(prompt, max_tokens=4, temperature=0.8,
                     seed=1).future
    eng.run_until_idle()
    res = fut.result(timeout=0)
    wall = time.perf_counter() - t0
    st = res.stats
    h = eng._h_chunk
    ws = attention_working_set_bytes(
        eng.max_batch, eng.max_blocks_per_seq, BS,
        cfg.num_attention_heads,
        cfg.hidden_size // cfg.num_attention_heads)
    decode_s = (st.latency or 0) - (st.ttft or 0)
    out.update({
        "longcontext_attention": eng.attention_mode,
        "longcontext_round_wall_s": round(wall, 2),
        "longcontext_ttft_s": round(st.ttft or 0, 2),
        "longcontext_chunks": int(h.collect()["count"]),
        "longcontext_chunk_p50_s": round(h.quantile(0.50), 4),
        "longcontext_chunk_p99_s": round(h.quantile(0.99), 4),
        "longcontext_decode_tok_per_s": round(
            (len(res.tokens) - 1) / decode_s, 2) if decode_s else None,
        "longcontext_gather_workset_mb": round(
            ws["gather_bytes"] / 1e6, 2),
        "longcontext_kernel_workset_mb": round(
            ws["kernel_bytes"] / 1e6, 2),
        "longcontext_workset_ratio": ws["ratio"],
        "longcontext_decode_traces": eng.compile_stats()
        ["decode_traces"],
    })

    # -- (b) shared-system-prompt mix: prefix-cache hit rate --------
    eng2 = DecodeEngine(net, max_batch=4, block_size=16,
                        num_blocks=256, prefill_chunk=128,
                        prefix_cache=True)
    system = rng.randint(0, cfg.vocab_size, (512,)).tolist()
    n_req = 4 if tiny else 12
    t0 = time.perf_counter()
    futs = []
    for _ in range(n_req):
        user = rng.randint(0, cfg.vocab_size, (16,)).tolist()
        futs.append(eng2.submit(system + user, max_tokens=4).future)
        eng2.run_until_idle()
    for f in futs:
        f.result(timeout=0)
    pstats = eng2._prefix.stats()
    out.update({
        "longcontext_prefix_requests": n_req,
        "longcontext_prefix_hit_rate": round(pstats["hit_rate"], 3),
        "longcontext_prefix_tokens_skipped": int(
            pstats["hits"] * 16),
        "longcontext_prefix_wall_s": round(
            time.perf_counter() - t0, 2),
    })

    # -- (c) chunked-prefill p99 impact on a running decode ---------
    big_len = min(4096, CTX) - 64

    def gap_p99(prefill_chunk):
        e = DecodeEngine(net, max_batch=2, block_size=64,
                         num_blocks=CTX // 64 + 16,
                         prefill_chunk=prefill_chunk)
        # warm pass: compile every prefill/chunk/decode trace this
        # measurement touches — the steady-state question is dispatch
        # interleaving, not cold-start (which (a) already records)
        for warm in (False, True):
            arrivals = []
            fa = e.submit(
                rng.randint(0, cfg.vocab_size, (8,)).tolist(),
                max_tokens=48,
                stream_cb=lambda rid, i, t: arrivals.append(
                    time.monotonic())).future
            for _ in range(4):
                e.step()                  # decode stream running
            big = e.submit(rng.randint(
                0, cfg.vocab_size, (big_len,)).tolist(),
                max_tokens=2).future
            e.run_until_idle()
            fa.result(timeout=0)
            big.result(timeout=0)
        gaps = sorted(b - a for a, b in zip(arrivals, arrivals[1:]))
        return gaps[min(len(gaps) - 1,
                        int(round(0.99 * (len(gaps) - 1))))]

    out["longcontext_decode_gap_p99_ms_whole"] = round(
        gap_p99(None) * 1e3, 1)
    out["longcontext_decode_gap_p99_ms_chunked"] = round(
        gap_p99(512) * 1e3, 1)
    _emit_result("longcontext", out)


def bench_disagg():
    """Disaggregated prefill/decode serving (ISSUE 16) — CPU by
    design like the other serving benches.  Two sub-rounds:

    (a) running-decode p99 inter-token gap while a ~32k prompt is
        admitted on a SEPARATE prefill replica and handed off as a
        page migration — the number this tier exists for: chunked
        prefill (PR 14) got the single-engine gap from 1281 ms to
        88 ms; moving admission off the decode replica entirely is
        supposed to beat that (the residual jitter was exactly the
        chunks still sharing the decode dispatch queue);
    (b) a mixed long/short Poisson arrival process through the full
        disaggregated pipeline vs the same process on one
        both-phases engine — handoff overhead must not cost
        throughput.

    Both replicas live in this one process, so without isolation they
    share ONE XLA host device: every computation serializes on that
    device's execution queue, and a late 32k chunk (a multi-second
    computation here) blocks the decode step queued behind it — the
    resource coupling disaggregation removes by putting phases on
    separate chips, and exactly what this bench must not re-measure.
    The in-process stand-in is two forced host devices with each
    replica pinned to its own (``LLMServer(device=...)``) plus
    single-threaded eigen so the two devices' computations don't fight
    over cores either: one replica's chunk occupies one core while
    the decode stream keeps dispatching on the other.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
        + " --xla_cpu_multi_thread_eigen=false"
        + " intra_op_parallelism_threads=1").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.inference.serving import DisaggRouter, LLMServer

    print("devices-ok", jax.devices(), flush=True)
    tiny = bool(os.environ.get("GRAFT_BENCH_TINY"))
    CTX = 2048 if tiny else int(
        os.environ.get("GRAFT_BENCH_LONGCONTEXT", "32768"))
    BS = 64 if tiny else 256            # KV block size
    CHUNK = 256 if tiny else 1024       # prefill admission unit
    stream_cap = 4096 if tiny else 16384   # running-stream budget
    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False, hidden_size=32,
                   num_attention_heads=2, num_hidden_layers=2,
                   intermediate_size=64,
                   max_position_embeddings=max(CTX, stream_cap)
                   + 2 * BS)
    net = GPTForCausalLM(cfg)
    net.eval()
    rng = np.random.RandomState(0)
    out = {"disagg_context_tokens": CTX, "disagg_block_size": BS,
           "disagg_prefill_chunk": CHUNK}

    # -- (a) running-decode gap under a 32k admission ---------------
    # the decode pool holds the stream's WORST CASE (its reservation)
    # next to the migrated big request; the prefill pool only ever
    # needs prompt blocks (prefill-role admission envelope)
    nb_pre = CTX // BS + 24
    nb_dec = CTX // BS + stream_cap // BS + 24
    dev_pre, dev_dec = jax.devices()[0], jax.devices()[-1]
    router = DisaggRouter(
        lambda: LLMServer(net, max_batch=2, block_size=BS,
                          num_blocks=nb_pre, role="prefill",
                          prefill_chunk=CHUNK, prefix_cache=False,
                          device=dev_pre),
        lambda: LLMServer(net, max_batch=2, block_size=BS,
                          num_blocks=nb_dec, role="decode",
                          prefix_cache=False, device=dev_dec),
        prefill_pool={"decision_interval_s": 0},
        decode_pool={"decision_interval_s": 0})
    big_prompt = rng.randint(0, cfg.vocab_size, (CTX - BS,)).tolist()

    # warm + calibrate: one short request end-to-end compiles the
    # chunk/export/decode/import/join paths for SHORT shapes and
    # measures the steady-state decode gap; one full-size admission
    # compiles every context-bucket chunk trace AND the big import
    # bucket, and measures the admission wall the measured stream
    # must outlive
    arrivals = []
    router.submit(
        rng.randint(0, cfg.vocab_size, (8,)).tolist(), max_tokens=64,
        stream_cb=lambda rid, i, t: arrivals.append(time.monotonic())
    ).result(timeout=600)
    gaps = sorted(b - a for a, b in zip(arrivals, arrivals[1:]))
    gap_p50 = gaps[len(gaps) // 2]
    t0 = time.perf_counter()
    router.submit(big_prompt, max_tokens=2).result(timeout=1200)
    admit_wall = time.perf_counter() - t0

    # measured round: a running decode stream sized to outlive the
    # whole admission (1.5x margin on the calibrated walls)
    stream_tokens = int(min(stream_cap, max(
        128, 1.5 * admit_wall / max(gap_p50, 1e-4))))
    arrivals = []
    f_stream = router.submit(
        rng.randint(0, cfg.vocab_size, (8,)).tolist(),
        max_tokens=stream_tokens,
        stream_cb=lambda rid, i, t: arrivals.append(time.monotonic()))
    deadline = time.monotonic() + 300
    while len(arrivals) < 8 and time.monotonic() < deadline:
        time.sleep(0.002)
    t_admit = time.monotonic()
    big = router.submit(big_prompt, max_tokens=2)
    big.result(timeout=1200)
    t_done = time.monotonic()
    f_stream.result(timeout=1200)
    window = [t for t in arrivals if t_admit <= t <= t_done]
    wgaps = sorted(b - a for a, b in zip(window, window[1:]))
    dec_server = router.decode.replicas[0]
    dst = dec_server.engine.stats()
    out.update({
        "disagg_admit_wall_s": round(admit_wall, 2),
        "disagg_stream_tokens": stream_tokens,
        "disagg_gap_samples_in_window": len(wgaps),
        "disagg_decode_gap_p50_ms": round(
            wgaps[len(wgaps) // 2] * 1e3, 1) if wgaps else None,
        "disagg_decode_gap_p99_ms": round(
            wgaps[min(len(wgaps) - 1,
                      int(round(0.99 * (len(wgaps) - 1))))] * 1e3, 1)
        if wgaps else None,
        "disagg_page_migrations": int(
            dec_server.engine._c_migrations.collect()),
        "disagg_migrated_blocks": int(
            dec_server.engine._c_migrated_blocks.collect()),
        "disagg_migration_p50_s": round(
            dec_server.engine._h_migration.quantile(0.50), 4),
        "disagg_decode_traces": dec_server.engine.compile_stats()
        ["decode_traces"],
    })
    router.close()

    # -- (b) mixed Poisson tok/s: disaggregated vs single engine ----
    n_req = 6 if tiny else 24
    long_len = 128 if tiny else 512

    def poisson_mix(submit, seed):
        r = np.random.RandomState(seed)
        futs = []
        t0 = time.perf_counter()
        for i in range(n_req):
            L = long_len if i % 3 == 0 else 16
            p = r.randint(0, cfg.vocab_size, (L,)).tolist()
            futs.append(submit(p, max_tokens=16))
            time.sleep(float(r.exponential(0.03)))
        toks = sum(len(f.result(timeout=600).tokens) for f in futs)
        return toks / (time.perf_counter() - t0)

    mix_kw = dict(block_size=16, num_blocks=256, prefill_chunk=128,
                  prefix_cache=False)
    single = LLMServer(net, max_batch=4, **mix_kw)
    single.submit([1, 2, 3], max_tokens=4).result(timeout=600)  # warm
    single_tps = poisson_mix(single.submit, seed=7)
    single.close()
    router2 = DisaggRouter(
        lambda: LLMServer(net, max_batch=4, role="prefill", **mix_kw),
        lambda: LLMServer(net, max_batch=4, role="decode", **mix_kw),
        prefill_pool={"decision_interval_s": 0},
        decode_pool={"decision_interval_s": 0})
    router2.submit([1, 2, 3], max_tokens=4).result(timeout=600)
    disagg_tps = poisson_mix(router2.submit, seed=7)
    router2.close()
    out.update({
        "disagg_mix_requests": n_req,
        "disagg_mix_tok_per_s": round(disagg_tps, 1),
        "disagg_mix_single_tok_per_s": round(single_tps, 1),
        "disagg_mix_vs_single": round(disagg_tps / single_tps, 3)
        if single_tps else None,
    })
    _emit_result("disagg", out)


# Fleet-bench worker: two beacon-publishing ranks with per-rank step
# pace, scraped from OUTSIDE over the controller's /fleet/* plane.
# Deliberately jax-free: what this bench measures is the
# observability plane itself (scrape + merge + straggler
# attribution), not device throughput.
_FLEET_WORKER = '''
import json, os, time
import paddle_tpu  # arms the per-rank /metrics endpoint from env
from paddle_tpu.distributed.resilience.elastic_rank import (
    ElasticRankContext)
from paddle_tpu.observability import metrics, trace

ctx = ElasticRankContext.from_env()
assert ctx is not None
ctx.register()
rank = ctx.rank
sleep_s = float(os.environ["FLEET_STEP_SLEEP"].split(",")[rank])
stop_file = os.environ["FLEET_STOP_FILE"]
reg = metrics.registry()
steps = reg.counter("fit_steps_total", "committed steps")
for step in range(1, 2000):
    with trace.span("train.step", {"rank": rank}):
        time.sleep(sleep_s)
    steps.inc()
    ctx.publish_beacon(step=step)
    if os.path.exists(stop_file):
        break
ctx.exit()
print(f"FLEET-WORKER-DONE rank={rank}", flush=True)
'''


def bench_fleet():
    """The distributed observability plane, measured end to end
    (ISSUE 10): a REAL ``launch --nproc_per_node 2 --metrics_port``
    run answered entirely over HTTP from outside — per-rank /metrics
    with rank labels, the controller's /fleet/metrics merge, the
    pid-per-rank /fleet/trace, and straggler attribution of an
    artificially slowed rank 1.  The record attaches ONE merged fleet
    snapshot (the controller's /fleet/metrics.json), not per-child
    dump files — the fleet answer IS the product here."""
    import socket
    import tempfile
    import urllib.request

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    here = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_fleet_")
    script = os.path.join(work, "fleet_worker.py")
    with open(script, "w") as f:
        f.write(_FLEET_WORKER)
    stop_file = os.path.join(work, "stop")
    base = free_port()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_TRACE": "1",
        "FLEET_STEP_SLEEP": "0.05,0.25",   # rank 1 is the straggler
        "FLEET_STOP_FILE": stop_file,
        "PYTHONPATH": here + os.pathsep + env.get("PYTHONPATH", ""),
    })
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--metrics_port", str(base),
         "--job_id", "bench-fleet", "--log_dir",
         os.path.join(work, "log"), script],
        env=env, cwd=work, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def get_json(port, path, timeout=1.0):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}",
                timeout=timeout) as r:
            return json.loads(r.read().decode())

    out = {"fleet_ranks": 2}
    merged = None
    straggler = None
    deadline = time.time() + 120
    try:
        while time.time() < deadline:
            time.sleep(0.5)
            if proc.poll() is not None:
                break
            try:
                snap = get_json(base, "/fleet/metrics.json")
                ctl = get_json(base, "/metrics.json")["metrics"]
            except OSError:
                continue
            except ValueError:
                continue
            have_sum = snap.get("fit_steps_total", {}).get("value", 0)
            flag = ctl.get('fleet_straggler{rank="1"}',
                           {}).get("value")
            if have_sum and have_sum >= 20 and flag == 1.0:
                merged = snap
                straggler = ctl
                break
        if merged is not None:
            out["fleet_scrape_to_straggler_s"] = round(
                time.perf_counter() - t0, 2)
            out["fleet_fit_steps_total"] = merged[
                "fit_steps_total"]["value"]
            # per-rank /metrics answers with the rank label; a rank
            # whose endpoint failed to bind (http arming degrades,
            # never kills the worker) records False instead of
            # killing the whole record
            for r in (0, 1):
                try:
                    txt = urllib.request.urlopen(
                        f"http://127.0.0.1:{base + 1 + r}/metrics",
                        timeout=2).read().decode()
                    out[f"fleet_rank{r}_has_rank_label"] = (
                        f'rank="{r}"' in txt)
                except OSError:
                    out[f"fleet_rank{r}_has_rank_label"] = False
            try:
                trace_json = get_json(base, "/fleet/trace",
                                      timeout=10.0)
                out["fleet_trace_pids"] = sorted(
                    {e["pid"] for e in trace_json["traceEvents"]})
            except (OSError, ValueError) as e:
                out["fleet_trace_error"] = f"{type(e).__name__}: {e}"
            out["fleet_straggler_rank1_step_time_s"] = straggler[
                'fleet_rank_step_time_s{rank="1"}']["value"]
            # ONE merged fleet snapshot, not per-child dumps
            path = os.path.join(here, ".bench_obs", "fleet.json")
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump({"fleet_metrics": merged,
                           "controller_metrics": straggler}, f,
                          indent=1)
            out["obs_snapshot_fleet"] = path
        else:
            out["fleet_error"] = "plane never converged in 120s"
    finally:
        with open(stop_file, "w") as f:
            f.write("1")
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()          # reap, so returncode is real
    out["fleet_launch_rc"] = proc.returncode
    print("RESULT " + json.dumps(out), flush=True)


# Self-heal bench worker: beacon-publishing ranks with per-MEMBER
# pace (the original rank-1 member is the straggler; the spare that
# replaces it runs at fleet pace), so the bench measures the action
# loop itself — latency onset → drain verdict → promotion → fleet
# step-time recovered — with no jax compile noise in the timeline.
_SELFHEAL_WORKER = '''
import os, time
import paddle_tpu  # arms the per-rank /metrics endpoint from env
from paddle_tpu.distributed.resilience.elastic_rank import (
    ElasticRankContext)

ctx = ElasticRankContext.from_env()
assert ctx is not None
ctx.register()
if ctx.role == "spare":
    ticket = ctx.wait_for_promotion()
    if ticket is None:
        ctx.exit()
        raise SystemExit(0)
slow_member = os.environ["SELFHEAL_SLOW_MEMBER"]
pace = (float(os.environ["SELFHEAL_SLOW_S"])
        if ctx.member_id == slow_member
        else float(os.environ["SELFHEAL_FAST_S"]))
stop_file = os.environ["SELFHEAL_STOP_FILE"]
for step in range(1, 100000):
    time.sleep(pace)
    ctx.publish_beacon(step=step)
    if os.path.exists(stop_file):
        break
ctx.exit()
print(f"SELFHEAL-WORKER-DONE member={ctx.member_id}", flush=True)
'''


def bench_selfheal():
    """The observability→action loop, measured end to end (ISSUE 13):
    a REAL ``launch --spares 1 --drain_stragglers`` run where the
    original rank 1 steps 5x slower than the fleet.  The record is
    the loop's reaction time, scraped from OUTSIDE over the
    controller plane: ``selfheal_to_drain_s`` (launch → drain
    decision on /fleet/events) and ``selfheal_drain_to_recovered_s``
    (drain → the promoted successor's step-time back under the
    straggler bar on the controller registry)."""
    import socket
    import tempfile
    import urllib.request

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    here = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_selfheal_")
    script = os.path.join(work, "selfheal_worker.py")
    with open(script, "w") as f:
        f.write(_SELFHEAL_WORKER)
    stop_file = os.path.join(work, "stop")
    base = free_port()
    factor = 2.0
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SELFHEAL_FAST_S": "0.08",
        "SELFHEAL_SLOW_S": "0.4",       # 5x the fleet pace
        "SELFHEAL_SLOW_MEMBER": "rank-1",
        "SELFHEAL_STOP_FILE": stop_file,
        "PYTHONPATH": here + os.pathsep + env.get("PYTHONPATH", ""),
    })
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--spares", "1",
         "--metrics_port", str(base),
         "--straggler_factor", str(factor),
         "--drain_stragglers", "8",
         "--beacon_timeout", "30",     # only the drain may replace
         "--job_id", "bench-selfheal",
         "--log_dir", os.path.join(work, "log"), script],
        env=env, cwd=work, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def get_json(path, timeout=1.0):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{base}{path}",
                timeout=timeout) as r:
            return json.loads(r.read().decode())

    out = {"selfheal_slow_factor": 5.0,
           "selfheal_drain_windows": 8}
    t_drain = t_recovered = None
    deadline = time.time() + 120
    try:
        while time.time() < deadline and proc.poll() is None:
            time.sleep(0.25)
            try:
                if t_drain is None:
                    ev = get_json("/fleet/events")
                    if any(e.get("kind") == "drain"
                           for e in ev.get("events", [])):
                        t_drain = time.perf_counter()
                    continue
                # after the drain: recovered when the successor holds
                # a step-time estimate back under the straggler bar
                ctl = get_json("/metrics.json")["metrics"]
                st1 = ctl.get('fleet_rank_step_time_s{rank="1"}',
                              {}).get("value")
                st0 = ctl.get('fleet_rank_step_time_s{rank="0"}',
                              {}).get("value")
                flag = ctl.get('fleet_straggler{rank="1"}',
                               {}).get("value")
                if (st0 and st1 and flag == 0.0
                        and st1 < factor * st0):
                    t_recovered = time.perf_counter()
                    break
            except (OSError, ValueError):
                continue
        if t_drain is not None:
            out["selfheal_to_drain_s"] = round(t_drain - t0, 2)
        else:
            out["selfheal_error"] = "no drain decision in 120s"
        if t_recovered is not None:
            out["selfheal_drain_to_recovered_s"] = round(
                t_recovered - t_drain, 2)
            out["selfheal_total_s"] = round(t_recovered - t0, 2)
            try:
                h = get_json("/fleet/healthz")
                out["selfheal_quarantined_total"] = \
                    h["quarantined_total"]
                out["selfheal_spares_available"] = \
                    h["spares_available"]
            except (OSError, ValueError):
                pass
        elif t_drain is not None:
            out["selfheal_error"] = "drained but never recovered"
    finally:
        with open(stop_file, "w") as f:
            f.write("1")
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()          # reap, so returncode is real
    out["selfheal_launch_rc"] = proc.returncode
    print("RESULT " + json.dumps(out), flush=True)


def bench_selfheal_hosts():
    """Multi-host self-heal (`--selfheal --hosts 2`, ISSUE 18): a
    REAL `launch --nnodes 2` run over two simulated host agents on
    one KV server; SIGKILL of the WHOLE second node (agent + both its
    ranks + its spares) mid-step.  The record is the node-level
    action loop measured from outside over the controller plane:
    ``selfheal_node_death_verdict_s`` (kill → node_death on
    /fleet/events, i.e. the lease-expiry judgment) and
    ``selfheal_node_death_to_recovered_s`` (kill → batch promotion
    complete: no pending failures, every rank id alive again)."""
    import signal
    import socket
    import tempfile
    import urllib.request

    from paddle_tpu.distributed.fleet.elastic import KVClient, KVServer
    from paddle_tpu.distributed.resilience.elastic_rank import kv_key

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    here = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_selfheal_hosts_")
    script = os.path.join(work, "selfheal_worker.py")
    with open(script, "w") as f:
        f.write(_SELFHEAL_WORKER)
    stop_file = os.path.join(work, "stop")
    base = free_port()
    job = "bench-selfheal-hosts"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SELFHEAL_FAST_S": "0.08",
        "SELFHEAL_SLOW_S": "0.08",     # nobody straggles: the fault
        "SELFHEAL_SLOW_MEMBER": "-",   # here is a whole dead node
        "SELFHEAL_STOP_FILE": stop_file,
        "PYTHONPATH": here + os.pathsep + env.get("PYTHONPATH", ""),
    })
    server = KVServer().start()
    client = KVClient(server.endpoint)
    agents = [subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--agent", "--host_id", h, "--elastic_server",
         server.endpoint, "--job_id", job,
         "--log_dir", os.path.join(work, "log")],
        env=env, cwd=work, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for h in ("h0", "h1")]
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "2", "--nproc_per_node", "2", "--spares", "2",
         "--elastic_server", server.endpoint,
         "--metrics_port", str(base),
         "--beacon_timeout", "30",     # only the lease may judge
         "--job_id", job,
         "--log_dir", os.path.join(work, "log"), script],
        env=env, cwd=work, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def get_json(path, timeout=1.0):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{base}{path}",
                timeout=timeout) as r:
            return json.loads(r.read().decode())

    out = {"selfheal_hosts": 2, "selfheal_world": 4}
    t_kill = t_event = t_recovered = None
    try:
        # wait until every rank on the doomed host is actually
        # stepping (beacon moving), so the kill lands mid-step
        run_id = None
        deadline = time.time() + 90
        while time.time() < deadline and run_id is None:
            try:
                raw = client.get(kv_key(job, "run"))
                if raw:
                    run_id = json.loads(raw)["run_id"]
            except (OSError, ValueError, KeyError):
                pass
            time.sleep(0.25)
        victim_pids = []
        while time.time() < deadline:
            try:
                raw = client.get(kv_key(job, "beacon", "2",
                                        run_id=run_id))
                if raw and json.loads(raw).get("step", 0) >= 2:
                    lease = json.loads(client.get(
                        kv_key(job, "node", "h1", run_id=run_id)))
                    victim_pids = [
                        p["pid"] for p in lease["procs"].values()
                        if p.get("pid") and p.get("rc") is None]
                    break
            except (OSError, ValueError, TypeError, KeyError):
                pass
            time.sleep(0.25)
        if not victim_pids:
            out["selfheal_error"] = "node h1 never reached step 2"
        else:
            agents[1].kill()          # the agent itself…
            for pid in victim_pids:   # …and every process it held
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            t_kill = time.perf_counter()
            deadline = time.time() + 120
            while time.time() < deadline and proc.poll() is None:
                time.sleep(0.25)
                try:
                    if t_event is None:
                        ev = get_json("/fleet/events")
                        if any(e.get("kind") == "node_death"
                               for e in ev.get("events", [])):
                            t_event = time.perf_counter()
                        continue
                    h = get_json("/fleet/healthz")
                    if (h["epoch"] >= 1 and not h["pending_failures"]
                            and all(m["alive"] or m["quarantined"]
                                    for m in h["members"])
                            and sum(1 for m in h["members"]
                                    if m["alive"]) >= 4):
                        t_recovered = time.perf_counter()
                        break
                except (OSError, ValueError, KeyError):
                    continue
            if t_event is not None:
                out["selfheal_node_death_verdict_s"] = round(
                    t_event - t_kill, 2)
            else:
                out["selfheal_error"] = "no node_death verdict in 120s"
            if t_recovered is not None:
                out["selfheal_node_death_to_recovered_s"] = round(
                    t_recovered - t_kill, 2)
                try:
                    ctl = get_json("/metrics.json")["metrics"]
                    out["selfheal_promotions_total"] = ctl.get(
                        "resilience_promotions_total", {}).get("value")
                except (OSError, ValueError):
                    pass
            elif t_event is not None:
                out["selfheal_error"] = "verdict but never recovered"
    finally:
        with open(stop_file, "w") as f:
            f.write("1")
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()          # reap, so returncode is real
        for a in agents:
            if a.poll() is None:
                try:
                    a.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    a.kill()
                    a.wait()
        server.stop()
    out["selfheal_launch_rc"] = proc.returncode
    print("RESULT " + json.dumps(out), flush=True)


def bench_flash_micro():
    """Pallas flash kernel vs composed XLA attention, fwd+bwd wall time
    per call at seq 1k/4k/8k (VERDICT r2 item 5 microbench line)."""
    _maybe_force_cpu()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops

    print("devices-ok", jax.devices(), flush=True)
    b, h, d = 1, 8, 64
    out = {}
    # on CPU (dryrun) the "pallas" path falls back to the composed form:
    # keep sequences tiny so the O(S^2) bwd can't blow the budget
    seqs = (1024, 4096, 8192) if jax.default_backend() == "tpu" \
        else (256,)
    for s in seqs:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b * h, s, d).astype(np.float32)
                        ).astype(jnp.bfloat16)
        empty = jnp.zeros((0,), jnp.int32)

        def loss_pallas(q_, k_, v_):
            return pallas_ops._flash_core(
                q_, k_, v_, empty, empty, True,
                pallas_ops._attention_form(h, d, s, s) is not None
            ).astype(jnp.float32).sum()

        def loss_ref(q_, k_, v_):
            return pallas_ops._flash_reference(
                q_, k_, v_, True).astype(jnp.float32).sum()

        for tag, fn in (("pallas", loss_pallas), ("xla", loss_ref)):
            if tag == "xla" and s > 4096:
                continue   # O(S^2) composed bwd at 8k risks OOM/time

            # CHAIN the fwd+bwd calls through a data dependency inside
            # ONE jitted program and take the slope between two chain
            # lengths (dispatch and transfer cost cancel), forcing
            # completion with a host transfer.
            def chain(n, fn=fn):
                def run(q_):
                    def body(carry, _):
                        dq, _dk, _dv = jax.grad(
                            fn, argnums=(0, 1, 2))(carry, carry, carry)
                        return (carry + 1e-3 * dq.astype(carry.dtype)
                                ), None
                    c, _ = jax.lax.scan(body, q_, None, length=n)
                    return c
                j = jax.jit(run)
                r = j(q)
                _ = float(r[0, 0, 0].astype(jnp.float32))  # warm+sync
                t0 = time.perf_counter()
                r = j(q + 1e-4)
                _ = float(r[0, 0, 0].astype(jnp.float32))
                return time.perf_counter() - t0

            n_lo, n_hi = (1, 5) if s >= 4096 else (2, 12)
            per = (chain(n_hi) - chain(n_lo)) / (n_hi - n_lo)
            out[f"flash_{tag}_s{s}_ms"] = round(per * 1000, 2)
    _emit_result("flash", out)


def _parse_result(line):
    try:
        return json.loads(line[len("RESULT "):])
    except (ValueError, KeyError):   # truncated write mid-kill
        return None


def _run_child(mode: str, overall_deadline: float):
    """Run one workload in a child; return (result_dict|None, err_str)."""
    env = dict(os.environ)
    env["_GRAFT_BENCH_CHILD"] = mode
    # persistent XLA compile cache ON by default for every bench child
    # (ROADMAP cold-start item): rounds r03-r05 lost entire workloads
    # to compile deadlines; a warm cache turns repeat compiles into
    # disk loads, and the per-round compile-time metrics
    # (train_compile_s / *_compile_warmup_s) measure exactly what it
    # saves.  framework/compile_cache.py decides where it lives
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_compile_cache).
    # Opt out with PADDLE_TPU_COMPILE_CACHE=0.
    env.setdefault("PADDLE_TPU_COMPILE_CACHE", "1")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    lines = []
    lock = threading.Lock()

    def reader():
        for line in proc.stdout:
            with lock:
                lines.append(line.rstrip("\n"))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t0 = time.time()
    err = ""
    done_at = None
    while True:
        now = time.time()
        with lock:
            init_seen = any(ln.startswith("devices-ok") for ln in lines)
            done = any(ln.startswith("RESULT ") for ln in lines)
        if done and done_at is None:
            done_at = now
        if done and proc.poll() is not None:
            break
        if done_at is not None and now - done_at > 15:
            proc.kill()   # result is in hand; don't wait out a hung teardown
            break
        if not init_seen and now - t0 > INIT_DEADLINE_S:
            err = f"backend init exceeded {INIT_DEADLINE_S}s"
            proc.kill()
            break
        if now - t0 > overall_deadline:
            err = f"bench exceeded {overall_deadline:.0f}s"
            proc.kill()
            break
        if proc.poll() is not None:
            break
        time.sleep(1.0)
    proc.wait()
    t.join(timeout=5)
    result = None
    with lock:
        tail = "\n".join(lines[-15:])
        for ln in lines:
            if ln.startswith("RESULT "):
                result = _parse_result(ln)
    if result is None and not err:
        err = f"child rc={proc.returncode}; tail:\n{tail}"
    return result, err


def main():
    # `python bench.py --fold [1,8,...]`: run ONLY the hapi fold sweep
    # and print its record — the cheap CPU path for tracking the
    # steps/s trend line between full bench rounds
    if "--fold" in sys.argv:
        i = sys.argv.index("--fold")
        if i + 1 < len(sys.argv):
            os.environ["GRAFT_BENCH_HAPI_FOLDS"] = sys.argv[i + 1]
        hapi, herr = _run_child("hapi", 300)
        print(json.dumps(hapi if hapi is not None
                         else {"error": herr[-1000:]}), flush=True)
        return

    # `python bench.py --serving`: run ONLY the serving bench (CPU,
    # cheap) and print its record — the between-rounds tracker for the
    # continuous-batching path, like --fold is for the fit loop
    if "--serving" in sys.argv:
        serving, serr = _run_child("serving", 420)
        print(json.dumps(serving if serving is not None
                         else {"error": serr[-1000:]}), flush=True)
        return

    # `python bench.py --spec`: the speculative-decoding matrix only
    # (ISSUE 19; CPU host-loop proxy, cheap) — tok/s per request and
    # dispatches/token vs the non-speculative engine across
    # k x {self-draft, adversarial-draft}
    if "--spec" in sys.argv:
        spec, sperr = _run_child("spec", 420)
        print(json.dumps(spec if spec is not None
                         else {"error": sperr[-1000:]}), flush=True)
        return

    # `python bench.py --longcontext`: the long-context serving tier
    # (ISSUE 14; CPU, self-contained) — a ~32k-token round through
    # chunked prefill + the fused paged-attention kernel (interpret),
    # prefix-cache hit rate under a shared-system-prompt mix, and the
    # chunked-vs-whole prefill p99 impact on a running decode stream
    if "--longcontext" in sys.argv:
        lc, lcerr = _run_child("longcontext", 600)
        print(json.dumps(lc if lc is not None
                         else {"error": lcerr[-1000:]}), flush=True)
        return

    # `python bench.py --disagg`: the disaggregated prefill/decode
    # tier (ISSUE 16; CPU, self-contained) — running-decode p99
    # inter-token gap while a 32k prompt admits on a SEPARATE prefill
    # replica (vs 88 ms chunked single-engine from PR 14), plus mixed
    # Poisson tok/s through the handoff pipeline vs one engine
    if "--disagg" in sys.argv:
        dg, dgerr = _run_child("disagg", 900)
        print(json.dumps(dg if dg is not None
                         else {"error": dgerr[-1000:]}), flush=True)
        return

    # `python bench.py --fleet`: the distributed observability plane
    # e2e (CPU, cheap) — a real 2-rank launch answered over HTTP:
    # per-rank /metrics, /fleet merge, straggler attribution, ONE
    # merged fleet snapshot attached to the record
    if "--fleet" in sys.argv:
        fleet, flerr = _run_child("fleet", 240)
        print(json.dumps(fleet if fleet is not None
                         else {"error": flerr[-1000:]}), flush=True)
        return

    # `python bench.py --selfheal`: the observability ACTION loop e2e
    # (ISSUE 13; CPU, cheap) — a real 2-rank + spare launch with
    # --drain_stragglers armed and rank 1 stepping 5x slow; records
    # time-from-latency-to-drain and drain-to-recovered-step-time.
    # `--selfheal --hosts 2` (ISSUE 18) runs the multi-host variant:
    # two host agents, whole-node SIGKILL, node-death-to-recovered
    if "--selfheal" in sys.argv:
        hosts = 1
        if "--hosts" in sys.argv:
            i = sys.argv.index("--hosts")
            if i + 1 < len(sys.argv):
                hosts = int(sys.argv[i + 1])
        if hosts >= 2:
            sh, sherr = _run_child("selfheal_hosts", 360)
        else:
            sh, sherr = _run_child("selfheal", 240)
        print(json.dumps(sh if sh is not None
                         else {"error": sherr[-1000:]}), flush=True)
        return

    # `python bench.py --mesh-fold [1,8,...]`: run ONLY the mesh fold
    # sweep (CPU dp mesh, cheap) — the multichip counterpart of --fold
    if "--mesh-fold" in sys.argv:
        i = sys.argv.index("--mesh-fold")
        if i + 1 < len(sys.argv):
            os.environ["GRAFT_BENCH_MESH_FOLDS"] = sys.argv[i + 1]
        mf, merr = _run_child("mesh_fold", 420)
        print(json.dumps(mf if mf is not None
                         else {"error": merr[-1000:]}), flush=True)
        return

    # `python bench.py --pp-fold [1,8,...]`: run ONLY the pipeline
    # fold sweep (CPU pp=2 mesh, cheap) — the pipeline-schedule
    # counterpart of --mesh-fold (ISSUE 15): legacy vs unified fold
    # curve with host-dispatch counts per batch on the record
    if "--pp-fold" in sys.argv:
        i = sys.argv.index("--pp-fold")
        if i + 1 < len(sys.argv):
            os.environ["GRAFT_BENCH_PP_FOLDS"] = sys.argv[i + 1]
        pf, perr = _run_child("pp_fold", 420)
        print(json.dumps(pf if pf is not None
                         else {"error": perr[-1000:]}), flush=True)
        return

    # `python bench.py --dp-compressed`: run ONLY the compressed +
    # sharded dp sweep (CPU dp mesh, cheap) — the dp gradient-path
    # counterpart of --mesh-fold (ISSUE 11)
    if "--dp-compressed" in sys.argv:
        dpc, derr = _run_child("dp_compressed", 420)
        print(json.dumps(dpc if dpc is not None
                         else {"error": derr[-1000:]}), flush=True)
        return

    mode = os.environ.get("_GRAFT_BENCH_CHILD")
    if mode == "gpt":
        return bench_gpt()
    if mode == "resnet":
        return bench_resnet()
    if mode == "ernie":
        return bench_ernie()
    if mode == "flash":
        return bench_flash_micro()
    if mode == "detector":
        return bench_detector()
    if mode == "vit":
        return bench_vit()
    if mode == "hapi":
        return bench_hapi()
    if mode == "mesh_fold":
        return bench_mesh_fold()
    if mode == "pp_fold":
        return bench_pp_fold()
    if mode == "dp_compressed":
        return bench_dp_compressed()
    if mode == "serving":
        return bench_serving()
    if mode == "spec":
        return bench_spec()
    if mode == "longcontext":
        return bench_longcontext()
    if mode == "disagg":
        return bench_disagg()
    if mode == "fleet":
        return bench_fleet()
    if mode == "selfheal":
        return bench_selfheal()
    if mode == "selfheal_hosts":
        return bench_selfheal_hosts()

    t_start = time.time()

    def remaining():
        return GLOBAL_DEADLINE_S - (time.time() - t_start)

    out = {"metric": "gpt2_small_bf16_train_tokens_per_sec_1chip",
           "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0}
    gpt, err = _run_child("gpt", min(GPT_DEADLINE_S, remaining()))
    if gpt is None and time.time() - t_start < RETRY_ONLY_BEFORE_S:
        # early failure (init-class) — one retry within the global budget
        gpt, err2 = _run_child("gpt", min(GPT_DEADLINE_S, remaining()))
        if gpt is None:
            err = f"attempt1: {err}; attempt2: {err2}"
    if gpt is not None:
        tps = gpt.get("tokens_per_sec", 0.0)
        out["value"] = round(tps, 1)
        out["vs_baseline"] = round(tps / BASELINE_TOKENS_PER_SEC, 3)
        for k in gpt:
            if k != "tokens_per_sec" and (
                    k.startswith("tokens_per_sec_") or k in
                    ("step_ms", "mfu", "model_tflops_per_sec",
                     "flops_per_token_m", "pipeline_overlap_ratio",
                     "train_compile_s")):
                out["gpt_" + k] = gpt[k]
    else:
        # no result is not a result of 0.0
        out["value"] = out["vs_baseline"] = None
        out["error"] = err[-2000:]

    # hapi fit loop-overhead microbench: CPU-only by design and cheap
    # (~30s), so it records even when every TPU workload fails — the
    # perf trajectory of the Model.fit hot path stays measurable
    # without a chip (ISSUE 4 satellite)
    if remaining() > 60 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        hapi, herr = _run_child("hapi", min(240, remaining()))
        if hapi is not None:
            # the fold sweep's whole record rides along (fold=1 is the
            # PR-4 regression guard, foldK the step-folding trend line)
            out.update(hapi)
        else:
            out["hapi_fit_error"] = herr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["hapi_fit_error"] = "skipped: out of budget"

    # mesh fold sweep: the multichip half of the unified dispatch
    # engine (CPU dp mesh, cheap) — folded mesh steps/s records every
    # round next to the single-chip sweep
    if remaining() > 60 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        mf, mferr = _run_child("mesh_fold", min(240, remaining()))
        if mf is not None:
            out.update(mf)
        else:
            out["mesh_fold_error"] = mferr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["mesh_fold_error"] = "skipped: out of budget"

    # pipeline fold sweep (CPU pp=2 mesh, cheap): legacy vs unified
    # fold curve + host-dispatch counts per batch — the pipeline
    # engine's trend line records every round (ISSUE 15)
    if remaining() > 60 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        pf, pferr = _run_child("pp_fold", min(240, remaining()))
        if pf is not None:
            out.update(pf)
        else:
            out["pp_fold_error"] = pferr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["pp_fold_error"] = "skipped: out of budget"

    # compressed + sharded dp sweep (CPU dp mesh, cheap): wire-format
    # x update-sharding matrix with bytes proxy + opt-state memory —
    # the dp gradient path's trend line records every round (ISSUE 11)
    if remaining() > 60 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        dpc, dperr = _run_child("dp_compressed", min(240, remaining()))
        if dpc is not None:
            out.update(dpc)
        else:
            out["dp_compressed_error"] = dperr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["dp_compressed_error"] = "skipped: out of budget"

    # fleet observability plane e2e (CPU, cheap): a 2-rank launch
    # answered over HTTP — merged fleet snapshot + straggler
    # attribution recorded every round (ISSUE 10)
    if remaining() > 60 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        fleet, flerr = _run_child("fleet", min(240, remaining()))
        if fleet is not None:
            out.update(fleet)
        else:
            out["fleet_error"] = flerr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["fleet_error"] = "skipped: out of budget"

    # serving loop bench: CPU-only by design and cheap, so the
    # continuous-batching path (tokens/s, p99 latency, compile/warmup
    # cold-start) records every round even without a chip
    if remaining() > 90 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        serving, serr = _run_child("serving", min(300, remaining()))
        if serving is not None:
            out.update(serving)
        else:
            out["serving_error"] = serr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["serving_error"] = "skipped: out of budget"

    # speculative decoding tier (CPU, self-contained): tok/s per
    # request and dispatches/token vs the non-speculative engine for
    # k x {self, adversarial} drafts record every round (ISSUE 19)
    if remaining() > 120 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        sp, sperr = _run_child("spec", min(300, remaining()))
        if sp is not None:
            out.update(sp)
        else:
            out["spec_error"] = sperr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["spec_error"] = "skipped: out of budget"

    # long-context serving tier (CPU, self-contained): the 32k-round
    # memory story (kernel vs gather working set), prefix-cache hit
    # rate, and chunked-prefill p99 impact record every round
    if remaining() > 300 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        lc, lcerr = _run_child("longcontext", min(600, remaining()))
        if lc is not None:
            out.update(lc)
        else:
            out["longcontext_error"] = lcerr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["longcontext_error"] = "skipped: out of budget"

    # disaggregated serving tier (CPU, self-contained): running-decode
    # p99 gap under a 32k admission on a separate prefill replica +
    # mixed-Poisson tok/s vs a single engine record every round
    if remaining() > 300 and not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        dg, dgerr = _run_child("disagg", min(900, remaining()))
        if dg is not None:
            out.update(dg)
        else:
            out["disagg_error"] = dgerr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["disagg_error"] = "skipped: out of budget"

    # ResNet-50 gets its slot whenever budget remains — even after a
    # GPT failure (VERDICT r3: images/s never landed in 3 rounds)
    if (remaining() > 120
            and not os.environ.get("GRAFT_BENCH_GPT_ONLY")):
        resnet, rerr = _run_child("resnet", remaining())
        if resnet is not None:
            ips = resnet.get("images_per_sec", 0.0)
            out["resnet50_images_per_sec"] = round(ips, 1)
            out["resnet50_vs_baseline"] = round(
                ips / BASELINE_RESNET50_IMG_PER_SEC, 3)
            for k in ("step_ms", "mfu"):
                if k in resnet:
                    out["resnet50_" + k] = resnet[k]
        else:
            out["resnet50_error"] = rerr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["resnet50_error"] = "skipped: out of budget"
    # ERNIE-3.0 MLM pretrain (north-star names both metrics)
    if (remaining() > 150
            and not os.environ.get("GRAFT_BENCH_GPT_ONLY")):
        ernie, eerr = _run_child("ernie", remaining() - 60)
        if ernie is not None:
            out["ernie3_base_tokens_per_sec"] = round(
                ernie.get("tokens_per_sec", 0.0), 1)
            out["ernie3_base_step_ms"] = ernie.get("step_ms")
        else:
            out["ernie3_base_error"] = eerr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["ernie3_base_error"] = "skipped: out of budget"
    # PP-YOLOE detector (config 5, dynamic-shape buckets) — guarded
    # slot: only when the primary metrics are already in the record
    if (remaining() > 150
            and not os.environ.get("GRAFT_BENCH_GPT_ONLY")):
        det, derr = _run_child("detector", remaining() - 60)
        if det is not None:
            out["ppyoloe_s_images_per_sec"] = round(
                det.get("images_per_sec", 0.0), 1)
            out["ppyoloe_s_step_ms"] = det.get("step_ms")
            out["ppyoloe_s_buckets"] = det.get("buckets")
        else:
            out["ppyoloe_s_error"] = derr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["ppyoloe_s_error"] = "skipped: out of budget"
    if (gpt is not None and remaining() > 90
            and not os.environ.get("GRAFT_BENCH_GPT_ONLY")):
        flash, ferr = _run_child("flash", remaining())
        if flash is not None:
            out.update(flash)
        else:
            out["flash_microbench_error"] = ferr[-500:]
    elif not os.environ.get("GRAFT_BENCH_GPT_ONLY"):
        out["flash_microbench_skipped"] = (
            "gpt bench failed" if gpt is None else "out of budget")
    print(json.dumps(out), flush=True)
    if gpt is None:
        # the primary workload gave no result: the record above says
        # why, and the exit code says so too — "value": 0.0 with exit
        # code 0 reads as a measurement
        sys.exit(1)


if __name__ == "__main__":
    main()
