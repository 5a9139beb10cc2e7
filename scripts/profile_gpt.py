"""Decompose the GPT-2-small bench step time on one chip.

Every measurement here is N chained train steps (params thread
through) followed by float(loss), which waits for the device — the
bench.py methodology.  It has never run on a chip (ROADMAP S3).

Decomposition by config deltas:
  - layers 12 vs 6          -> per-decoder-layer cost
  - flash on vs off         -> attention kernel contribution
  - AdamW vs SGD            -> optimizer update cost
  - full vs tiny vocab head -> lm-head + loss contribution
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def step_time(cfg_kw, opt_name="adamw", steps=12, batch=8, seq=1024):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    paddle.seed(0)
    base = dict(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                num_attention_heads=12, intermediate_size=3072,
                max_position_embeddings=1024, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0, use_flash_attention=True)
    base.update(cfg_kw)
    cfg = GPTConfig(**base)
    net = GPTForCausalLM(cfg)
    if opt_name == "adamw":
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=net.parameters(),
                              multi_precision=True)
    else:
        opt = optimizer.SGD(learning_rate=1e-4,
                            parameters=net.parameters())
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    y = np.roll(x, -1, axis=1)
    mesh = collective.build_mesh({})
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, GPTPretrainingCriterion(),
                               mesh=mesh)
    xs = [Tensor(jax.device_put(x))]
    ys = [Tensor(jax.device_put(y))]
    float(runner.train_step(xs, ys))   # compile
    float(runner.train_step(xs, ys))   # warmup (pipe prime)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = runner.train_step(xs, ys)
    float(loss)
    return (time.perf_counter() - t0) / steps * 1000.0


def main():
    import subprocess, sys, os, json
    # run each config in a separate process (one backend init each, and
    # isolates any compile-cache contention)
    if len(sys.argv) > 1:
        spec = json.loads(sys.argv[1])
        print("MS", step_time(spec["cfg"], spec.get("opt", "adamw")),
              flush=True)
        return
    cases = [
        ("baseline L12 flash adamw", {"cfg": {}}),
        ("L6", {"cfg": {"num_hidden_layers": 6}}),
        ("L12 no-flash(sdpa)", {"cfg": {"use_flash_attention": False}}),
        ("L12 sgd", {"cfg": {}, "opt": "sgd"}),
        ("L12 vocab 4k", {"cfg": {"vocab_size": 4096}}),
    ]
    results = {}
    for name, spec in cases:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), json.dumps(spec)],
            capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        ms = None
        for ln in p.stdout.splitlines():
            if ln.startswith("MS "):
                ms = float(ln.split()[1])
        results[name] = ms
        print(f"{name:28s} {ms if ms else -1:8.2f} ms/step", flush=True)
        if ms is None:
            print(p.stdout[-1500:], p.stderr[-1500:])
    if results.get("baseline L12 flash adamw") and results.get("L6"):
        per_layer = (results["baseline L12 flash adamw"]
                     - results["L6"]) / 6.0
        print(f"per-decoder-layer (fwd+bwd): {per_layer:.2f} ms")


if __name__ == "__main__":
    main()
