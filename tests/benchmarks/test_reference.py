"""The family's plain float32 reference against the program, at a toy
size on the CPU, with flash attention in the Pallas interpreter: they
agree inside the tolerances the driver writes down, and stop agreeing
when the mathematics is not the same.
"""

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import train_lm                     # noqa: E402
from benchmarks.families import gpt                         # noqa: E402
from benchmarks.harness import cells                        # noqa: E402

SEQ = 128        # the interpreter takes the kernels from s128 up


class Collect(train_lm.Checks):
    def __init__(self):
        super().__init__(say=lambda msg: None)
        self.passed = []

    def __call__(self, ok, what):
        super().__call__(ok, what)
        if ok:
            self.passed.append(what)


@pytest.fixture
def toy(monkeypatch):
    """(runner, config): the rehearsal's toy gpt2-medium, bf16 O2, after
    one training step, so that no parameter is at a special value."""
    import jax
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cell = cells.load_cell("gpt2m-pretrain-s1024", ROOT)
    config = cells.sized(cell.config, rehearse=True)
    runner = train_lm.build_runner(config, seed=11, devices=jax.devices()[:1])
    ids = np.random.default_rng(0).integers(
        0, config["vocab_size"], (2, SEQ), dtype=np.int64)
    float(runner.train_step([ids], [np.roll(ids, -1, axis=1)]))
    return runner, config


@pytest.mark.parametrize("parts", [8, 3])     # 3: the last part is shorter
def test_reference_forward_agrees_with_the_program(toy, monkeypatch, parts):
    runner, config = toy
    monkeypatch.setattr(train_lm, "VOCAB_PARTS", parts)
    check = Collect()
    train_lm.check_logits(check, runner, gpt, config, SEQ, seed=5)
    assert not check.failed and len(check.passed) == 1


@pytest.mark.parametrize("broken", ["gpt.layers.1.attn.out_proj.weight",
                                    "gpt.layers.0.mlp.fc2.weight"])
def test_reference_disagrees_when_a_projection_is_zeroed(toy, broken):
    runner, config = toy

    def reference_hidden(param, config_, ids):
        def wrong(name, rows=None):
            value = param(name, rows)
            return value * 0 if name == broken else value
        return gpt.reference_hidden(wrong, config_, ids)

    check = Collect()
    family = types.SimpleNamespace(reference_hidden=reference_hidden,
                                   reference_logits=gpt.reference_logits,
                                   EMBEDDING=gpt.EMBEDDING)
    train_lm.check_logits(check, runner, family, config, SEQ, seed=5)
    assert len(check.failed) == 1 and not check.passed


def test_kernels_agree_with_plain_attention(toy):
    runner, config = toy
    check = Collect()
    train_lm.check_kernels(check, runner, gpt, config, batch=2, seq_len=SEQ,
                           seed=5)
    assert not check.failed and len(check.passed) == 4   # out, dq, dk, dv


def test_kernel_check_catches_a_kernel_without_its_mask(toy, monkeypatch):
    from paddle_tpu.ops import pallas_ops
    runner, config = toy
    real = pallas_ops.flash_attention.raw
    monkeypatch.setattr(
        pallas_ops.flash_attention, "raw",
        lambda q, k, v, causal: real(q, k, v, causal=False))
    check = Collect()
    train_lm.check_kernels(check, runner, gpt, config, batch=2, seq_len=SEQ,
                           seed=5)
    assert check.failed


def test_plain_attention_is_causal_and_normalised():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((16, 2, 8)), jnp.float32)
               for _ in range(3))
    out = gpt.causal_attention(q, k, v)
    # position 0 sees only itself
    np.testing.assert_allclose(out[0], v[0], rtol=1e-5, atol=1e-6)
    # changing a later key or value leaves earlier positions alone
    out2 = gpt.causal_attention(q, k.at[9].set(5.0), v.at[9].set(-3.0))
    np.testing.assert_allclose(out2[:9], out[:9], rtol=1e-6)
    assert not np.allclose(out2[9:], out[9:])
    # against the definition, one head and one position by hand
    scores = (q[5, 1] @ k[:6, 1].T) / np.sqrt(8.0)
    probs = np.exp(scores - scores.max())
    probs /= probs.sum()
    np.testing.assert_allclose(out[5, 1], probs @ v[:6, 1], rtol=1e-5,
                               atol=1e-6)


def test_loss_criterion():
    import math
    check = Collect()
    falling = [math.log(1024) + 0.05 - 0.002 * i for i in range(40)]
    train_lm.check_losses(check, falling, 1024)
    assert not check.failed
    for bad in (falling[::-1],                      # rises
                [v + 1.0 for v in falling],         # starts far from uniform
                falling[:20] + [float("nan")] + falling[20:],
                []):
        check = Collect()
        train_lm.check_losses(check, bad, 1024)
        assert check.failed, bad[:3]
