"""Granite 4.0-H against its plain reference
(``benchmarks/families/granite_hybrid.py``, the recurrence a position at
a time) at a small size on the CPU, and what its pieces promise:
recomputing a layer changes nothing, a share of the tied matrix gives
its columns of the whole model's logits, the scale left for q is exact,
and the model trains through ``DistributedRunner`` under bf16 O2.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu.nn import functional_call as F                # noqa: E402
from paddle_tpu.models import (                               # noqa: E402
    GraniteHybridConfig, GraniteHybridForCausalLM,
    GraniteHybridPretrainingCriterion, granite_hybrid_tiny)
from paddle_tpu.models import granite_hybrid                  # noqa: E402
from benchmarks.families import granite_hybrid as family      # noqa: E402

VOCAB, SEQ, BATCH = 64, 64, 2


def family_config(c):
    """The program's config under the published keys."""
    out = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    out["vocab_size"] = c.vocab_rows_held
    out["layer_types"] = list(c.layer_types)
    return out


def seeded(config, seed=11):
    """A model with seeded weights away from their symmetric start: no
    norm is the identity."""
    paddle.seed(seed)
    net = GraniteHybridForCausalLM(config)
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    return net


@pytest.fixture(scope="module")
def tiny():
    config = granite_hybrid_tiny(vocab_rows_held=VOCAB)
    ids = np.random.default_rng(6).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    return seeded(config), config, ids, np.roll(ids, -1, axis=1)


def program_loss(net, params, ids, labels):
    out, _ = F.functional_call(net, params, F.buffer_dict(net),
                               (paddle.to_tensor(ids),))
    logp = jax.nn.log_softmax(out._value.astype(jnp.float32), -1)
    loss = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                -1).mean()
    return loss, out._value


def test_logits_loss_and_every_gradient_agree_with_the_reference(tiny):
    net, config, ids, labels = tiny
    params = F.param_dict(net)
    cfg = family_config(config)
    assert family.param_count(cfg) == sum(
        int(np.prod(p.shape)) for p in net.parameters())
    kinds = [n.split(".")[3] for n in params if n.endswith("_proj.weight")]
    assert kinds.count("mamba") == 4 and kinds.count("self_attn") == 4

    (loss, logits), got = jax.value_and_grad(
        lambda p: program_loss(net, p, ids, labels), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(lambda p: family.reference_loss(
        p, cfg, jnp.asarray(ids), jnp.asarray(labels)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)

    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    for b in range(BATCH):
        hidden = family.reference_hidden(param, cfg, jnp.asarray(ids[b]))
        np.testing.assert_allclose(
            logits[b], family.reference_logits(
                hidden, params[family.EMBEDDING], cfg),
            rtol=2e-4, atol=2e-5)
    assert set(got) == set(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_the_chunk_changes_nothing(tiny, chunk):
    """``mamba_chunk_size`` is how the scan is computed, not what: the
    loss at chunks of 8, 32 and the whole sequence is the loss at 16."""
    net, config, ids, labels = tiny
    other = GraniteHybridForCausalLM(
        dataclasses.replace(config, mamba_chunk_size=chunk))
    params = F.param_dict(net)
    want, _ = program_loss(net, params, ids, labels)
    got, _ = program_loss(other, params, ids, labels)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_recompute_gives_the_same_loss_and_gradients(tiny):
    net, config, ids, labels = tiny
    again = GraniteHybridForCausalLM(
        dataclasses.replace(config, recompute=True))
    assert again.training
    params = F.param_dict(net)

    def both(model):
        return jax.value_and_grad(
            lambda p: program_loss(model, p, ids, labels)[0])(params)

    (loss, grads), (want_loss, want) = both(again), both(net)
    assert float(loss) == float(want_loss)
    for name in want:
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)

    # the recomputed step holds a checkpoint a layer, the plain one none
    def layers_recomputed(model):
        jaxpr = jax.make_jaxpr(
            lambda p: program_loss(model, p, ids, labels)[0])(params)
        # a layer's checkpoint holds its products; the final RMSNorm's
        # own holds none
        return sum(eqn.primitive.name == "remat2"
                   and "dot_general" in str(eqn) for eqn in jaxpr.jaxpr.eqns)

    from paddle_tpu.observability import metrics
    assert layers_recomputed(again) == config.num_hidden_layers
    assert metrics.registry().gauge("recompute_layers").collect() == 3
    assert layers_recomputed(net) == 0
    assert metrics.registry().gauge("recompute_layers").collect() == 0
    again.eval()        # an evaluation recomputes nothing
    program_loss(again, params, ids, labels)
    assert metrics.registry().gauge("recompute_layers").collect() == 0


def test_a_share_of_the_rows_gives_its_columns_of_the_whole_logits():
    """The model of one rank holds rows 0 .. V/8 of the whole model's
    tied matrix; on ids from those rows its logits are the first V/8
    columns of the whole reference's."""
    whole = granite_hybrid_tiny(vocab_size=512)
    share = granite_hybrid_tiny(vocab_size=512, vocab_rows_held=64)
    big = seeded(whole)
    small = GraniteHybridForCausalLM(share)
    params = dict(F.param_dict(big))
    held = dict(params)
    held[family.EMBEDDING] = params[family.EMBEDDING][:64]
    assert F.param_dict(small)[family.EMBEDDING].shape == (64, 64)
    ids = np.random.default_rng(7).integers(0, 64, (1, SEQ), dtype=np.int64)
    got, _ = F.functional_call(small, held, F.buffer_dict(small),
                               (paddle.to_tensor(ids),))

    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    cfg = family_config(whole)
    hidden = family.reference_hidden(param, cfg, jnp.asarray(ids[0]))
    want = family.reference_logits(hidden, params[family.EMBEDDING], cfg)
    assert want.shape == (SEQ, 512)
    np.testing.assert_allclose(got._value[0], want[:, :64], rtol=2e-4,
                               atol=2e-5)


def test_an_eighth_for_q_is_the_published_scale_bit_for_bit_in_bf16():
    """The kernels scale by 1 / sqrt(64); multiplying q by 1/8 first is
    exact in bf16, so the scores are those of ``attention_multiplier``
    1/64 to the bit."""
    c = GraniteHybridConfig(num_hidden_layers=1, layer_types=("attention",))
    left = c.attention_multiplier * np.sqrt(c.head_dim)
    assert (c.head_dim, c.attention_multiplier, left) == (64, 1 / 64, 1 / 8)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((256, 64)) * 3, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((256, 64)) * 3, jnp.bfloat16)
    scaled = (q * left).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(scaled.astype(jnp.float32)),
        np.asarray(q.astype(jnp.float32)) / 8)
    dot = lambda a, b: jnp.einsum("qd,kd->qk", a, b,       # noqa: E731
                                  preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dot(scaled, k) * (1 / 8)),
        np.asarray(dot(q, k) * c.attention_multiplier))


def test_the_mixers_start_as_mamba2_starts(tiny):
    net, config, _, _ = tiny
    paddle.seed(3)
    mixer = granite_hybrid.GraniteDecoderLayer(
        granite_hybrid_tiny(mamba_n_heads=64, mamba_d_head=2,
                            hidden_size=64), 0).mamba
    steps = np.asarray(jax.nn.softplus(mixer.dt_bias._value))
    assert 0.001 <= steps.min() and steps.max() <= 0.1 + 1e-6
    assert steps.max() / steps.min() > 10        # spread over the decades
    a = np.exp(np.asarray(mixer.A_log._value))
    assert 1.0 <= a.min() and a.max() <= 16.0
    np.testing.assert_array_equal(np.asarray(mixer.D._value), 1.0)
    assert mixer.conv1d.weight.shape == [config.d_inner + 2 * 16, 4]
    with pytest.raises(ValueError, match="names a mixer"):
        granite_hybrid_tiny(layer_types=("mamba", "attention"))
    with pytest.raises(ValueError, match="mamba_expand"):
        granite_hybrid_tiny(mamba_n_heads=3)
    published = GraniteHybridConfig()
    assert [i for i, kind in enumerate(published.layer_types)
            if kind == "attention"] == [5, 15, 25, 35]
    assert (published.d_inner, published.conv_dim) == (4096, 4352)


def test_kernel_widths_give_the_xla_forms_loss_and_gradients(monkeypatch):
    """At widths ``ssm.scan_form`` gives to the Mosaic kernels (two
    heads of 64, state 128, chunks of 128; interpreted here), every layer
    recomputed: the loss and every gradient are the XLA form's."""
    from paddle_tpu.ops import ssm
    config = granite_hybrid_tiny(
        vocab_rows_held=VOCAB, mamba_n_heads=2, mamba_d_head=64,
        mamba_d_state=128, mamba_chunk_size=128, recompute=True)
    seq = 256
    shape = (seq, 2, 64, 1, 128, 128)
    ids = np.random.default_rng(8).integers(0, VOCAB, (1, seq),
                                            dtype=np.int64)
    net = seeded(config)
    params = F.param_dict(net)

    def loss_and_grads():
        return jax.value_and_grad(lambda p: program_loss(
            net, p, ids, np.roll(ids, -1, axis=1))[0])(params)

    assert ssm.scan_form(*shape) == "xla"
    want_loss, want = loss_and_grads()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.scan_form(*shape) == "kernels"
    before = _kernel_visits()
    loss, grads = loss_and_grads()
    # two Mamba layers x 2 chunks: forward and the forward again, back
    assert _kernel_visits() - before == (2 * 2 * 2) + (2 * 2)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-3,
                                   atol=1e-4 * scale, err_msg=name)


def _kernel_visits():
    from paddle_tpu.observability import metrics
    return sum(metrics.registry().counter(
        "ssm_scan_kernel_visits_total", labels={"kind": kind}).collect()
        for kind in ("fwd", "bwd"))


def test_the_scans_are_counted_as_they_are_traced(tiny):
    net, config, ids, labels = tiny
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    read = lambda i: reg.counter(                           # noqa: E731
        "ssm_scan_chunks_total", labels={"layer": str(i)}).collect()
    before = [read(i) for i in range(3)]
    jax.eval_shape(lambda p: program_loss(net, p, ids, labels)[0],
                   F.param_dict(net))
    grew = [read(i) - b for i, b in zip(range(3), before)]
    # two sequences x 4 chunks of 16 x 8 heads; the attention layer none
    assert grew == [BATCH * (SEQ // 16) * 8, 0, BATCH * (SEQ // 16) * 8]
    assert reg.gauge("ssm_scan_state_bytes",
                     labels={"layer": "2"}).collect() == 4 * 8 * 16 * 16 * 4


def test_it_trains_through_the_runner_under_bf16_o2_with_recompute():
    """The way a user's script does it, as the benchmark's driver does:
    seed -> model -> AdamW -> amp.decorate O2 -> mesh -> runner, every
    layer recomputed; the loss falls on a batch seen again and again."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(21)
    net = GraniteHybridForCausalLM(granite_hybrid_tiny(
        vocab_rows_held=VOCAB, recompute=True))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, GraniteHybridPretrainingCriterion(),
                               mesh=mesh)
    ids = np.random.default_rng(9).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    labels = np.roll(ids, -1, axis=1)
    losses = [float(runner.train_step([ids], [labels])) for _ in range(8)]
    assert all(np.isfinite(losses))
    assert abs(losses[0] - np.log(VOCAB)) < 0.5
    assert losses[-1] < losses[0] - 0.02
    assert net.model.embed_tokens.weight._value.dtype == jnp.bfloat16
    logits = runner.predict_step([ids])._value
    assert logits.shape == (BATCH, SEQ, VOCAB)
    assert logits.dtype == jnp.bfloat16
