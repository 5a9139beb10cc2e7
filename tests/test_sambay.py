"""SambaY (``models/sambay.py``) against its plain reference
(``benchmarks/families/sambay.py``: the recurrence a position at a time,
attention a block of queries at a time, each of a pair's maps once) at a
small size on the CPU, and what its pieces promise: the layout rule at
the published widths counts the published parameters, the handed-on
memory, keys and values carry gradients back from every reader across
``fleet.recompute``, recomputing changes nothing, and the model trains
through ``DistributedRunner`` under bf16 O2.
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
    SambaYConfig, SambaYForCausalLM, SambaYPretrainingCriterion,
    sambay_tiny)
from paddle_tpu.models import sambay                          # noqa: E402
from paddle_tpu.ops import ssm                                # noqa: E402
from benchmarks.families import sambay as family              # noqa: E402

VOCAB, SEQ, BATCH = 64, 64, 2


def family_config(c):
    """The program's config under the published keys."""
    return {"hidden_size": c.hidden_size,
            "intermediate_size": c.intermediate_size,
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "num_hidden_layers": c.num_hidden_layers,
            "layers": {"n_self": c.n_self, "n_cross": c.n_cross},
            "mb_per_layer": c.mb_per_layer,
            "sliding_window": c.sliding_window,
            "layer_norm_eps": c.layer_norm_eps,
            "mamba_d_state": c.mamba_d_state, "mamba_d_conv": c.mamba_d_conv,
            "mamba_expand": c.mamba_expand, "mamba_dt_rank": c.mamba_dt_rank,
            "vocab_size": c.vocab_rows_held}


def seeded(config, seed=11):
    """A model with seeded weights away from their symmetric start: no
    norm is the identity and no bias is nothing."""
    paddle.seed(seed)
    net = SambaYForCausalLM(config)
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name or name.endswith(("bias", "subln", "D")):
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    return net


@pytest.fixture(scope="module")
def tiny():
    config = sambay_tiny(vocab_rows_held=VOCAB)
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
    assert config.kinds == family.kinds(cfg) == (
        "mamba", "swa", "mamba_memory", "full_kv", "gmu", "cross")
    assert family.param_count(cfg) == sum(
        int(np.prod(p.shape)) for p in net.parameters())

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
                hidden, params[family.EMBEDDING]), rtol=2e-4, atol=2e-5)
    assert set(got) == set(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


def test_the_producers_collect_from_their_readers(tiny):
    """The gradients by the memory's layer and the K/V layer's parameters
    as ``reference_producer_grads`` makes them (the check the cell runs
    on the chip) are the whole model's; with the readers cut off (their
    out-projections at nothing) the producers' own scan and K/V
    parameters still feel the loss through their own layer only."""
    net, config, ids, labels = tiny
    params = F.param_dict(net)
    cfg = family_config(config)
    assert family.producers(cfg) == (2, 3)

    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    got = jax.grad(lambda p: program_loss(net, p, ids[:1], labels[:1])[0])(
        params)
    want = family.reference_producer_grads(
        param, cfg, jnp.asarray(ids[0]), jnp.asarray(labels[0]))
    assert set(want) == {n for n in params
                         if n.startswith(("model.layers.2.",
                                          "model.layers.3."))}
    for name in sorted(want):
        scale = float(jnp.abs(want[name]).max())
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)
    # what the readers add: without them a gradient is another
    cut = dict(params)
    for reader in (4, 5):
        name = f"model.layers.{reader}.mixer.out_proj.weight"
        cut[name] = jnp.zeros_like(params[name])
    alone = jax.grad(lambda p: program_loss(net, p, ids[:1], labels[:1])[0])(
        cut)
    for name in ("model.layers.2.mixer.A_log",
                 "model.layers.3.mixer.Wqkv.weight"):
        gap = float(jnp.abs(alone[name] - got[name]).max())
        assert gap > 1e-2 * float(jnp.abs(got[name]).max()), name


def test_recompute_gives_the_same_loss_and_gradients(tiny):
    """Every layer through ``fleet.recompute``: the readers are given m,
    K and V as arguments, the producers return them, and nothing
    moves."""
    net, config, ids, labels = tiny
    again = SambaYForCausalLM(dataclasses.replace(
        config, recompute=tuple(range(6))))
    some = SambaYForCausalLM(dataclasses.replace(config, recompute=(2, 5)))
    params = F.param_dict(net)

    def both(model):
        return jax.value_and_grad(
            lambda p: program_loss(model, p, ids, labels)[0])(params)

    want_loss, want = both(net)
    from paddle_tpu.observability import metrics
    gauge = lambda kind: metrics.registry().gauge(          # noqa: E731
        "recompute_layers", labels={"kind": kind}).collect()
    assert [gauge(k) for k in sambay.KINDS] == [0] * 6
    for model, counts in ((again, [1] * 6), (some, [0, 0, 1, 0, 0, 1])):
        loss, grads = both(model)
        assert [gauge(k) for k in sambay.KINDS] == counts
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
        for name in want:
            np.testing.assert_allclose(grads[name], want[name], rtol=1e-4,
                                       atol=1e-6, err_msg=name)
    again.eval()        # an evaluation recomputes nothing
    program_loss(again, params, ids, labels)
    assert [gauge(k) for k in sambay.KINDS] == [0] * 6


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_the_chunk_changes_nothing(tiny, chunk, monkeypatch):
    """The chunk follows from the sequence's length (8 at 64 positions);
    another gives the same loss."""
    net, config, ids, labels = tiny
    params = F.param_dict(net)
    want, _ = program_loss(net, params, ids, labels)
    monkeypatch.setattr(ssm, "selective_chunk", lambda seq: chunk)
    got, _ = program_loss(net, params, ids, labels)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_the_window_is_felt(tiny):
    """A window of the whole sequence is full attention, and the tiny
    preset's 16 of 64 is not."""
    net, config, ids, labels = tiny
    params = F.param_dict(net)
    want, _ = program_loss(net, params, ids, labels)
    wide = SambaYForCausalLM(dataclasses.replace(config, sliding_window=SEQ))
    wider = SambaYForCausalLM(dataclasses.replace(config,
                                                  sliding_window=4 * SEQ))
    a, _ = program_loss(wide, params, ids, labels)
    b, _ = program_loss(wider, params, ids, labels)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    assert abs(float(a) - float(want)) > 1e-5


def test_the_layout_counts_the_published_parameters():
    """16 + 2 + 14 at the published widths is the published 3.8 B (3 852
    M by the issue's arithmetic); 2 + 2 + 2 with an eighth of the rows is
    the cell's 697.1 M; by the family's count and by the program's
    shapes."""
    published = {"hidden_size": 2560, "intermediate_size": 10240,
                 "num_attention_heads": 40, "num_key_value_heads": 20,
                 "num_hidden_layers": 32, "mb_per_layer": 2,
                 "layers": {"n_self": 16, "n_cross": 14},
                 "sliding_window": 512, "layer_norm_eps": 1e-5,
                 "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                 "mamba_dt_rank": 160, "vocab_size": 200064}
    assert family.param_count(published) == pytest.approx(3852e6, rel=0.01)
    ks = family.kinds(published)
    assert [ks.count(k) for k in sambay.KINDS] == [8, 8, 1, 1, 7, 7]
    assert ks[16] == "mamba_memory" and ks[17] == "full_kv"
    assert SambaYConfig().kinds == ks
    per = family.layer_params(published)
    assert [round(per[k] / 1e6, 1) for k in sambay.KINDS] == [
        119.9, 98.3, 119.9, 98.3, 104.9, 91.8]
    cell = {**published, "num_hidden_layers": 6, "vocab_size": 25008,
            "layers": {"n_self": 2, "n_cross": 2}}
    assert family.param_count(cell) == 697_094_272
    assert family.kinds(cell) == sambay.KINDS
    # the program's shapes, with no array made
    from paddle_tpu.nn import layer as nn_layer
    config = SambaYConfig(n_self=2, n_cross=2, vocab_rows_held=25008)
    with nn_layer.LazyGuard():
        net = SambaYForCausalLM(config)
    assert sum(int(np.prod(p.shape)) for p in net.parameters()) == \
        family.param_count(cell)
    with pytest.raises(ValueError, match="even"):
        SambaYConfig(n_self=3)
    with pytest.raises(ValueError, match="names layers"):
        sambay_tiny(recompute=(6,))


def test_lambda_and_the_mamba_start():
    assert sambay.lambda_init(0) == pytest.approx(0.2)
    assert sambay.lambda_init(5) == pytest.approx(
        0.8 - 0.6 * np.exp(-1.5)) == family.lambda_init(5)
    paddle.seed(3)
    mixer = sambay.SambaYMamba(sambay_tiny(hidden_size=128), 0, False)
    steps = np.asarray(jax.nn.softplus(mixer.dt_proj.bias._value))
    assert 0.001 <= steps.min() and steps.max() <= 0.1 + 1e-6
    a = np.exp(np.asarray(mixer.A_log._value))
    np.testing.assert_allclose(a, np.broadcast_to(
        np.arange(1, 5, dtype=np.float32), (256, 4)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mixer.D._value), 1.0)
    assert mixer.x_proj.weight.shape == [256, 8 + 2 * 4]
    cross = sambay.SambaYAttention(sambay_tiny(), 5, "cross")
    assert cross.Wqkv.weight.shape == [64, 64]       # a query only
    assert sambay.SambaYAttention(sambay_tiny(), 3,
                                  "full_kv").Wqkv.weight.shape == [64, 128]


def test_scans_and_handed_on_arrays_are_counted_as_they_are_traced(tiny):
    net, config, ids, labels = tiny
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    read = lambda i: reg.counter(                           # noqa: E731
        "s6_scan_chunks_total", labels={"layer": str(i)}).collect()
    before = [read(i) for i in range(6)]
    jax.eval_shape(lambda p: program_loss(net, p, ids, labels)[0],
                   F.param_dict(net))
    grew = [read(i) - b for i, b in zip(range(6), before)]
    # two sequences x 8 chunks of 8 in the two Mamba layers; no other
    assert grew == [BATCH * SEQ // 8, 0, BATCH * SEQ // 8, 0, 0, 0]
    assert reg.gauge("s6_scan_state_bytes",
                     labels={"layer": "2"}).collect() == 8 * 128 * 4 * 4
    # m [B, S, 128] and K, V [B, S, 2, 16] each, float32 here
    assert reg.gauge("gmu_memory_bytes").collect() == BATCH * SEQ * 128 * 4
    assert reg.gauge("yoco_shared_kv_bytes").collect() == \
        2 * BATCH * SEQ * 32 * 4


def test_it_trains_through_the_runner_under_bf16_o2_with_recompute():
    """The way a user's script does it, as the benchmark's driver does:
    seed -> model -> AdamW -> amp.decorate O2 -> mesh -> runner, every
    layer recomputed; the loss falls on a batch seen again and again."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(21)
    net = SambaYForCausalLM(sambay_tiny(
        vocab_rows_held=VOCAB, recompute=tuple(range(6))))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, SambaYPretrainingCriterion(),
                               mesh=mesh)
    ids = np.random.default_rng(9).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    labels = np.roll(ids, -1, axis=1)
    losses = [float(runner.train_step([ids], [labels])) for _ in range(8)]
    assert all(np.isfinite(losses))
    assert abs(losses[0] - np.log(VOCAB)) < 0.5
    assert losses[-1] < losses[0] - 0.02
    named = dict(net.named_parameters())
    assert named["model.embed_tokens.weight"]._value.dtype == jnp.bfloat16
    assert named["model.layers.0.input_layernorm.weight"]._value.dtype == \
        jnp.float32
    logits = runner.predict_step([ids])._value
    assert logits.shape == (BATCH, SEQ, VOCAB)
    assert logits.dtype == jnp.bfloat16
