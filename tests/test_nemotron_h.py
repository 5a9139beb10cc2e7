"""Nemotron-H against its plain reference
(``benchmarks/families/nemotron_h.py``) at a small size on the CPU, and
what its pieces promise: recomputing any choice of blocks changes nothing
(an expert block routes again to the same experts), the shares of an
expert-parallel layout add up to the uncut layer with the shared expert
counted once, no pair is dropped under full imbalance, the router
chooses by ``s + b`` and weighs by ``s``, the gated norm is by group, and
the scan at several groups and chunk 128 takes the kernels.
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
from paddle_tpu.incubate.distributed.models.moe import grouped  # noqa: E402
from paddle_tpu.models import (                               # noqa: E402
    NemotronHConfig, NemotronHForCausalLM, NemotronHPretrainingCriterion,
    blocks, mamba2, nemotron_h, nemotron_h_tiny)
from paddle_tpu.observability import metrics                  # noqa: E402
from paddle_tpu.ops import pallas_ops, ssm                    # noqa: E402
from benchmarks.families import nemotron_h as family          # noqa: E402

VOCAB, SEQ, BATCH = 64, 64, 2


def family_config(c: NemotronHConfig) -> dict:
    """The program's config under the configuration file's keys."""
    out = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    first, count = c.blocks_held
    out.update(vocab_size=c.vocab_rows_held, num_hidden_layers=count,
               hybrid_override_pattern=c.hybrid_override_pattern[
                   first:first + count],
               n_routed_experts=c.experts_held[1],
               published={"n_routed_experts": c.n_routed_experts})
    return out


def seeded(config, seed=11):
    """A model with seeded weights away from their symmetric start: no
    norm is the identity and the router's bias is not 0."""
    paddle.seed(seed)
    net = NemotronHForCausalLM(config)
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    for name, b in net.named_buffers():
        if name.endswith("e_score_correction_bias"):
            b._value = jnp.asarray(0.2 * rng.standard_normal(b.shape),
                                   jnp.float32)
    return net


def everything(net) -> dict:
    """Parameters and the routers' biases by name, as the reference's
    ``param`` reads them."""
    return {**F.param_dict(net), **{
        n: b for n, b in F.buffer_dict(net).items()
        if n.endswith("e_score_correction_bias")}}


@pytest.fixture(scope="module")
def tiny():
    config = nemotron_h_tiny(vocab_rows_held=VOCAB, experts_held=(2, 4))
    ids = np.random.default_rng(6).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    return seeded(config), config, ids, np.roll(ids, -1, axis=1)


def program_loss(net, params, ids, labels, buffers=None):
    out, buffers = F.functional_call(
        net, params, F.buffer_dict(net) if buffers is None else buffers,
        (paddle.to_tensor(ids),))
    logp = jax.nn.log_softmax(out._value.astype(jnp.float32), -1)
    loss = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                -1).mean()
    return loss, (out._value, buffers["expert_tokens"])


def test_logits_loss_and_every_gradient_agree_with_the_reference(tiny):
    net, config, ids, labels = tiny
    params, cfg = F.param_dict(net), family_config(config)
    assert family.param_count(cfg) == sum(
        int(np.prod(p.shape)) for p in net.parameters())
    assert config.kinds == ("mamba", "moe", "attention", "moe")
    biases = {n: v for n, v in everything(net).items() if n not in params}
    assert len(biases) == 2

    (loss, (logits, tokens)), got = jax.value_and_grad(
        lambda p: program_loss(net, p, ids, labels), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(lambda p: family.reference_loss(
        {**p, **biases}, cfg, jnp.asarray(ids), jnp.asarray(labels)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)

    both = everything(net)
    counts = 0
    for b in range(BATCH):
        ref = family.reference_forward(
            lambda name, rows=None: both[name] if rows is None
            else both[name][rows], cfg, jnp.asarray(ids[b]))
        np.testing.assert_allclose(
            logits[b], family.reference_logits(ref["hidden"],
                                               params[family.HEAD]),
            rtol=2e-4, atol=2e-5)
        counts = counts + np.stack(ref["counts"])
    # by held expert, the pairs computed are the pairs the reference's
    # loop routes here
    np.testing.assert_array_equal(np.asarray(tokens), counts)
    assert set(got) == set(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("blocks", [(0,), (1, 3), (0, 1, 2, 3)],
                         ids=["mamba", "moe", "every_block"])
def test_recompute_gives_the_same_routing_loss_and_gradients(tiny, blocks):
    net, config, ids, labels = tiny
    again = NemotronHForCausalLM(
        dataclasses.replace(config, recompute=blocks))
    assert again.training
    params, buffers = F.param_dict(net), F.buffer_dict(net)

    def all_of(model):
        return jax.value_and_grad(
            lambda p: program_loss(model, p, ids, labels, buffers),
            has_aux=True)(params)

    ((loss, (_, tokens)), grads) = all_of(again)
    by_kind = {kind: metrics.registry().gauge(
        "recompute_layers", labels={"kind": kind}).collect()
        for kind in ("mamba", "moe", "attention")}
    ((want_loss, (_, want_tokens)), want) = all_of(net)
    assert float(loss) == float(want_loss)
    np.testing.assert_array_equal(np.asarray(tokens),
                                  np.asarray(want_tokens))
    for name in want:
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)
    assert by_kind == {kind: sum(config.kinds[i] == kind for i in blocks)
                       for kind in by_kind}
    assert sum(metrics.registry().gauge(
        "recompute_layers", labels={"kind": kind}).collect()
        for kind in by_kind) == 0        # the plain model, traced last

    def blocks_recomputed(model):
        jaxpr = jax.make_jaxpr(
            lambda p: program_loss(model, p, ids, labels, buffers)[0])(
                params)
        return sum(eqn.primitive.name == "remat2"
                   and "dot_general" in str(eqn) for eqn in jaxpr.jaxpr.eqns)

    # a block's checkpoint holds its products; a norm's own holds none
    assert blocks_recomputed(again) - blocks_recomputed(net) == len(blocks)
    with pytest.raises(ValueError, match="index among"):
        dataclasses.replace(config, recompute=(4,))


def _moe_block(config, net):
    return next(b for b in net.backbone.layers if b.kind == "moe").mixer


def test_the_shares_add_up_to_the_uncut_layer():
    """Four ranks hold two of eight experts each.  What each computes, its
    own routed experts' part plus the shared expert, summed with the
    shared expert counted once, is the reference's uncut layer."""
    whole = nemotron_h_tiny(vocab_rows_held=VOCAB)
    big = seeded(whole)
    params, buffers = dict(F.param_dict(big)), dict(F.buffer_dict(big))
    u = jnp.asarray(np.random.default_rng(7).standard_normal((1, SEQ, 64)),
                    jnp.float32)
    prefix = "backbone.layers.1.mixer."
    shared = _moe_block(whole, big).shared(paddle.to_tensor(u[0]))._value
    total, pairs = 0.0, 0
    for rank in range(4):
        share = nemotron_h_tiny(vocab_rows_held=VOCAB,
                                experts_held=(2 * rank, 2))
        net = NemotronHForCausalLM(share)
        held = dict(params)
        for w in ("w1", "w2"):
            name = prefix + "experts." + w
            held[name] = params[name][2 * rank:2 * rank + 2]
        mixer = _moe_block(share, net)
        (out, sizes, _), _ = F.functional_call(
            mixer, {k[len(prefix):]: v for k, v in held.items()
                    if k.startswith(prefix)},
            {k[len(prefix):]: v for k, v in buffers.items()
             if k.startswith(prefix)}, (paddle.to_tensor(u),))
        total = total + (out._value[0] - shared)
        pairs += int(sizes._value.sum())
    assert pairs == SEQ * whole.num_experts_per_tok
    both = everything(big)
    p = {k: both[prefix + n[len("mixer."):]]
         for k, n in family.MIXER["moe"].items()}
    want, _, counts = family._moe_mixer(
        u[0], p, jnp.zeros((SEQ, 2), jnp.int32), top_k=2, first=0,
        scale=2.5, given=False)
    assert int(counts.sum()) == pairs
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-3      # it is no small part


def test_no_pair_is_dropped_when_the_bias_sends_every_token_to_one_expert():
    """``b`` lifts expert 3 over every other: all tokens choose it, the
    pairs held here overflow the usual window and the later windows run;
    values and gradients are the reference's."""
    tokens, d, f, experts, k = 2048, 16, 8, 64, 2
    rng = np.random.default_rng(8)
    y, router, w1, w2 = (jnp.asarray(rng.standard_normal(s) * scale,
                                     jnp.float32) for s, scale in (
        ((tokens, d), 1.0), ((d, experts), 1.0), ((experts, d, f), 0.3),
        ((experts, f, d), 0.3)))
    bias = jnp.zeros((experts,)).at[3].set(10.0)
    first, held = 2, 4

    def share(y_, router_, w1_, w2_):
        chosen, gates = grouped.route_sigmoid(y_ @ router_, bias, k, 2.5)
        return grouped.experts_forward(
            y_, chosen, gates, (w1_[first:first + held],
                                w2_[first:first + held]), first, experts)

    def reference(y_, router_, w1_, w2_):
        p = {"router": router_, "bias": bias, "w1": w1_[first:first + held],
             "w2": w2_[first:first + held],
             "shared_up": jnp.zeros((d, 1)), "shared_down": jnp.zeros((1, d))}
        out, _, counts = family._moe_mixer(
            y_, p, jnp.zeros((tokens, k), jnp.int32), top_k=k, first=first,
            scale=2.5, given=False)
        return out, counts

    out, sizes = share(y, router, w1, w2)
    want, counts = reference(y, router, w1, w2)
    assert int(sizes[1]) == tokens          # expert 3 is row 1 of the held
    assert int(sizes.sum()) > grouped.usual_rows(tokens, k, held, experts)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(counts))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda *a: (share(*a)[0] ** 2).sum(),
                   argnums=(0, 1, 2, 3))(y, router, w1, w2)
    ref = jax.grad(lambda *a: (reference(*a)[0] ** 2).sum(),
                   argnums=(0, 1, 2, 3))(y, router, w1, w2)
    for name, a, b in zip(("y", "router", "w1", "w2"), got, ref):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_router_chooses_by_s_plus_b_and_weighs_by_s():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.5]])
    s = jax.nn.sigmoid(logits)
    none, gates0 = grouped.route_sigmoid(logits, jnp.zeros(4), 2, 2.5)
    np.testing.assert_array_equal(np.asarray(none), [[0, 1], [2, 3]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])        # lifts expert 3
    chosen, gates = grouped.route_sigmoid(logits, bias, 2, 2.5)
    np.testing.assert_array_equal(np.asarray(chosen), [[3, 0], [3, 2]])
    # weighed by the sigmoid alone, over the chosen, times the scale
    want = jnp.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        gates, 2.5 * want / want.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-6)
    # no gradient reaches the bias; the logits' passes through the gates
    d_logits, d_bias = jax.grad(
        lambda l, b: (grouped.route_sigmoid(l, b, 2, 2.5)[1]
                      * jnp.asarray([1.0, -1.0])).sum(), (0, 1))(logits, bias)
    assert float(jnp.abs(d_bias).max()) == 0.0
    assert float(jnp.abs(d_logits).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(d_logits[:, 1]), 0.0)
    with pytest.raises(ValueError, match="chooses over all experts"):
        nemotron_h_tiny(n_group=2)


def test_the_balancing_rule_moves_the_bias_towards_equal_loads(tiny):
    """After a pass in training mode ``b_e`` has moved by the rate towards
    the mean load (up where the expert drew fewer tokens than the mean,
    down where more), over all experts, held here or not; an evaluation
    and a rate of 0 leave it; pass after pass the loads draw level."""
    _, config, ids, _ = tiny
    paddle.seed(4)
    net = NemotronHForCausalLM(dataclasses.replace(
        config, router_bias_update_rate=1e-3, recompute=(1,)))
    name = "backbone.layers.1.mixer.e_score_correction_bias"
    params, buffers = F.param_dict(net), F.buffer_dict(net)

    def one_pass(bufs):
        out, new = F.functional_call(net, params, bufs,
                                     (paddle.to_tensor(ids),),
                                     {"output_routing": True})
        load = np.bincount(np.asarray(out[1]._value[0]).reshape(-1),
                           minlength=config.n_routed_experts)
        return load, new

    load, after = one_pass(buffers)
    assert load.sum() == BATCH * SEQ * config.num_experts_per_tok
    np.testing.assert_allclose(
        np.asarray(after[name]) - np.asarray(buffers[name]),
        1e-3 * np.sign(load.mean() - load), atol=1e-9)
    assert np.asarray(buffers[name]).any() == False        # noqa: E712
    first = load
    for _ in range(80):
        load, after = one_pass(after)
    assert load.std() < 0.5 * first.std()
    # it is a buffer: the optimizer never sees it, no gradient reaches it
    assert name not in params
    net.eval()
    _, kept = one_pass(after)
    np.testing.assert_array_equal(np.asarray(kept[name]),
                                  np.asarray(after[name]))
    still = NemotronHForCausalLM(config)          # the rate defaults to 0
    out, same = F.functional_call(still, F.param_dict(still),
                                  F.buffer_dict(still),
                                  (paddle.to_tensor(ids),))
    assert not np.asarray(same[name]).any()


def test_the_gated_norm_is_by_group_and_the_references():
    rng = np.random.default_rng(9)
    y, z = (jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
            for _ in range(2))
    y = y * jnp.repeat(jnp.asarray([0.1, 1.0, 10.0, 100.0]), 32)
    weight = jnp.asarray(1 + 0.1 * rng.standard_normal(128), jnp.float32)
    gated = blocks.silu_gate(z, y)
    by_group = mamba2._rms_by_group(gated, weight, 1e-5, 4)
    whole = mamba2._rms_by_group(gated, weight, 1e-5, 1)
    np.testing.assert_array_equal(
        np.asarray(whole),
        np.asarray(blocks.rms(gated, weight, 1e-5)))
    np.testing.assert_allclose(
        by_group, family.gated_norm(y, z, weight, 4, 1e-5), rtol=1e-5)
    np.testing.assert_allclose(
        whole, family.gated_norm(y, z, weight, 1, 1e-5), rtol=1e-5)
    # over the whole width the largest group drowns the others
    assert float(jnp.abs(by_group[:, :32]).mean()) > 50 * float(
        jnp.abs(whole[:, :32]).mean())
    # each group of the grouped norm has unit mean square before its
    # weight (but for eps, which the smallest group feels)
    unit = mamba2._rms_by_group(gated, jnp.ones(128), 1e-5, 4)
    np.testing.assert_allclose(
        (unit.reshape(32, 4, 32) ** 2).mean(-1), 1.0, rtol=5e-2)


def test_the_published_scan_takes_the_kernels_on_a_tpu(monkeypatch):
    shape = (8192, 64, 64, 8, 128, 128)
    assert ssm.scan_form(*shape) == "xla"           # a CPU, no interpreter
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    assert ssm.scan_form(*shape) == "kernels"
    c = NemotronHConfig()
    assert shape == (8192, c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                     c.ssm_state_size, c.chunk_size)
    mixer = mamba2.Mamba2Mixer(64, c.mamba_num_heads, c.mamba_head_dim,
                               c.ssm_state_size, c.n_groups, c.conv_kernel,
                               c.chunk_size, 1e-5, 0.02, 0.02, 0)
    assert (mixer.d_inner, mixer.conv_dim, mixer.d_inner // mixer.groups) == (
        4096, 6144, 512)
    assert c.kinds.count("mamba") == c.kinds.count("moe") == 23
    assert c.kinds.count("attention") == 6 and c.kinds[:9] == (
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe",
        "mamba", "moe")


def _scan_inputs(seq, heads, width, groups, state, seed=0):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s),     # noqa: E731
                                    jnp.float32)
    return (normal(seq, heads, width),
            jax.nn.softplus(normal(seq, heads)), -jnp.exp(normal(heads)),
            normal(seq, groups, state), normal(seq, groups, state),
            normal(heads), normal(seq, heads, width))


@pytest.mark.parametrize("heads, groups", [
    pytest.param(8, 2, id="two_groups_of_two_lane_groups"),
    pytest.param(16, 4, id="four_groups_of_two_lane_groups"),
    pytest.param(24, 2, id="two_groups_of_six_lane_groups"),
])
def test_kernels_at_several_groups_and_chunk_128_are_the_xla_form(
        monkeypatch, heads, groups):
    """A group's ``C . B^T`` is made at its first lane group and shared by
    the rest, and its gradient gathered at its last: y and the six
    gradients against the XLA form."""
    seq, width, state, chunk = 256, 64, 128, 128
    *inputs, w = _scan_inputs(seq, heads, width, groups, state)

    def value_and_grads():
        return jax.value_and_grad(
            lambda *a: (ssm.ssd_scan(*a, chunk) * w).sum(),
            argnums=tuple(range(6)))(*inputs)

    assert ssm.scan_form(seq, heads, width, groups, state, chunk) == "xla"
    want = value_and_grads()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.scan_form(seq, heads, width, groups, state,
                         chunk) == "kernels"
    got = value_and_grads()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for name, a, b in zip("x dt A B C D".split(), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=3e-5 * float(jnp.abs(b).max()),
            err_msg=name)


def test_it_trains_through_the_runner_under_bf16_o2_with_recompute():
    """The way a user's script does it, as the benchmark's driver does:
    seed -> model -> AdamW -> amp.decorate O2 -> mesh -> runner, the
    Mamba-2 block and one expert block recomputed, the balancing rule on;
    the loss falls on a batch seen again and again, and the step's
    counters are published."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(21)
    net = NemotronHForCausalLM(nemotron_h_tiny(
        vocab_rows_held=VOCAB, experts_held=(0, 4), recompute=(0, 1),
        router_bias_update_rate=1e-3))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, NemotronHPretrainingCriterion(),
                               mesh=mesh)
    ids = np.random.default_rng(9).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    labels = np.roll(ids, -1, axis=1)
    reg = metrics.registry()
    pairs = lambda i: reg.counter(                          # noqa: E731
        "moe_pairs_total", labels={"layer": str(i)}).collect()
    before = [pairs(i) for i in range(4)]
    losses = [float(runner.train_step([ids], [labels])) for _ in range(8)]
    assert all(np.isfinite(losses))
    assert abs(losses[0] - np.log(VOCAB)) < 0.5
    assert losses[-1] < losses[0] - 0.02
    assert net.backbone.embeddings.weight._value.dtype == jnp.bfloat16
    assert net.backbone.layers[1].mixer.e_score_correction_bias._value.dtype \
        == jnp.float32
    tokens = np.asarray(net.expert_tokens._value)
    assert tokens.shape == (2, 4) and tokens.sum() > 0
    # the step carries the routers' biases as it carries any buffer: eight
    # steps of the balancing rule, one rate each
    bias = np.asarray(net.backbone.layers[1].mixer.e_score_correction_bias
                      ._value)
    assert bias.any() and np.abs(bias).max() <= 8e-3 + 1e-9
    assert not any("e_score_correction_bias" in n
                   for n, _ in net.named_parameters())
    net.observe_step()
    grew = [pairs(i) - b for i, b in zip(range(4), before)]
    assert grew == [0, tokens[0].sum(), 0, tokens[1].sum()]
    assert net.moe_blocks() == (1, 3)
    assert reg.gauge("moe_expert_tokens_max",
                     labels={"layer": "3"}).collect() == tokens[1].max()
    assert [reg.gauge("recompute_layers", labels={"kind": k}).collect()
            for k in ("mamba", "moe", "attention")] == [1, 1, 0]
    logits = runner.predict_step([ids])._value
    assert logits.shape == (BATCH, SEQ, VOCAB)
    assert logits.dtype == jnp.bfloat16


def test_a_middle_stage_holds_its_blocks_of_the_pattern():
    c = nemotron_h_tiny(num_hidden_layers=8,
                        hybrid_override_pattern="ME*EMEM*",
                        blocks_held=(4, 3), vocab_rows_held=VOCAB)
    assert c.kinds == ("mamba", "moe", "mamba")
    net = NemotronHForCausalLM(c)
    assert net.moe_blocks() == (5,)
    assert [b.mixer.layer_idx for b in net.backbone.layers
            if b.kind == "mamba"] == [4, 6]
    # what writes into the stream starts smaller by sqrt(depth)
    paddle.seed(3)
    wide = nemotron_h.NemotronHBlock(nemotron_h_tiny(
        hidden_size=256, mamba_num_heads=32, num_hidden_layers=16,
        hybrid_override_pattern="M" * 16), 0)
    assert float(wide.mixer.out_proj.weight._value.std()) == pytest.approx(
        0.02 / 4, rel=0.05)
    assert float(wide.mixer.in_proj.weight._value.std()) == pytest.approx(
        0.02, rel=0.05)
    c = nemotron_h_tiny(hidden_size=256, mamba_num_heads=32,
                        num_hidden_layers=16, embedding_range=1.0,
                        hybrid_override_pattern="ME*E" * 4)
    assert c.residual_range == 0.02 / 4
    net = NemotronHForCausalLM(c)
    std = lambda p: float(p._value.std())                   # noqa: E731
    moe, attention = net.backbone.layers[1].mixer, \
        net.backbone.layers[2].mixer
    for small in (moe.experts.w2, moe.shared_down.weight,
                  attention.o_proj.weight):
        assert std(small) == pytest.approx(0.02 / 4, rel=0.05)
    for usual in (moe.experts.w1, moe.shared_up.weight, moe.gate.weight,
                  attention.q_proj.weight, net.lm_head.weight):
        assert std(usual) == pytest.approx(0.02, rel=0.05)
    assert std(net.backbone.embeddings.weight) == pytest.approx(1.0, rel=0.05)
    plain = nemotron_h_tiny(rescale_prenorm_residual=False)
    assert plain.residual_range == plain.embedding_range == 0.02
    with pytest.raises(ValueError, match="names a mixer"):
        nemotron_h_tiny(hybrid_override_pattern="MEXE")
