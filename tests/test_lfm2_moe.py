"""LFM2-MoE against its plain reference
(``benchmarks/families/lfm2_moe.py``) at a small size on the CPU, and what
its pieces promise: the gated short convolution is the loop a position at
a time and causal, recomputing any choice of layers changes nothing (an
expert layer routes again to the same experts), the shares of an
expert-parallel layout add up to the uncut layer, no pair is dropped under
full imbalance, the router chooses by ``s + b``, weighs by ``s`` and adds
1e-6, the balancing rule draws the loads level, and the experts' products
at the published widths take the kernels on a TPU.
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
    Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoePretrainingCriterion,
    lfm2_moe, lfm2_moe_tiny, nemotron_h_tiny, NemotronHForCausalLM)
from paddle_tpu.observability import metrics                  # noqa: E402
from paddle_tpu.ops import grouped_matmul, pallas_ops, short_conv  # noqa: E402
from benchmarks.families import lfm2_moe as family            # noqa: E402

VOCAB, SEQ, BATCH = 64, 64, 2
BIAS = "expert_bias"


def family_config(c: Lfm2MoeConfig) -> dict:
    """The program's config under the configuration file's keys."""
    out = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    first, count = c.layers_held
    out.update(vocab_size=c.vocab_rows_held, num_hidden_layers=count,
               layer_types=c.layer_types[first:first + count],
               num_dense_layers=max(0, c.num_dense_layers - first),
               num_experts=c.experts_held[1],
               published={"num_experts": c.num_experts})
    return out


def seeded(config, seed=11):
    """A model with seeded weights away from their symmetric start: no
    norm is the identity and the router's bias is not 0."""
    paddle.seed(seed)
    net = Lfm2MoeForCausalLM(config)
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    for name, b in net.named_buffers():
        if name.endswith(BIAS):
            b._value = jnp.asarray(0.2 * rng.standard_normal(b.shape),
                                   jnp.float32)
    return net


def everything(net) -> dict:
    """Parameters and the routers' biases by name, as the reference's
    ``param`` reads them."""
    return {**F.param_dict(net), **{
        n: b for n, b in F.buffer_dict(net).items() if n.endswith(BIAS)}}


@pytest.fixture(scope="module")
def tiny():
    config = lfm2_moe_tiny(vocab_rows_held=VOCAB, experts_held=(2, 4))
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
    assert config.kinds == family.kinds(cfg) == (
        "conv_dense", "conv_moe", "attention_moe", "conv_moe")
    biases = {n: v for n, v in everything(net).items() if n not in params}
    assert len(biases) == 3
    for i in range(4):
        assert set(family.layer_parameters(cfg, i)) == {
            n for n in params if n.startswith(f"model.layers.{i}.")}

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
                                               params[family.EMBEDDING]),
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


# --------------------------------------------------------------------------
# the operator
# --------------------------------------------------------------------------
def _operator_inputs(seq=48, channels=16, width=3, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((seq, 3 * channels)), dtype),
            jnp.asarray(rng.standard_normal((channels, width)), dtype),
            jnp.asarray(rng.standard_normal((seq, channels)), dtype))


@pytest.mark.parametrize("width", [1, 3, 4])
def test_the_operator_is_the_loop_a_position_at_a_time(width, monkeypatch):
    bcx, taps, w = _operator_inputs(width=width)
    before = [metrics.registry().counter(
        "short_conv_calls_total", labels={"kind": k}).collect()
        for k in ("forward", "backward")]
    y, vjp = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    d_bcx, d_taps = vjp(w)
    want = family.reference_conv_grads(bcx, taps, w)
    for name, a, b in zip(("y", "dbcx", "dweight"), (y, d_bcx, d_taps), want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(family.short_conv(bcx, taps), want[0],
                               rtol=1e-5, atol=1e-6)
    # its own backward pass, and jax's of the same forward
    for a, b in zip((d_bcx, d_taps), jax.vjp(short_conv._forward, bcx,
                                             taps)[1](w)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert [metrics.registry().counter(
        "short_conv_calls_total", labels={"kind": k}).collect()
        for k in ("forward", "backward")] == [before[0] + 1, before[1] + 1]
    # the XLA form on the CPU; the cell's shape takes the kernels where
    # they run
    assert short_conv.gated_short_conv_form(48, 16, width) == "xla"
    assert short_conv.gated_short_conv_form(8192, 2048, 3) == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert short_conv.gated_short_conv_form(48, 16, width) == "xla"
    assert short_conv.gated_short_conv_form(8192, 2048, 3) == "kernels"


def test_the_operators_first_positions_read_zeros_and_it_is_causal():
    bcx, taps, _ = _operator_inputs()
    b, c, x = (np.asarray(a) for a in jnp.split(bcx, 3, -1))
    z, w = b * x, np.asarray(taps)
    y = np.asarray(short_conv.gated_short_conv(bcx, taps))
    # position 0 sees its own product alone, position 1 its own and one
    np.testing.assert_allclose(y[0], c[0] * w[:, 2] * z[0], rtol=1e-5)
    np.testing.assert_allclose(
        y[1], c[1] * (w[:, 2] * z[1] + w[:, 1] * z[0]), rtol=1e-5)
    np.testing.assert_allclose(
        y[2], c[2] * (w[:, 2] * z[2] + w[:, 1] * z[1] + w[:, 0] * z[0]),
        rtol=1e-5)
    # token t moves no output before position t, and none after t + 2
    t = 20
    moved = np.asarray(short_conv.gated_short_conv(
        bcx.at[t].add(1.0), taps))
    changed = np.abs(moved - y).max(-1) > 0
    assert not changed[:t].any() and changed[t:t + 3].all() \
        and not changed[t + 3:].any()
    # the backward pass keeps bcx and the taps and nothing else
    _, kept = short_conv._fwd(bcx, taps)
    assert len(kept) == 2 and kept[0] is bcx and kept[1] is taps
    assert short_conv.gated_short_conv_bytes(8192, 2048) == \
        8192 * 2048 * 2 * 11
    with pytest.raises(ValueError, match="three blocks"):
        short_conv.gated_short_conv(bcx[:, :-1], taps)


def test_the_operator_in_bf16_sums_in_float32():
    bcx, taps, w = _operator_inputs(seq=256, channels=128, dtype=jnp.bfloat16)
    y, vjp = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    d_bcx, d_taps = vjp(w)
    assert (y.dtype, d_bcx.dtype, d_taps.dtype) == (jnp.bfloat16,) * 3
    want = family.reference_conv_grads(*(a.astype(jnp.float32)
                                         for a in (bcx, taps, w)))
    for name, a, b in zip(("y", "dbcx", "dweight"), (y, d_bcx, d_taps), want):
        err = float(jnp.abs(a.astype(jnp.float32) - b).max()
                    / jnp.abs(b).max())
        assert err < 8e-3, (name, err)        # one rounding of the result


# --------------------------------------------------------------------------
# the operator's Mosaic kernels (ops/short_conv_kernels.py), interpreted
# --------------------------------------------------------------------------
def _kernel_calls():
    return [metrics.registry().counter(
        "short_conv_kernel_calls_total", labels={"kind": k}).collect()
        for k in ("fwd", "bwd")]


@pytest.mark.parametrize("seq, channels, width", [
    pytest.param(768, 256, 1, id="three_tiles-two_lane_groups-1_tap"),
    pytest.param(768, 256, 3, id="three_tiles-two_lane_groups-3_taps"),
    pytest.param(768, 256, 4, id="three_tiles-two_lane_groups-4_taps"),
    pytest.param(48, 128, 9, id="three_tiles_of_16_rows-reach_of_8_rows"),
])
def test_the_kernels_are_the_loop_a_position_at_a_time(
        monkeypatch, seq, channels, width):
    """y and the gradients by ``[B | C | x]`` and by the taps through the
    kernels, over tiles whose carried rows cross each boundary forward
    (z) and back (dc), against the float32 loop and the XLA form."""
    bcx, taps, w = _operator_inputs(seq, channels, width, seed=width)
    xla_form = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert short_conv.gated_short_conv_form(seq, channels,
                                            width) == "kernels"
    before = _kernel_calls()
    y, vjp = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    got = (y,) + vjp(w)
    assert [a - b for a, b in zip(_kernel_calls(), before)] == [1, 1]
    want = family.reference_conv_grads(bcx, taps, w)
    for name, a, b, c in zip(("y", "dbcx", "dweight"), got, want,
                             (xla_form[0],) + xla_form[1](w)):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        scale = float(jnp.abs(b).max())     # dweight: sums of 768 terms
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=name)


def test_the_kernels_in_bf16_round_as_the_xla_form(monkeypatch):
    """bf16 operands, float32 sums, one rounding of each result: within
    check (c)'s limit of the float32 loop and a rounding of the XLA form."""
    bcx, taps, w = _operator_inputs(512, 128, 3, jnp.bfloat16)
    xla_form = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    y, vjp = jax.vjp(short_conv.gated_short_conv, bcx, taps)
    want = family.reference_conv_grads(*(a.astype(jnp.float32)
                                         for a in (bcx, taps, w)))
    for name, a, b, c in zip(("y", "dbcx", "dweight"), (y,) + vjp(w), want,
                             (xla_form[0],) + xla_form[1](w)):
        assert a.dtype == jnp.bfloat16, name
        a, c = a.astype(jnp.float32), c.astype(jnp.float32)
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 8e-3, name
        assert float(jnp.abs(a - c).max() / jnp.abs(c).max()) < 8e-3, name


def test_the_kernels_are_counted_as_they_are_traced(monkeypatch):
    """``short_conv_kernel_calls_total{kind}``: a call differentiated at
    the cell's shape reads one forward and one backward call, and keeps
    ``bcx`` and the taps alone; the XLA form adds 0, and
    ``short_conv_calls_total`` counts either."""
    bcx = jax.ShapeDtypeStruct((8192, 3 * 2048), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((2048, 3), jnp.bfloat16)

    def traced():
        def weighted(b, t):
            return short_conv.gated_short_conv(b, t).astype(
                jnp.float32).sum()

        before = _kernel_calls() + [metrics.registry().counter(
            "short_conv_calls_total", labels={"kind": k}).collect()
            for k in ("forward", "backward")]
        jax.eval_shape(jax.grad(weighted, argnums=(0, 1)), bcx, taps)
        after = _kernel_calls() + [metrics.registry().counter(
            "short_conv_calls_total", labels={"kind": k}).collect()
            for k in ("forward", "backward")]
        return [a - b for a, b in zip(after, before)]

    assert traced() == [0, 0, 1, 1]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced() == [1, 1, 1, 1]
    small, taps_, _ = _operator_inputs(48, 128, 3)
    _, kept = short_conv._fwd(small, taps_)
    assert len(kept) == 2 and kept[0] is small and kept[1] is taps_


@pytest.mark.parametrize("interpreted, shape, form", [
    # (seq, channels, width)
    pytest.param(True, (8192, 2048, 3), "kernels", id="the_cells_shape"),
    pytest.param(False, (8192, 2048, 3), "xla", id="on_the_cpu"),
    pytest.param(True, (8192, 2000, 3), "xla", id="no_whole_lane_groups"),
    pytest.param(True, (8192 + 8, 2048, 3), "xla",
                 id="no_whole_number_of_sublane_tiles"),
    pytest.param(True, (8192, 2048, 10), "xla",
                 id="taps_further_back_than_eight_rows"),
    pytest.param(True, (8192, 8 * 2048, 3), "xla",
                 id="a_tile_of_all_channels_fills_vmem"),
])
def test_the_form_reads_platform_and_shape(monkeypatch, interpreted, shape,
                                           form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert short_conv.gated_short_conv_form(*shape) == form


# --------------------------------------------------------------------------
# recomputation, the shares, imbalance, the router
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layers", [(0,), (1, 2), (0, 1, 2, 3)],
                         ids=["conv_dense", "moe", "every_layer"])
def test_recompute_gives_the_same_routing_loss_and_gradients(tiny, layers):
    net, config, ids, labels = tiny
    again = Lfm2MoeForCausalLM(
        dataclasses.replace(config, recompute=layers))
    assert again.training
    params, buffers = F.param_dict(net), F.buffer_dict(net)
    kinds = ("conv_dense", "conv_moe", "attention_moe")

    def all_of(model):
        return jax.value_and_grad(
            lambda p: program_loss(model, p, ids, labels, buffers),
            has_aux=True)(params)

    ((loss, (_, tokens)), grads) = all_of(again)
    by_kind = {kind: metrics.registry().gauge(
        "recompute_layers", labels={"kind": kind}).collect()
        for kind in kinds}
    ((want_loss, (_, want_tokens)), want) = all_of(net)
    assert float(loss) == float(want_loss)
    np.testing.assert_array_equal(np.asarray(tokens),
                                  np.asarray(want_tokens))
    for name in want:
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)
    assert by_kind == {kind: sum(config.kinds[i] == kind for i in layers)
                       for kind in kinds}
    assert sum(metrics.registry().gauge(
        "recompute_layers", labels={"kind": kind}).collect()
        for kind in kinds) == 0          # the plain model, traced last
    with pytest.raises(ValueError, match="index among"):
        dataclasses.replace(config, recompute=(4,))


def _moe_block(net):
    return next(l for l in net.model.layers if l.is_moe).feed_forward


def test_the_shares_add_up_to_the_uncut_layer():
    """Four ranks hold eight of 32 experts each.  What each computes, its
    own routed experts' part (nothing is computed alike on every rank: the
    family has no shared expert), summed, is the reference's uncut
    layer."""
    experts, k, hidden = 32, 4, 64
    whole = lfm2_moe_tiny(vocab_rows_held=VOCAB, num_experts=experts,
                          num_experts_per_tok=k)
    big = seeded(whole)
    params, buffers = dict(F.param_dict(big)), dict(F.buffer_dict(big))
    u = jnp.asarray(np.random.default_rng(7).standard_normal(
        (1, SEQ, hidden)), jnp.float32)
    prefix = "model.layers.1.feed_forward."
    total, pairs, chosen = 0.0, 0, None
    for rank in range(4):
        share = lfm2_moe_tiny(vocab_rows_held=VOCAB, num_experts=experts,
                              num_experts_per_tok=k,
                              experts_held=(8 * rank, 8))
        held = dict(params)
        for w in ("w1", "w3", "w2"):
            name = prefix + "experts." + w
            held[name] = params[name][8 * rank:8 * rank + 8]
        (out, sizes, experts_), _ = F.functional_call(
            _moe_block(Lfm2MoeForCausalLM(share)),
            {n[len(prefix):]: v for n, v in held.items()
             if n.startswith(prefix)},
            {n[len(prefix):]: v for n, v in buffers.items()
             if n.startswith(prefix)}, (paddle.to_tensor(u),))
        total = total + out._value[0]
        pairs += int(sizes._value.sum())
        # every rank routes over all 32 and chooses alike
        assert chosen is None or (chosen == np.asarray(experts_._value)).all()
        chosen = np.asarray(experts_._value)
    assert pairs == SEQ * k and chosen.max() >= 24 and chosen.min() < 8
    both = everything(big)
    p = {key: both[prefix + n[len("feed_forward."):]]
         for key, n in family.FF["moe"].items()}
    want, own, counts = family._moe_ff(
        u[0], p, jnp.zeros((SEQ, k), jnp.int32), top_k=k, first=0,
        scale=1.0, given=False)
    assert int(counts.sum()) == pairs
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(np.asarray(own), -1))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_no_pair_is_dropped_when_the_bias_sends_every_token_to_one_expert():
    """``b`` lifts expert 3 over every other: all tokens choose it, the
    pairs held here overflow the usual window and the later windows run;
    values and gradients are the reference's."""
    tokens, d, f, experts, k = 2048, 16, 8, 64, 2
    rng = np.random.default_rng(8)
    y, router, w1, w3, w2 = (jnp.asarray(rng.standard_normal(s) * scale,
                                         jnp.float32) for s, scale in (
        ((tokens, d), 1.0), ((d, experts), 1.0), ((experts, d, f), 0.3),
        ((experts, d, f), 0.3), ((experts, f, d), 0.3)))
    bias = jnp.zeros((experts,)).at[3].set(10.0)
    first, held = 2, 4
    mine = slice(first, first + held)

    def share(y_, router_, w1_, w3_, w2_):
        chosen, gates = grouped.route_sigmoid(y_ @ router_, bias, k, 1.0,
                                              eps=lfm2_moe.GATE_EPS)
        return grouped.experts_forward(
            y_, chosen, gates, (w1_[mine], w3_[mine], w2_[mine]), first,
            experts)

    def reference(y_, router_, w1_, w3_, w2_):
        p = {"router": router_, "bias": bias, "w1": w1_[mine],
             "w3": w3_[mine], "w2": w2_[mine]}
        out, _, counts = family._moe_ff(
            y_, p, jnp.zeros((tokens, k), jnp.int32), top_k=k, first=first,
            scale=1.0, given=False)
        return out, counts

    args = (y, router, w1, w3, w2)
    out, sizes = share(*args)
    want, counts = reference(*args)
    assert int(sizes[1]) == tokens          # expert 3 is row 1 of the held
    assert int(sizes.sum()) > grouped.usual_rows(tokens, k, held, experts)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(counts))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda *a: (share(*a)[0] ** 2).sum(),
                   argnums=tuple(range(5)))(*args)
    ref = jax.grad(lambda *a: (reference(*a)[0] ** 2).sum(),
                   argnums=tuple(range(5)))(*args)
    for name, a, b in zip(("y", "router", "w1", "w3", "w2"), got, ref):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_router_chooses_by_s_plus_b_weighs_by_s_and_adds_1e_6():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.5],
                          [-20.0, -20.0, -21.0, -22.0]])
    s = jax.nn.sigmoid(logits)
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])        # lifts expert 3
    chosen, gates = grouped.route_sigmoid(logits, bias, 2, 1.0, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  [[3, 0], [3, 2], [3, 0]])
    want = jnp.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        gates, want / (want.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # where the scores are nothing the 1e-6 holds the gates down; the
    # default's 1e-20 leaves them summing to 1 (Nemotron's constant)
    assert float(gates[2].sum()) < 1e-2
    _, plain = grouped.route_sigmoid(logits, bias, 2, 1.0)
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(gates[:2].sum(-1), 1.0, rtol=1e-5)
    # the block passes the family's constant, its scale and its bias
    net = seeded(lfm2_moe_tiny(vocab_rows_held=VOCAB,
                               routed_scaling_factor=1.5))
    block = _moe_block(net)
    y = jnp.asarray(np.random.default_rng(3).standard_normal((1, SEQ, 64)),
                    jnp.float32)
    _, _, experts = block(paddle.to_tensor(y))
    scores, own = family.route(y[0], block.gate.weight._value,
                               block.expert_bias._value, 2)
    np.testing.assert_array_equal(np.sort(np.asarray(experts._value), -1),
                                  np.sort(np.asarray(own), -1))
    plain_choice = jax.lax.top_k(scores, 2)[1]
    assert (np.sort(np.asarray(plain_choice), -1)
            != np.sort(np.asarray(own), -1)).any()      # b chooses
    for bad in (dict(norm_topk_prob=False), dict(use_expert_bias=False),
                dict(conv_bias=True)):
        with pytest.raises(ValueError, match="this family"):
            lfm2_moe_tiny(**bad)


def test_nemotrons_lowered_step_is_the_parents_byte_for_byte(monkeypatch):
    """``route_sigmoid`` gained ``eps`` with the constant it had as its
    default, and the Nemotron model's loss and gradients still carry it.
    The text is no longer pinned to an earlier commit's: the experts' way
    back changed by design, and what stands in its place is the check
    that the change holds.  Lowered as on the chip (the kernels, here
    interpreted), the step takes its results back by the window's held
    rows and holds no array shaped ``[BATCH * SEQ, k, d]``, the per-slot
    form's layout, which the CPU's form, lowered the same, holds."""
    paddle.seed(1)
    config = nemotron_h_tiny(
        vocab_rows_held=VOCAB, experts_held=(2, 4), recompute=(1,),
        router_bias_update_rate=1e-3)
    net = NemotronHForCausalLM(config)
    params, buffers = F.param_dict(net), F.buffer_dict(net)
    ids = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int64)
    slots = (f"tensor<{BATCH * SEQ}x{config.num_experts_per_tok}x"
             f"{config.hidden_size}x")

    def loss(p, b, ids_):
        out, new = F.functional_call(net, p, b, (paddle.to_tensor(ids_),))
        return out._value.astype(jnp.float32).mean(), new

    def lowered():
        return jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
            params, buffers, ids).as_text()

    def held_rows():
        return metrics.registry().counter(
            "moe_combine_calls_total", labels={"form": "held_rows"}).collect()

    text = lowered()
    assert "9.99999968E-21" in text         # the constant, in float32
    assert slots in text                    # the CPU's per-slot form
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    before = held_rows()
    text = lowered()
    assert held_rows() > before
    assert "9.99999968E-21" in text
    assert slots not in text


def test_the_balancing_rule_moves_the_bias_towards_equal_loads(tiny):
    """After a pass in training mode ``b_e`` has moved by the rate towards
    the mean load, over all experts, held here or not; an evaluation and a
    rate of 0 leave it; pass after pass the loads draw level."""
    _, config, ids, _ = tiny
    paddle.seed(4)
    net = Lfm2MoeForCausalLM(dataclasses.replace(
        config, router_bias_update_rate=1e-3, recompute=(1,)))
    name = "model.layers.1.feed_forward.expert_bias"
    params, buffers = F.param_dict(net), F.buffer_dict(net)

    def one_pass(bufs):
        out, new = F.functional_call(net, params, bufs,
                                     (paddle.to_tensor(ids),),
                                     {"output_routing": True})
        load = np.bincount(np.asarray(out[1]._value[0]).reshape(-1),
                           minlength=config.num_experts)
        return load, new, np.asarray(out[1]._value[0])

    load, after, chosen = one_pass(buffers)
    assert load.sum() == BATCH * SEQ * config.num_experts_per_tok
    np.testing.assert_allclose(
        np.asarray(after[name]) - np.asarray(buffers[name]),
        1e-3 * np.sign(load.mean() - load), atol=1e-9)
    np.testing.assert_allclose(
        after[name], family.balanced_bias(buffers[name], chosen, 1e-3),
        atol=1e-9)
    assert not np.asarray(buffers[name]).any()
    first = load
    for _ in range(80):
        load, after, _ = one_pass(after)
    assert load.std() < 0.5 * first.std()
    assert name not in params
    net.eval()
    _, kept, _ = one_pass(after)
    np.testing.assert_array_equal(np.asarray(kept[name]),
                                  np.asarray(after[name]))
    still = Lfm2MoeForCausalLM(config)          # the rate defaults to 0
    _, same = F.functional_call(still, F.param_dict(still),
                                F.buffer_dict(still),
                                (paddle.to_tensor(ids),))
    assert not np.asarray(same[name]).any()


# --------------------------------------------------------------------------
# the runner, a middle stage, the published shapes
# --------------------------------------------------------------------------
def test_it_trains_through_the_runner_under_bf16_o2_with_recompute():
    """The way a user's script does it, as the benchmark's driver does:
    seed -> model -> AdamW -> amp.decorate O2 -> mesh -> runner, the dense
    layer and one expert layer recomputed, the balancing rule on, the
    step's choices kept; the loss falls on a batch seen again and again,
    and the step's counters are published."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(21)
    net = Lfm2MoeForCausalLM(lfm2_moe_tiny(
        vocab_rows_held=VOCAB, experts_held=(0, 4), recompute=(0, 1),
        router_bias_update_rate=1e-3, routing_kept=BATCH * SEQ))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, Lfm2MoePretrainingCriterion(),
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
    assert net.model.embed_tokens.weight._value.dtype == jnp.bfloat16
    bias = net.model.layers[1].feed_forward.expert_bias._value
    assert bias.dtype == jnp.float32
    tokens = np.asarray(net.expert_tokens._value)
    assert tokens.shape == (3, 4) and tokens.sum() > 0
    # the step returns what it chose: the pairs of the held experts are
    # the choices that fall on them
    chosen = np.asarray(net.experts_chosen._value)
    assert chosen.shape == (3, BATCH * SEQ, 2) and chosen.max() >= 4
    np.testing.assert_array_equal(
        tokens, [[(layer == e).sum() for e in range(4)] for layer in chosen])
    # eight steps of the balancing rule, one rate each
    assert np.asarray(bias).any() and np.abs(bias).max() <= 8e-3 + 1e-9
    assert not any(BIAS in n for n, _ in net.named_parameters())
    net.observe_step()
    grew = [pairs(i) - b for i, b in zip(range(4), before)]
    assert grew == [0] + [row.sum() for row in tokens]
    assert net.moe_layers() == (1, 2, 3)
    assert reg.gauge("moe_expert_tokens_max",
                     labels={"layer": "3"}).collect() == tokens[2].max()
    assert [reg.gauge("recompute_layers", labels={"kind": k}).collect()
            for k in ("conv_dense", "conv_moe", "attention_moe")] == [1, 1, 0]
    assert reg.gauge("short_conv_bytes", labels={"layer": "3"}).collect() \
        == SEQ * 64 * 2 * 11
    logits = runner.predict_step([ids])._value
    assert logits.shape == (BATCH, SEQ, VOCAB)
    assert logits.dtype == jnp.bfloat16


def test_a_middle_stage_holds_its_layers_of_layer_types():
    c = Lfm2MoeConfig(vocab_rows_held=VOCAB, hidden_size=64,
                      intermediate_size=96, moe_intermediate_size=32,
                      num_attention_heads=4, num_key_value_heads=2,
                      num_experts=8, num_experts_per_tok=2,
                      layers_held=(1, 5), experts_held=(0, 4))
    # the published pattern: attention at 2, 6, 10, 14, 18, 21 of 24, the
    # first two layers dense
    assert [i for i, t in enumerate(c.layer_types)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert c.layer_types.count("conv") == 18
    assert c.kinds == ("conv_dense", "attention_moe", "conv_moe", "conv_moe",
                       "conv_moe")
    net = Lfm2MoeForCausalLM(c)
    assert net.moe_layers() == (2, 3, 4, 5)
    assert [l.layer_idx for l in net.model.layers] == [1, 2, 3, 4, 5]
    assert [hasattr(l, "conv") for l in net.model.layers] == [
        True, False, True, True, True]
    later = dataclasses.replace(c, layers_held=(6, 6))
    assert later.kinds == ("attention_moe",) + ("conv_moe",) * 3 + (
        "attention_moe", "conv_moe")
    whole = Lfm2MoeConfig()
    assert whole.kinds.count("conv_dense") == 2 and whole.head_dim == 64
    assert whole.kinds.count("conv_moe") == 16
    assert whole.kinds.count("attention_moe") == 6
    # the tie: one matrix, its rows held, and no head of its own
    names = [n for n, _ in net.named_parameters()]
    assert "model.embed_tokens.weight" in names
    assert not [n for n in names if "lm_head" in n]
    assert net.model.embed_tokens.weight.shape == [VOCAB, 64]
    # where the weights start
    paddle.seed(3)
    wide = lfm2_moe.Lfm2ShortConv(lfm2_moe_tiny(hidden_size=512,
                                                num_attention_heads=8), 0)
    taps = np.asarray(wide.conv_weight._value)
    assert np.abs(taps).max() <= 1 / np.sqrt(3) and taps.std() == \
        pytest.approx(1 / 3, rel=0.1)
    assert float(wide.in_proj.weight._value.std()) == pytest.approx(
        0.02, rel=0.05)
    with pytest.raises(ValueError, match="names an operator"):
        lfm2_moe_tiny(layer_types=("conv", "mamba", "conv", "conv"))
    with pytest.raises(ValueError, match="layers 3..7 of 4"):
        lfm2_moe_tiny(layers_held=(3, 4))


def test_the_published_experts_take_the_kernels_on_a_tpu(monkeypatch):
    """``[rows, 2048] x [8, 2048, 1792]`` and its transpose, bf16, on a
    described TPU: the grouped products' Mosaic kernels; on this CPU the
    ragged product."""
    rows = grouped.usual_rows(8192, 4, 8, 32)
    assert rows == 16384
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    up = (spec(rows, 2048), spec(8, 2048, 1792))
    down = (spec(rows, 1792), spec(8, 1792, 2048))
    assert grouped_matmul.form(*up) == grouped_matmul.form(*down) == "xla"
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    assert grouped_matmul.form(*up) == "kernels"
    assert grouped_matmul.form(*down) == "kernels"
    c = Lfm2MoeConfig()
    assert (c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok) == (2048, 1792, 32, 4)
    # attention at the published sizes takes the packed flash kernels
    assert pallas_ops._attention_form(32, 64, 8192, 8192) == "packed"
