"""Solar Open 2 against its plain reference
(``benchmarks/families/solar_open2.py``) at a small size on the CPU, and
what its pieces promise: the chunked gated delta rule is the recurrence a
position at a time, forward and in all five gradients, with beta near 2
and with a decay steep enough that ``e^-g`` of a chunk's sum would
overflow, and so is its Mosaic kernel form (under the interpreter), which
``rule_form`` picks by platform and shape, counting its visits, and which a
recomputed KDA layer at kernel widths trains through as through the XLA
form; recomputing the mixers changes nothing; the shares of heads and
of experts, with the shared expert and the stream counted once, add up to
the uncut layer; the gated grouped-query attention is the plain one times
its gate; the balancing rule draws the loads level; and the model trains
through the runner under bf16 O2.
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
    SolarOpen2ForCausalLM, SolarOpen2PretrainingCriterion, solar_open2_tiny)
from paddle_tpu.models.blocks import PositionFreeAttention    # noqa: E402
from paddle_tpu.models.solar_open2 import SolarOpen2Config    # noqa: E402
from paddle_tpu.observability import metrics                  # noqa: E402
from paddle_tpu.ops import delta_rule, ssm                    # noqa: E402
from benchmarks.families import solar_open2 as family         # noqa: E402

VOCAB, SEQ, BATCH = 64, 48, 2
BIAS = "e_score_correction_bias"


def family_config(c: SolarOpen2Config) -> dict:
    """The program's config under the configuration file's keys."""
    out = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    out.update(vocab_size=c.vocab_rows_held,
               num_hidden_layers=c.layers_held[1],
               num_attention_heads=c.heads_held[1],
               num_key_value_heads=c.kv_heads_held[1],
               n_routed_experts=c.experts_held[1],
               published={"n_routed_experts": c.n_routed_experts})
    return out


def seeded(config, seed=11):
    """A model with seeded weights away from their symmetric start: no
    norm is the identity, no gate's bias 0, and the router's bias is not
    0."""
    paddle.seed(seed)
    net = SolarOpen2ForCausalLM(config)
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name or name.endswith(("g_bias", "dt_bias")):
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


def _param(values):
    return lambda name, rows=None: (values[name] if rows is None
                                    else values[name][rows])


@pytest.fixture(scope="module")
def tiny():
    config = solar_open2_tiny(vocab_rows_held=VOCAB, experts_held=(2, 4),
                              heads_held=(2, 2))
    ids = np.random.default_rng(6).integers(0, VOCAB, (BATCH, SEQ),
                                            dtype=np.int64)
    return seeded(config), config, ids, np.roll(ids, -1, axis=1)


# --------------------------------------------------------------------------
# the delta rule
# --------------------------------------------------------------------------
def _rule_inputs(seq, heads=2, dim=16, steep=1.0, beta_near_2=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    shape = (seq, heads, dim)
    f32 = jnp.float32
    q = unit(jax.random.normal(ks[0], shape, f32)) * dim ** -0.5
    k = unit(jax.random.normal(ks[1], shape, f32))
    v = jax.random.normal(ks[2], shape, f32)
    log_alpha = -steep * jax.nn.softplus(jax.random.normal(
        ks[3], shape, f32)) * jax.random.uniform(ks[4], shape, f32, 0.5, 2.0)
    logits = jax.random.normal(ks[5], (seq, heads), f32)
    beta = 2.0 * jax.nn.sigmoid(8.0 + logits if beta_near_2 else logits)
    w = jax.random.normal(ks[6], shape, f32)
    return (q, k, v, log_alpha, beta), w


CASES = {
    # name: (sequence, chunk, steepness, beta near 2)
    "chunk_64": (128, 64, 1.0, False),
    "chunk_16_beta_near_2": (96, 16, 1.0, True),
    "steep_decay": (128, 64, 120.0, False),
    "gentle_decay": (128, 32, 0.001, False),
    "ragged_length_chunk_8": (45, 8, 1.0, True),
}


@pytest.mark.parametrize("seq, chunk, steep, near_2", CASES.values(),
                         ids=CASES.keys())
def test_the_chunked_rule_is_the_recurrence(seq, chunk, steep, near_2):
    """o and the gradients of ``sum(o * w)`` by q, k, v, log alpha and
    beta, against the recurrence a position at a time (the family's
    ``kda_loop``, written apart from the op)."""
    xs, w = _rule_inputs(seq, steep=steep, beta_near_2=near_2)
    if near_2:
        assert float(xs[4].min()) > 1.95
    if steep > 100:
        # a chunk's sum of log alpha is far below -88: e^-g would overflow
        g = np.cumsum(np.asarray(xs[3][:chunk]), 0)
        assert g.min() < -1000 and not np.isfinite(np.exp(np.float32(
            -g.min())))

    def loss(fn):
        return lambda *a: (fn(*a) * w).sum()

    chunked = jax.jit(jax.value_and_grad(
        loss(lambda *a: delta_rule.gated_delta_rule(*a, chunk)),
        argnums=tuple(range(5))))(*xs)
    want = jax.jit(jax.value_and_grad(loss(family.kda_loop),
                                      argnums=tuple(range(5))))(*xs)
    got_o = delta_rule.gated_delta_rule(*xs, chunk)
    want_o = family.kda_loop(*xs)
    for a, b in zip((got_o,) + chunked[1], (want_o,) + want[1]):
        assert bool(jnp.isfinite(a).all())
        scale = float(jnp.abs(b).max())
        # steep decay: the gradient by log alpha of a forgotten state is a
        # difference of nearly equal float32 sums
        tol = 2e-3 if steep > 100 else 2e-5
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * scale)


def test_the_rule_is_causal_and_its_state_starts_at_zero():
    xs, _ = _rule_inputs(64)
    o = delta_rule.gated_delta_rule(*xs, 16)
    later = tuple(x.at[40:].set(x[40:] * 3.0 + 1.0) if i != 3
                  else x.at[40:].set(-0.5) for i, x in enumerate(xs))
    np.testing.assert_array_equal(
        np.asarray(delta_rule.gated_delta_rule(*later, 16))[:40],
        np.asarray(o)[:40])
    # from a zero state, o_0 = beta_0 (k_0 . q_0) v_0
    q, k, v, _, beta = xs
    np.testing.assert_allclose(
        o[0], beta[0][:, None] * (k[0] * q[0]).sum(-1)[:, None] * v[0],
        rtol=1e-5, atol=1e-6)


def test_the_rule_counts_its_calls_as_they_are_traced():
    """A call counts once as it is traced, differentiated or not; a
    jitted call served from jax's cache counts nothing."""
    calls = metrics.registry().counter("delta_rule_calls_total")
    xs, w = _rule_inputs(32)
    before = calls.collect()
    jax.grad(lambda q: (delta_rule.gated_delta_rule(q, *xs[1:], 16)
                        * w).sum())(xs[0])
    assert calls.collect() - before == 1
    rule = jax.jit(delta_rule.gated_delta_rule, static_argnums=5)
    before = calls.collect()
    rule(*xs, 16)
    rule(*xs, 16)
    assert calls.collect() - before == 1
    with pytest.raises(ValueError, match="sub-chunks"):
        delta_rule.gated_delta_rule(*xs, 24)


def test_the_rule_in_bf16_sums_in_float32():
    """v in bf16 as the layer hands it over: o is bf16 and within a
    rounding of the float32 rule on the same values."""
    xs, _ = _rule_inputs(64)
    half = xs[:2] + (xs[2].astype(jnp.bfloat16),) + xs[3:]
    o = delta_rule.gated_delta_rule(*half, 64)
    assert o.dtype == jnp.bfloat16
    want = delta_rule.gated_delta_rule(
        *(half[:2] + (half[2].astype(jnp.float32),) + half[3:]), 64)
    np.testing.assert_allclose(np.asarray(o, np.float32), want, rtol=1e-2,
                               atol=1e-2 * float(jnp.abs(want).max()))


# --------------------------------------------------------------------------
# the delta rule's Mosaic kernels (ops/delta_rule_kernels.py), interpreted
# --------------------------------------------------------------------------
KERNELS = "PADDLE_TPU_PALLAS_INTERPRET"
# two heads of 128 at chunk 64 are one turn of the kernels' head loop, the
# cell's; the cases share their shapes, and so the kernels' traces
KERNEL_CASES = {
    # name: (sequence, steepness, beta near 2, v in bf16)
    "two_chunks": (128, 1.0, False, False),
    "two_chunks_steep_gates_beta_near_2": (128, 5.0, True, False),
    "one_chunk_bf16_v": (64, 1.0, False, True),
}


def _visits(kind):
    return metrics.registry().counter("delta_rule_kernel_visits_total",
                                      labels={"kind": kind})


@pytest.mark.parametrize("seq, steep, near_2, half", KERNEL_CASES.values(),
                         ids=KERNEL_CASES.keys())
def test_the_kernels_are_the_xla_form_and_the_recurrence(
        monkeypatch, seq, steep, near_2, half):
    """o and the gradients of ``sum(o * w)`` by q, k, v, log alpha and
    beta from the kernels, against the XLA form on the same inputs and
    against the recurrence a position at a time (the family's
    ``kda_loop``): two heads of 128 stacked in a turn, one chunk or two,
    v in bf16, gates steep enough (log alpha down to about -20 a
    position) that ``e^-g`` of a chunk's sum overflows, beta near 2."""
    heads, chunk = 2, 64
    xs, w = _rule_inputs(seq, heads=heads, dim=128, steep=steep,
                         beta_near_2=near_2)
    if half:
        xs = xs[:2] + (xs[2].astype(jnp.bfloat16),) + xs[3:]
    if near_2:
        assert float(xs[4].min()) > 1.95
    if steep > 1:
        g = np.cumsum(np.asarray(xs[3][:chunk]), 0)
        assert g.min() < -88 and float(xs[3].min()) < -15

    def weighted(*a):
        o = delta_rule.gated_delta_rule(*a, chunk)
        return (o.astype(jnp.float32) * w).sum(), o

    def run():
        form = delta_rule.rule_form(seq, heads, 128, 128, chunk)
        grads, o = jax.jit(jax.grad(weighted, argnums=tuple(range(5)),
                                    has_aux=True))(*xs)
        return form, (o,) + grads

    monkeypatch.setenv(KERNELS, "1")
    before = _visits("bwd").collect()
    form, got = run()
    assert form == "kernels"
    assert _visits("bwd").collect() - before == seq // chunk * heads
    monkeypatch.setenv(KERNELS, "")
    form, xla = run()
    assert form == "xla"
    want = family.reference_kda_grads(
        *(x.astype(jnp.float32) for x in xs), w)
    # bf16 v: o and dv are bf16, and the cotangent of o is rounded to it
    tol = 1e-2 if half else 2e-4 if steep > 1 else 2e-5
    for name, a, b, c in zip(("o", "dq", "dk", "dv", "dlog_alpha", "dbeta"),
                             got, xla, want):
        assert a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        for other in (b, c):
            other = np.asarray(other, np.float32)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), other, rtol=0,
                atol=tol * float(np.abs(other).max()), err_msg=name)


@pytest.mark.parametrize("shape, interpreted, form", [
    ((8192, 8, 128, 128, 64), "1", "kernels"),   # the cell's
    ((8192, 8, 128, 128, 64), "", "xla"),        # the CPU
    ((8192, 8, 64, 128, 64), "1", "xla"),        # keys no lane group
    ((8192, 8, 128, 96, 64), "1", "xla"),        # values no lane group
    ((8100, 8, 128, 128, 64), "1", "xla"),       # no whole chunks
    ((8192, 8, 128, 128, 8), "1", "xla"),        # under a sub-chunk
    ((8192, 64, 256, 256, 64), "1", "xla"),      # a visit over VMEM
], ids=["cell", "cpu", "keys_64", "values_96", "ragged", "chunk_8",
        "over_vmem"])
def test_the_rule_form_follows_platform_and_shape(monkeypatch, shape,
                                                  interpreted, form):
    monkeypatch.setenv(KERNELS, interpreted)
    assert delta_rule.rule_form(*shape) == form


def test_the_kernels_count_their_visits_as_they_are_traced(monkeypatch):
    """At the cell's shape a kernel call is 128 chunks of 8 heads, 1 024
    visits, forward and back; where the XLA form runs, none."""
    f32 = jnp.float32
    spec = jax.ShapeDtypeStruct((8192, 8, 128), f32)
    args = (spec, spec, jax.ShapeDtypeStruct((8192, 8, 128), jnp.bfloat16),
            spec, jax.ShapeDtypeStruct((8192, 8), f32))
    do = jax.ShapeDtypeStruct((8192, 8, 128), jnp.bfloat16)

    def differentiated(*a):
        return jax.vjp(lambda *b: delta_rule.gated_delta_rule(*b, 64),
                       *a[:5])[1](a[5])

    calls = metrics.registry().counter("delta_rule_calls_total")
    for interpreted, visits in (("1", 1024), ("", 0)):
        monkeypatch.setenv(KERNELS, interpreted)
        before = [c.collect() for c in (_visits("fwd"), _visits("bwd"),
                                        calls)]
        # a function of its own each time: jax keeps the trace of one
        jax.eval_shape(lambda *a: differentiated(*a), *args, do)
        assert [c.collect() - b for c, b in zip(
            (_visits("fwd"), _visits("bwd"), calls), before)] == [
                visits, visits, 1]


def test_a_recomputed_kda_layer_at_kernel_widths_is_the_xla_forms(
        monkeypatch):
    """A KDA mixer of two heads of 128 (so that the rule takes its kernels
    under the interpreter; the convolution keeps its XLA form on both
    sides), through ``fleet.recompute``: the loss and every gradient are
    the XLA form's.
    ``A_log``'s gradient, a sum over every position and key channel of a
    head whose terms cancel to about a hundredth of their size, is held
    to 2e-3 of its largest value, the others to 1e-4."""
    config = solar_open2_tiny(
        head_dim=128, linear_attn_config={"short_conv_kernel_size": 4,
                                          "head_dim": 128, "num_heads": 4,
                                          "num_kv_heads": None},
        heads_held=(0, 2), layers_held=(1, 1), recompute=(0,))
    mixer = seeded(config).model.layers[0].mixer
    assert mixer.kind == "kda" and mixer.recomputed
    monkeypatch.setattr(ssm, "conv_form", lambda *a: "xla")
    params = F.param_dict(mixer)
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)

    def loss(p, x):
        out, _ = F.functional_call(mixer, p, {}, (paddle.to_tensor(x),))
        return (out._value * w).sum()

    def run(interpreted):
        monkeypatch.setenv(KERNELS, interpreted)
        before = _visits("bwd").collect()
        value, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params, h)
        return value, grads, _visits("bwd").collect() - before

    value, grads, visits = run("1")
    want_value, want, none = run("")
    assert (visits, none) == (2 * 2, 0)
    np.testing.assert_allclose(float(value), float(want_value), rtol=1e-4)
    for name in want[0]:
        scale = float(jnp.abs(want[0][name]).max())
        tol = 2e-3 if name.endswith("A_log") else 1e-4
        np.testing.assert_allclose(grads[0][name], want[0][name], rtol=0,
                                   atol=tol * scale, err_msg=name)
    np.testing.assert_allclose(grads[1], want[1], rtol=0,
                               atol=1e-4 * float(jnp.abs(want[1]).max()))


# --------------------------------------------------------------------------
# the model against the reference
# --------------------------------------------------------------------------
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
    assert config.kinds == family.kinds(cfg) == ("gqa", "kda", "kda", "kda")
    biases = {n: v for n, v in everything(net).items() if n not in params}
    assert len(biases) == 4
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
        ref = family.reference_forward(_param(both), cfg, jnp.asarray(ids[b]))
        np.testing.assert_allclose(
            logits[b], family.reference_logits(ref["hidden"],
                                               params[family.HEAD].T),
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


def test_the_layer_gradients_given_the_routing_are_the_whole_models(tiny):
    """``reference_layer_grads`` (what ``train_solar_lm``'s check (f) holds the
    step against) for the GQA layer and the first KDA layer is the whole
    reference's gradient for their parameters."""
    net, config, ids, labels = tiny
    cfg = family_config(config)
    both = everything(net)
    params = F.param_dict(net)
    biases = {n: v for n, v in both.items() if n not in params}
    layers = family.checked_layers(cfg)
    assert layers == (0, 1)
    ref = family.reference_forward(_param(both), cfg, jnp.asarray(ids[0]))
    got = family.reference_layer_grads(
        _param(both), cfg, jnp.asarray(ids[0]), jnp.asarray(labels[0]),
        ref["experts"], layers)
    want = jax.grad(lambda p: family.reference_loss(
        {**p, **biases}, cfg, jnp.asarray(ids[:1]),
        jnp.asarray(labels[:1])))(params)
    assert set(got) == {n for l in layers
                        for n in family.layer_parameters(cfg, l)}
    for name, g in got.items():
        np.testing.assert_allclose(g, want[name], rtol=1e-4,
                                   atol=1e-6 * float(jnp.abs(g).max()),
                                   err_msg=name)


@pytest.mark.parametrize("layers", [(0,), (1, 3), (0, 1, 2, 3)],
                         ids=["gqa", "kda", "every_mixer"])
def test_recomputing_the_mixers_changes_nothing(tiny, layers):
    net, config, ids, labels = tiny
    again = SolarOpen2ForCausalLM(dataclasses.replace(config,
                                                      recompute=layers))
    params, buffers = F.param_dict(net), F.buffer_dict(net)

    def all_of(model):
        return jax.value_and_grad(
            lambda p: program_loss(model, p, ids, labels, buffers),
            has_aux=True)(params)

    ((loss, (_, tokens)), grads) = all_of(again)
    by_kind = {kind: metrics.registry().gauge(
        "recompute_layers", labels={"kind": kind}).collect()
        for kind in ("gqa", "kda")}
    ((want_loss, (_, want_tokens)), want) = all_of(net)
    assert float(loss) == float(want_loss)
    np.testing.assert_array_equal(np.asarray(tokens),
                                  np.asarray(want_tokens))
    for name in want:
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)
    assert by_kind == {kind: sum(config.kinds[i] == kind for i in layers)
                       for kind in ("gqa", "kda")}
    # the expert half is never recomputed: only the mixers are wrapped
    assert all(l.mixer.recomputed == (i in layers)
               for i, l in enumerate(again.model.layers))
    with pytest.raises(ValueError, match="index among"):
        dataclasses.replace(config, recompute=(4,))


# --------------------------------------------------------------------------
# the shares of a deployment
# --------------------------------------------------------------------------
def _head_share(params, prefix, kind, first, count, dim, group):
    """The uncut layer's mixer parameters as a rank that holds heads
    ``first .. first + count`` has them."""
    cols = np.arange(first * dim, (first + count) * dim)
    kv_cols = np.arange(first // group * dim, (first + count) // group * dim)
    width = params[prefix + "q_proj.weight"].shape[1]
    by_column = {"q_proj.weight": cols, "g_proj.weight": cols,
                 "f_b_proj.weight": cols, "g_b_proj.weight": cols,
                 "b_proj.weight": np.arange(first, first + count),
                 "k_proj.weight": cols if kind == "kda" else kv_cols,
                 "v_proj.weight": cols if kind == "kda" else kv_cols}
    by_row = {"o_proj.weight": cols, "dt_bias": cols, "g_bias": cols,
              "A_log": np.arange(first, first + count),
              "conv_weight": np.concatenate([cols + i * width
                                             for i in range(3)])}
    out = {}
    for name, value in params.items():
        if name.startswith(prefix):
            key = name[len(prefix):]
            if key in by_column:
                value = value[:, by_column[key]]
            elif key in by_row:
                value = value[by_row[key]]
            out[key] = value
    return out


@pytest.mark.parametrize("layer", [0, 1], ids=["gqa", "kda"])
def test_the_shares_add_up_to_the_uncut_layer(layer):
    """Two ranks hold two of four heads each, two ranks four of eight
    experts each.  The heads' parts of the mixer's output summed, with the
    stream counted once, then the experts' parts summed on that stream,
    with the shared expert counted once, are the reference's uncut layer:
    what every rank computes alike is counted once."""
    hidden, k = 64, 2
    whole = solar_open2_tiny(vocab_rows_held=VOCAB)
    big = seeded(whole)
    both = everything(big)
    params = dict(F.param_dict(big))
    cfg = family_config(whole)
    kind = whole.kinds[layer]
    h = jnp.asarray(np.random.default_rng(7).standard_normal(
        (1, SEQ, hidden)), jnp.float32)
    layer_prefix = f"model.layers.{layer}."
    mixer_at = layer_prefix + family.MIXER_AT[kind]
    u = family._norm(h[0], both[layer_prefix + "mixer.input_layernorm.weight"],
                     eps=whole.rms_norm_eps)
    mixed = h[0]
    for first in (0, 2):
        share = SolarOpen2ForCausalLM(solar_open2_tiny(
            vocab_rows_held=VOCAB, heads_held=(first, 2)))
        mixer = share.model.layers[layer].mixer
        module = mixer.self_attn if kind == "gqa" else mixer.linear_attn
        out, _ = F.functional_call(
            module, _head_share(params, mixer_at, kind, first, 2, 16, 2), {},
            (paddle.to_tensor(u[None]),))
        mixed = mixed + out._value[0]
    routed, chosen = 0.0, None
    y = family._norm(mixed, both[layer_prefix
                                 + "post_attention_layernorm.weight"],
                     eps=whole.rms_norm_eps)
    moe_at = layer_prefix + "mlp."
    with jax.default_matmul_precision("highest"):
        shared = (jax.nn.silu(y @ params[moe_at + "shared_gate.weight"])
                  * (y @ params[moe_at + "shared_up.weight"])) \
            @ params[moe_at + "shared_down.weight"]
    for first in (0, 4):
        share = SolarOpen2ForCausalLM(solar_open2_tiny(
            vocab_rows_held=VOCAB, experts_held=(first, 4)))
        block = share.model.layers[layer].mlp
        held = {n[len(moe_at):]: v for n, v in params.items()
                if n.startswith(moe_at)}
        for w in ("w1", "w3", "w2"):
            held["experts." + w] = held["experts." + w][first:first + 4]
        (out, sizes, experts), _ = F.functional_call(
            block, held, {BIAS: both[moe_at + BIAS]},
            (paddle.to_tensor(y[None]),))
        # every rank adds the shared expert: its routed part is the rest
        routed = routed + out._value[0] - shared
        assert chosen is None or (chosen == np.asarray(experts._value)).all()
        chosen = np.asarray(experts._value)
    total = mixed + routed + shared
    want = family.reference_forward(_param(both), cfg, None, layers=(
        layer, layer + 1), stream=h[0])["stream"]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_the_gated_attention_is_the_plain_one_times_its_gate():
    """``PositionFreeAttention(gated=True)``: ``(attention * sigmoid(x
    W_g)) W_o``, against the plain attention of the same layer without
    its gate; without ``gated`` the layer holds the four matrices it
    always held, in their order."""
    paddle.seed(3)
    gated = PositionFreeAttention(32, 4, 2, 8, 0.02, 0.02, gated=True)
    plain = PositionFreeAttention(32, 4, 2, 8, 0.02, 0.02)
    assert [n for n, _ in plain.named_parameters()] == [
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight"]
    assert [n for n, _ in gated.named_parameters()][-1] == "g_proj.weight"
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 16, 32)),
                    jnp.float32)
    params = F.param_dict(gated)
    got, _ = F.functional_call(gated, params, {}, (paddle.to_tensor(x),))
    # the plain layer's output before W_o: W_o the identity
    heads = {n: v for n, v in params.items() if n != "g_proj.weight"}
    heads["o_proj.weight"] = jnp.eye(32, dtype=jnp.float32)
    attended, _ = F.functional_call(plain, heads, {},
                                    (paddle.to_tensor(x),))
    gate = jax.nn.sigmoid(x @ params["g_proj.weight"])
    np.testing.assert_allclose(
        got._value, (attended._value * gate) @ params["o_proj.weight"],
        rtol=1e-5, atol=1e-7)


# --------------------------------------------------------------------------
# the router's bias, the runner
# --------------------------------------------------------------------------
def test_the_balancing_rule_moves_the_bias_towards_equal_loads(tiny):
    """After a pass in training mode ``b_e`` has moved by the rate towards
    the mean load, over all 8 experts, held here or not; an evaluation
    leaves it; pass after pass the loads draw level."""
    _, config, ids, _ = tiny
    paddle.seed(4)
    net = SolarOpen2ForCausalLM(dataclasses.replace(
        config, router_bias_update_rate=1e-3, recompute=(1,)))
    name = "model.layers.1.mlp." + BIAS
    params, buffers = F.param_dict(net), F.buffer_dict(net)

    def one_pass(bufs):
        out, new = F.functional_call(net, params, bufs,
                                     (paddle.to_tensor(ids),),
                                     {"output_routing": True})
        chosen = np.asarray(out[1]._value[1])
        load = np.bincount(chosen.reshape(-1),
                           minlength=config.n_routed_experts)
        return load, new, chosen

    load, after, chosen = one_pass(buffers)
    assert load.sum() == BATCH * SEQ * config.num_experts_per_tok
    np.testing.assert_allclose(
        np.asarray(after[name]) - np.asarray(buffers[name]),
        1e-3 * np.sign(load.mean() - load), atol=1e-9)
    np.testing.assert_allclose(
        after[name], family.balanced_bias(buffers[name], chosen, 1e-3),
        atol=1e-9)
    first = load
    @jax.jit
    def again(bufs):
        """The same pass as one program (traced in training mode)."""
        out, new = F.functional_call(net, params, bufs,
                                     (paddle.to_tensor(ids),),
                                     {"output_routing": True})
        return out[1]._value[1], new

    for _ in range(80):
        chosen, after = again(after)
    load = np.bincount(np.asarray(chosen).reshape(-1),
                       minlength=config.n_routed_experts)
    assert load.std() < 0.5 * first.std()
    assert name not in params
    net.eval()
    _, kept, _ = one_pass(after)
    np.testing.assert_array_equal(np.asarray(kept[name]),
                                  np.asarray(after[name]))


def test_it_trains_through_the_runner_under_bf16_o2_with_recompute():
    """The way a user's script does it, as the benchmark's driver does:
    seed -> model -> AdamW -> amp.decorate O2 -> mesh -> runner, every
    mixer recomputed, the balancing rule on, the step's choices kept; the
    loss falls on a batch seen again and again, and the step's counters
    are published."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(21)
    net = SolarOpen2ForCausalLM(solar_open2_tiny(
        vocab_rows_held=VOCAB, experts_held=(0, 4), heads_held=(0, 2),
        recompute=(0, 1, 2, 3), router_bias_update_rate=1e-3,
        routing_kept=BATCH * SEQ))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    collective.set_mesh(mesh)
    runner = DistributedRunner(net, opt, SolarOpen2PretrainingCriterion(),
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
    assert net.model.layers[0].mlp.e_score_correction_bias._value.dtype \
        == jnp.float32
    tokens = np.asarray(net.expert_tokens._value)
    assert tokens.shape == (4, 4) and tokens.sum() > 0
    chosen = np.asarray(net.experts_chosen._value)
    assert chosen.shape == (4, BATCH * SEQ, 2) and chosen.max() >= 4
    np.testing.assert_array_equal(
        tokens, [[(layer == e).sum() for e in range(4)] for layer in chosen])
    net.observe_step()
    grew = [pairs(i) - b for i, b in zip(range(4), before)]
    assert grew == [row.sum() for row in tokens]
    assert net.moe_layers() == (0, 1, 2, 3)
    assert [reg.gauge("recompute_layers", labels={"kind": k}).collect()
            for k in ("gqa", "kda")] == [1, 3]
    logits = runner.predict_step([ids])._value
    assert logits.shape == (BATCH, SEQ, VOCAB)


def test_a_later_stage_holds_its_layers_of_gqa_layers():
    c = SolarOpen2Config(layers_held=(4, 4), heads_held=(8, 8),
                         experts_held=(8, 8), vocab_rows_held=24576)
    assert c.kinds == ("gqa", "kda", "kda", "kda")
    assert c.kv_heads_held == (1, 1)
    assert SolarOpen2Config(layers_held=(45, 3)).kinds == ("kda",) * 3
    with pytest.raises(ValueError, match="whole groups"):
        SolarOpen2Config(heads_held=(0, 4))
    with pytest.raises(ValueError, match="untied head"):
        SolarOpen2Config(tie_word_embeddings=True)
