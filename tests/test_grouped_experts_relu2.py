"""``moe/grouped.py``'s squared-ReLU expert form (two matrices, no gate)
against a loop over the experts, forward and backward, under the
sigmoid router: a share of the experts, all of them, and a routing so
uneven that the later windows run.  Its way back to the tokens by the
window's held rows against every token's k slots, forward and
gradients.  The SiLU-gated cases stand in ``tests/test_keye_lm.py`` as
they were, and their way back in ``tests/test_moe.py``.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                    # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import grouped  # noqa: E402
from paddle_tpu.observability import metrics                  # noqa: E402
from paddle_tpu.ops import token_rows                         # noqa: E402

TOKENS, D, F, EXPERTS, K = 1024, 16, 8, 32, 4


def layer(seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(s) * scale, jnp.float32)
                 for s, scale in (((TOKENS, D), 1.0), ((D, EXPERTS), 1.0),
                                  ((EXPERTS, D, F), 0.3),
                                  ((EXPERTS, F, D), 0.3)))


def by_loop(y, router, w1, w2, bias, first, held):
    """Every held expert on every token, weighed by the gate where the
    token chose it."""
    s = jax.nn.sigmoid(y @ router)
    _, chosen = jax.lax.top_k(s + bias, K)
    picked = jnp.take_along_axis(s, chosen, -1)
    gates = 2.5 * picked / picked.sum(-1, keepdims=True)
    out, counts = jnp.zeros_like(y), []
    for e in range(first, first + held):
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
        hidden = jnp.maximum(y @ w1[e], 0.0) ** 2
        out = out + weight[:, None] * (hidden @ w2[e])
        counts.append((chosen == e).sum())
    return out, jnp.stack(counts)


def by_windows(y, router, w1, w2, bias, first, held):
    chosen, gates = grouped.route_sigmoid(y @ router, bias, K, 2.5)
    return grouped.experts_forward(
        y, chosen, gates, (w1[first:first + held], w2[first:first + held]),
        first, EXPERTS)


@pytest.mark.parametrize("first, held, lifted", [
    pytest.param(0, EXPERTS, None, id="every_expert"),
    pytest.param(8, 4, None, id="a_share_of_four"),
    pytest.param(8, 4, 9, id="every_token_on_one_held_expert"),
    pytest.param(8, 4, 3, id="every_token_on_an_absent_expert"),
])
def test_relu2_experts_are_the_loop_forward_and_backward(first, held, lifted):
    y, router, w1, w2 = layer(first + held)
    bias = jnp.zeros((EXPERTS,))
    if lifted is not None:
        bias = bias.at[lifted].set(10.0)
    out, sizes = by_windows(y, router, w1, w2, bias, first, held)
    want, counts = by_loop(y, router, w1, w2, bias, first, held)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(counts))
    usual = grouped.usual_rows(TOKENS, K, held, EXPERTS)
    if lifted is not None and first <= lifted < first + held:
        assert int(sizes[lifted - first]) == TOKENS     # none dropped
        assert int(sizes.sum()) > usual                 # later windows
    elif held < EXPERTS:
        assert int(sizes.sum()) <= usual
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(out.shape),
                    jnp.float32)
    got = jax.grad(lambda *a: (by_windows(*a, bias, first, held)[0]
                               * w).sum(), argnums=(0, 1, 2, 3))(
        y, router, w1, w2)
    ref = jax.grad(lambda *a: (by_loop(*a, bias, first, held)[0] * w).sum(),
                   argnums=(0, 1, 2, 3))(y, router, w1, w2)
    for name, a, b in zip(("y", "router", "w1", "w2"), got, ref):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-4 * float(jnp.abs(b).max()),
            err_msg=name)
    # experts that are not held take no gradient
    absent = np.ones(EXPERTS, bool)
    absent[first:first + held] = False
    assert float(jnp.abs(got[2][absent]).max(initial=0.0)) == 0.0


def test_the_layer_holds_two_matrices_and_is_told_its_experts():
    paddle.seed(2)
    experts = grouped.GroupedRelu2Experts(D, F, EXPERTS, first=8, held=4)
    assert [n for n, _ in experts.named_parameters()] == ["w1", "w2"]
    assert experts.w1.shape == [4, D, F] and experts.w2.shape == [4, F, D]
    y, router, _, _ = layer(3)
    chosen, gates = grouped.route_sigmoid(y @ router, jnp.zeros(EXPERTS), K,
                                          2.5)
    out, sizes = experts(paddle.to_tensor(y), paddle.to_tensor(chosen),
                         paddle.to_tensor(gates))
    want, want_sizes = grouped.experts_forward(
        y, chosen, gates, (experts.w1._value, experts.w2._value), 8, EXPERTS)
    np.testing.assert_array_equal(np.asarray(sizes._value),
                                  np.asarray(want_sizes))
    np.testing.assert_allclose(out._value, want, rtol=1e-6)
    assert out._value.dtype == jnp.float32
    with pytest.raises(ValueError, match="experts 30..34 of 32"):
        grouped.GroupedRelu2Experts(D, F, EXPERTS, first=30, held=4)
    # the activation keeps its operand and nothing else for the backward
    up = jnp.asarray([[-1.0, 0.0, 2.0]], jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(grouped._relu2(up).astype(jnp.float32)), [[0.0, 0.0, 4.0]])
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda u: grouped._relu2(u).astype(
            jnp.float32).sum())(up).astype(jnp.float32)), [[0.0, 0.0, 4.0]])


def _combine_calls():
    return {form: metrics.registry().counter(
        "moe_combine_calls_total", labels={"form": form}).collect()
        for form in ("held_rows", "per_slot")}


def _value_and_grads(y, chosen, gates, weights, first):
    probe = jnp.asarray(np.random.default_rng(5).standard_normal(y.shape),
                        jnp.float32)

    def loss(y_, gates_, *weights_):
        out, _ = grouped.experts_forward(y_, chosen, gates_, weights_, first,
                                         EXPERTS)
        return (out * probe).sum(), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=tuple(range(2 + len(weights))), has_aux=True)(
            y, gates, *weights)
    return out, grads


@pytest.mark.parametrize("lifted", [None, 9],
                         ids=["rows_past_the_count", "later_windows_run"])
def test_held_rows_form_is_the_per_slot_form(monkeypatch, lifted):
    """Under the interpreter the window's rows go back to their tokens by
    ``ops/token_rows.py``'s kernel (the counter says so: the first
    window's combine and dispatch, the later windows' under the scan, and
    their forward again in the backward pass); the result and the
    gradients for y, the gates and both matrices are the per-slot form's.
    Uniform routing leaves tokens with several held pairs, tokens with
    none, and rows past the count; one lifted expert overflows the
    window."""
    first, held = 8, 4
    y, router, w1, w2 = layer(20)
    bias = jnp.zeros((EXPERTS,))
    if lifted is not None:
        bias = bias.at[lifted].set(10.0)
    chosen, gates = grouped.route_sigmoid(y @ router, bias, K, 2.5)
    weights = (w1[first:first + held], w2[first:first + held])
    mine = ((chosen >= first) & (chosen < first + held)).sum(1)
    usual = grouped.usual_rows(TOKENS, K, held, EXPERTS)
    assert (int(mine.sum()) > usual) == (lifted is not None)
    if lifted is None:
        assert int(mine.min()) == 0 and int(mine.max()) >= 2
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    before = _combine_calls()
    out, grads = _value_and_grads(y, chosen, gates, weights, first)
    after = _combine_calls()
    assert {f: after[f] - before[f] for f in after} == {
        "held_rows": 5, "per_slot": 0}
    monkeypatch.setattr(token_rows, "form", lambda *a: "xla")
    want, want_grads = _value_and_grads(y, chosen, gates, weights, first)
    assert _combine_calls()["per_slot"] - after["per_slot"] == 5
    np.testing.assert_allclose(out, want, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(want).max()))
    for name, a, b in zip(("y", "gates", "w1", "w2"), grads, want_grads):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_combine_counter_names_the_form_that_ran(monkeypatch):
    """``moe_combine_calls_total{form}`` at the Nemotron cell's shape (8
    of 128 experts, 6 a token, a window of 6 144 rows for 49 152 slots):
    the CPU traces the per-slot form five times a layer's step, the
    kernels' platform (here the interpreter) the held-rows form, and the
    other label reads 0."""
    shapes = (jax.ShapeDtypeStruct((8192, 2688), jnp.bfloat16),
              jax.ShapeDtypeStruct((8192, 6), jnp.int32),
              jax.ShapeDtypeStruct((8192, 6), jnp.float32),
              (jax.ShapeDtypeStruct((8, 2688, 1856), jnp.bfloat16),
               jax.ShapeDtypeStruct((8, 1856, 2688), jnp.bfloat16)))

    def traced():
        before = _combine_calls()
        jax.eval_shape(jax.grad(
            lambda y, c, g, w: grouped.experts_forward(
                y, c, g, w, 0, 128)[0].sum(), argnums=(0, 2, 3)), *shapes)
        after = _combine_calls()
        return {f: after[f] - before[f] for f in after}

    assert grouped.usual_rows(8192, 6, 8, 128) == 6144
    assert traced() == {"held_rows": 0, "per_slot": 5}
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced() == {"held_rows": 5, "per_slot": 0}
