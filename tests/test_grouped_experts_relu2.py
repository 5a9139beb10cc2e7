"""``moe/grouped.py``'s squared-ReLU expert form (two matrices, no gate)
against a loop over the experts, forward and backward, under the
sigmoid router: a share of the experts, all of them, and a routing so
uneven that the later windows run.  The SiLU-gated cases stand in
``tests/test_keye_lm.py`` as they were.
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
