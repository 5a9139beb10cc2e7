"""MoE / expert-parallel tests (SURVEY.md §2.2 "EP"; upstream tests:
test/collective/fleet test_moe_* — here single-process SPMD on the
virtual 8-device CPU mesh, per §4 "lessons")."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, ops
from paddle_tpu.incubate.distributed.models.moe import (
    ExpertLayer, GShardGate, GroupedExpertsFFN, MoELayer, NaiveGate,
    SwitchGate, global_gather, global_scatter)
from paddle_tpu.incubate.distributed.models.moe import grouped
from paddle_tpu.observability import metrics
from paddle_tpu.ops import token_rows

pytestmark = pytest.mark.dist


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


def test_gate_shapes_and_capacity():
    paddle.seed(0)
    g = GShardGate(16, num_experts=4)
    x = paddle.randn([32, 16])
    combine, dispatch = g(x)
    assert list(combine.shape) == [32, 4, g.capacity(32)]
    d = np.asarray(dispatch.numpy())
    # ≤ capacity tokens per expert slot-buffer, one slot per token
    assert d.sum(axis=(0, 2)).max() <= g.capacity(32)
    assert (d.sum(axis=(1, 2)) <= g.top_k + 1e-6).all()
    # combine weights of one token sum to ≤ 1 (normalised over kept)
    c = np.asarray(combine.numpy()).sum(axis=(1, 2))
    assert (c <= 1.0 + 1e-5).all()
    assert g.loss is not None and np.isfinite(float(g.loss))


def test_switch_gate_top1():
    paddle.seed(0)
    g = SwitchGate(8, num_experts=4)
    x = paddle.randn([16, 8])
    combine, dispatch = g(x)
    d = np.asarray(dispatch.numpy())
    assert (d.sum(axis=(1, 2)) <= 1 + 1e-6).all()


def test_moe_layer_listed_experts_forward_backward():
    paddle.seed(0)
    experts = [ExpertLayer(16, 32) for _ in range(4)]
    moe = MoELayer(d_model=16, experts=experts, gate="gshard")
    x = paddle.randn([2, 8, 16])
    x.stop_gradient = False
    y = moe(x)
    assert list(y.shape) == [2, 8, 16]
    loss = (y * y).mean() + moe.l_aux
    loss.backward()
    got = [p.name or i for i, p in enumerate(moe.parameters())
           if p.grad is not None]
    # gate weight and at least some expert weights get gradients
    assert moe.gate.weight.grad is not None
    assert any(e.htoh4.weight.grad is not None for e in experts)


def test_moe_grouped_experts_matches_loop():
    """Grouped-GEMM expert path == loop-of-experts with same weights."""
    paddle.seed(0)
    grouped = GroupedExpertsFFN(4, 8, 16)
    dispatched = paddle.randn([4, 6, 8])
    out_g = grouped(dispatched).numpy()
    for e in range(4):
        h = np.asarray(dispatched[e].numpy()) @ \
            np.asarray(grouped.w1[e].numpy()) + \
            np.asarray(grouped.b1[e].numpy())
        h = np.asarray(ops.gelu(paddle.to_tensor(h)).numpy())
        ref = h @ np.asarray(grouped.w2[e].numpy()) + \
            np.asarray(grouped.b2[e].numpy())
        np.testing.assert_allclose(out_g[e], ref, rtol=2e-4, atol=2e-4)


def test_moe_expert_parallel_parity_on_mesh():
    """EP over the 'mp' axis gives the same result as dense 1-chip."""
    _need_devices(8)
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.communication import Group
    paddle.seed(0)
    moe = MoELayer(d_model=8, num_experts=8, d_hidden=16, gate="gshard",
                   moe_group=Group(list(range(4)), axis_name="mp"))
    x = paddle.randn([4, 4, 8])

    dense = moe(x).numpy()          # no mesh → annotation is a no-op

    mesh = collective.build_mesh({"mp": 4})
    collective.set_mesh(mesh)
    from paddle_tpu.nn import functional_call as F
    params = F.param_dict(moe)

    def fwd(p, xv):
        with F.bind(moe, p, F.buffer_dict(moe), F.frozen_dict(moe)):
            return moe(paddle.Tensor(xv))._value

    with mesh:
        sharded = jax.jit(fwd)(params, x._value)
    np.testing.assert_allclose(dense, np.asarray(sharded), rtol=1e-4,
                               atol=1e-4)


def test_global_scatter_gather_roundtrip_on_mesh():
    _need_devices(8)
    from paddle_tpu.distributed.shard_map_compat import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import collective
    mesh = collective.build_mesh({"mp": 8})
    x = np.random.RandomState(0).randn(8, 4, 2).astype(np.float32)

    def f(xv):
        s = global_scatter.raw(xv, axis_name="mp")
        return global_gather.raw(s, axis_name="mp")

    out = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6, atol=1e-6)


def test_moe_in_transformer_block_trains():
    """MoE-FFN transformer block end-to-end small train loop."""
    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.attn_norm = nn.LayerNorm(16)
            self.moe = MoELayer(d_model=16, num_experts=4, d_hidden=32,
                                gate="switch")
            self.head = nn.Linear(16, 4)

        def forward(self, x):
            h = self.moe(self.attn_norm(x))
            return self.head(h.mean(axis=1))

    net = Block()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    x = paddle.randn([8, 6, 16])
    y = paddle.to_tensor(np.random.RandomState(0).randint(0, 4, (8,)))
    losses = []
    for _ in range(5):
        logits = net(x)
        loss = nn.functional.cross_entropy(logits, y).mean() \
            + 0.01 * net.moe.l_aux
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _combine_calls():
    return {form: metrics.registry().counter(
        "moe_combine_calls_total", labels={"form": form}).collect()
        for form in ("held_rows", "per_slot")}


@pytest.mark.parametrize("lifted", [None, 9],
                         ids=["rows_past_the_count", "later_windows_run"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_grouped_swiglu_experts_go_back_by_held_rows(monkeypatch, router,
                                                     lifted):
    """``GroupedSwiGLUExperts`` under the softmax router (Keye's pairing)
    and the sigmoid router (LFM2's): with the kernels interpreted the
    window's rows go back to their tokens by ``ops/token_rows.py`` (the
    counter says so), and the layer's result and the gradients for y,
    the gates and all three matrices are the per-slot form's.  Uniform
    routing leaves tokens with several held pairs, tokens with none and
    rows past the count; one lifted expert overflows the window."""
    tokens, d, f, experts, k, first, held = 1024, 16, 8, 32, 4, 8, 4
    paddle.seed(11)
    layer = grouped.GroupedSwiGLUExperts(d, f, experts, first, held,
                                         initializer_range=0.3)
    rng = np.random.default_rng(12)
    y = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((tokens, experts)), jnp.float32)
    if lifted is not None:
        logits = logits.at[:, lifted].add(20.0)
    if router == "softmax":
        chosen, gates = grouped.route(logits, k)
    else:
        chosen, gates = grouped.route_sigmoid(logits, jnp.zeros(experts), k,
                                              1.0)
    weights = tuple(m._value for m in (layer.w1, layer.w3, layer.w2))
    mine = ((chosen >= first) & (chosen < first + held)).sum(1)
    usual = grouped.usual_rows(tokens, k, held, experts)
    assert (int(mine.sum()) > usual) == (lifted is not None)
    if lifted is None:
        assert int(mine.min()) == 0 and int(mine.max()) >= 2
    probe = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)

    def loss(y_, gates_, *weights_):
        out, _ = grouped.experts_forward(y_, chosen, gates_, weights_, first,
                                         experts)
        return (out * probe).sum(), out

    run = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    before = _combine_calls()
    out, _ = layer(paddle.to_tensor(y), paddle.to_tensor(chosen),
                   paddle.to_tensor(gates))
    (_, got), got_grads = run(y, gates, *weights)
    after = _combine_calls()
    # the layer's forward: the first window and the later ones under the
    # scan; the step's the same, their dispatch, and the later windows'
    # forward again in the backward pass
    assert {f_: after[f_] - before[f_] for f_ in after} == {
        "held_rows": 7, "per_slot": 0}
    monkeypatch.setattr(token_rows, "form", lambda *a: "xla")
    (_, want), want_grads = run(y, gates, *weights)
    np.testing.assert_allclose(out._value, want, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(want).max()))
    for name, a, b in zip(("y", "gates", "w1", "w3", "w2"), got_grads,
                          want_grads):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=name)
