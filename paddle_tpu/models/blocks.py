"""The blocks that two or more of this package's models build, once: norms,
rotary positions, initial values, gates, position-free attention, the
recomputed layer, the routed experts' router, balancing rule and counters,
and the causal-LM criterion.  A model file keeps its config, its tiny
preset, its layer pattern and the blocks only it has.  Each piece opens the
scopes, names the parameters (in the order it creates them) and writes the
metrics its models had before it moved here: the benchmark reads them by
name.
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..framework import dtype as dtypes, random as _random
from ..nn import initializer as I
from ..ops import pallas_ops
from ..ops._primitive import apply_closure
from ..distributed.fleet.meta_parallel import ParallelCrossEntropy
from ..incubate.distributed.models.moe import grouped


def rms(x, weight, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


class RMSNorm(nn.Layer):
    """RMSNorm whose backward pass keeps its input as it is stored and
    computes the float32 insides again (``ops.rms_norm`` keeps three
    float32 copies of a ``[S, hidden]`` input: 0.4 GB a layer at 8k
    tokens)."""

    def __init__(self, size: int, epsilon: float):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[size], default_initializer=I.Constant(1.0))

    def forward(self, x):
        eps = self._epsilon
        return apply_closure(
            jax.checkpoint(lambda x_, w: rms(x_, w, eps)),
            [x, self.weight], name="rms_norm")


def rotary_angles(positions, dim: int, theta: float, sections=None):
    """Angles ``[S, dim / 2]`` float32.  ``positions`` is ``[S]``, or
    ``[3, S]`` with ``sections``: frequency pair i then turns with the
    position stream its section names (temporal, height, width).  On
    text the three streams are the token's index and this is plain
    rotary."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = jnp.asarray(positions, jnp.float32)
    if positions.ndim == 1:
        return positions[:, None] * inv_freq[None, :]
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                        total_repeat_length=dim // 2)
    return positions[stream, :].T * inv_freq[None, :]


def apply_rotary(x, angles):
    """``x [S, heads, dim]`` (or ``[S, dim]``) rotated by ``angles [S,
    dim / 2]``: the pairs are (i, i + dim / 2), computed in float32."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def normed_rotated(x, weight, angles, eps):
    """Per-head RMS normalisation, then the rotation.  Its float32
    insides are cheap to compute again, so the backward pass keeps the
    bf16 input and nothing else."""
    return apply_rotary(rms(x, weight, eps), angles)


def linear(fan_in, fan_out, std):
    return nn.Linear(fan_in, fan_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)))


def embedding(rows: int, width: int, std: float):
    return nn.Embedding(rows, width, weight_attr=nn.ParamAttr(
        initializer=I.Normal(0.0, std)))


class Conv1d(nn.Layer):
    """Depthwise, causal: ``weight [channels, width]`` and a bias."""

    def __init__(self, channels: int, width: int):
        super().__init__()
        bound = 1.0 / math.sqrt(width)     # torch's Conv1d, fan-in = width
        self.weight = self.create_parameter(
            shape=[channels, width],
            default_initializer=I.Uniform(-bound, bound))
        self.bias = self.create_parameter(
            shape=[channels], default_initializer=I.Uniform(-bound, bound))


class InverseSoftplusOfSteps(I.Initializer):
    """``softplus(value)`` is a step drawn log-uniformly from [low, high]."""

    def __init__(self, low: float = 0.001, high: float = 0.1):
        self.low, self.high = math.log(low), math.log(high)

    def __call__(self, shape, dtype):
        step = jnp.exp(jax.random.uniform(
            _random.next_key(), tuple(shape), jnp.float32, self.low,
            self.high))
        return (step + jnp.log(-jnp.expm1(-step))).astype(
            dtypes.to_jax_dtype(dtype))


class LogOfUniform(I.Initializer):
    """``log(value)`` drawn uniformly from [low, high]."""

    def __init__(self, low: float, high: float):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        return jnp.log(jax.random.uniform(
            _random.next_key(), tuple(shape), jnp.float32, self.low,
            self.high)).astype(dtypes.to_jax_dtype(dtype))


@jax.checkpoint
def silu_gate(a, b):
    af = a.astype(jnp.float32)
    return (af * jax.nn.sigmoid(af) * b.astype(jnp.float32)).astype(a.dtype)


@jax.checkpoint
def sigmoid_gate(x, gate):
    """``x * sigmoid(gate)`` in float32; the backward pass keeps the two
    operands as they are stored."""
    return (x.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(x.dtype)


@jax.checkpoint
def step_sizes(dt, dt_bias):
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + dt_bias.astype(jnp.float32))


def gated_mlp(x, w_in, w_out):
    """``silu(a) * b`` through ``w_out``, where ``[a, b] = x w_in``."""
    def closure(x_, w_in_, w_out_):
        a, b = jnp.split(x_ @ w_in_, 2, axis=-1)
        return silu_gate(a, b) @ w_out_

    return apply_closure(closure, [x, w_in, w_out], name="gated_mlp")


class PositionFreeAttention(nn.Layer):
    """Causal grouped-query attention with no positions: ``heads`` query
    heads of ``head_dim`` over ``kv_heads``.  ``o_proj`` starts at
    ``out_std``, the other three at ``std``.  The kernels scale the scores
    by ``1 / sqrt(head_dim)``; where ``q_scale`` is given q is multiplied
    by it first.  ``gated``: the heads' output is multiplied by
    ``sigmoid(x W_g)``, a gate a channel, before ``o_proj`` (``g_proj``,
    at ``std``)."""

    def __init__(self, hidden: int, heads: int, kv_heads: int,
                 head_dim: int, std: float, out_std: float,
                 q_scale: Optional[float] = None, gated: bool = False):
        super().__init__()
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.q_scale = q_scale
        self.q_proj = linear(hidden, heads * head_dim, std)
        self.k_proj = linear(hidden, kv_heads * head_dim, std)
        self.v_proj = linear(hidden, kv_heads * head_dim, std)
        self.o_proj = linear(heads * head_dim, hidden, out_std)
        self.gated = gated
        if gated:
            self.g_proj = linear(hidden, heads * head_dim, std)

    @jax.named_scope("attn")
    def forward(self, x):
        heads, kv_heads, dim = self.heads, self.kv_heads, self.head_dim
        scale = self.q_scale

        def closure(x_, wq, wk, wv, wo, *wg):
            batch, seq = x_.shape[:2]
            split = lambda a, n: a.reshape(batch, seq, n, dim)  # noqa
            # a scaled q is made before the core, an unscaled one in it
            q = None if scale is None else ((x_ @ wq) * scale).astype(
                x_.dtype)
            with jax.named_scope("gqa_core"):
                out = pallas_ops.flash_attention.raw(
                    split(x_ @ wq if q is None else q, heads),
                    split(x_ @ wk, kv_heads), split(x_ @ wv, kv_heads),
                    causal=True)
            out = out.reshape(batch, seq, -1)
            if wg:
                out = sigmoid_gate(out, x_ @ wg[0])
            return out @ wo

        gate = [self.g_proj.weight] if self.gated else []
        return apply_closure(
            closure, [x, self.q_proj.weight, self.k_proj.weight,
                      self.v_proj.weight, self.o_proj.weight] + gate,
            name="position_free_attention")


class Recomputable(nn.Layer):
    """A layer whose ``_block`` runs through ``fleet.recompute`` where
    ``recompute`` is set and the model trains: its forward pass runs again
    in the backward pass, and only its arguments are kept."""

    def __init__(self, recompute: bool):
        super().__init__()
        self._recompute = recompute

    @property
    def recomputed(self) -> bool:
        return self._recompute and self.training

    def forward(self, *args):
        if self.recomputed:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._block, *args)
        return self._block(*args)


def gauge_recomputed(layers: Sequence[Recomputable], kinds: Iterable[str]):
    """``recompute_layers{kind}``, for each of ``kinds``: how many of
    ``layers`` (each with its ``kind``) are recomputed."""
    from ..observability import metrics
    for kind in kinds:
        metrics.registry().gauge(
            "recompute_layers", "layers of the model last traced that run "
            "their forward pass again in the backward pass, by their kind",
            labels={"kind": kind}).set(sum(
                l.recomputed for l in layers if l.kind == kind))


def sigmoid_router(flat, gate, bias, top_k: int, scale: float, eps: float):
    """(experts ``[T, k]``, gates ``[T, k]``) of ``grouped.route_sigmoid``
    for the tokens ``flat [T, hidden]`` under the router's matrix ``gate``
    and its ``bias``; ``eps`` is added to the chosen gates' sum."""
    def router(flat_, gate_w, bias_):
        with jax.named_scope("router"):
            # float32 operands and sums: a bf16 product flips the
            # choice of an expert at the border
            logits = jnp.matmul(
                flat_.astype(jnp.float32), gate_w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            return grouped.route_sigmoid(logits, bias_, top_k, scale,
                                         eps=eps)

    return apply_closure(router, [flat, gate, bias], name="sigmoid_router")


@jax.named_scope("mlp")
def balance(bias, experts, rate: float):
    """The balancing rule on one router's ``bias`` buffer, from the experts
    a pass's tokens chose ``[T, k]``: ``b_e += rate * sign(mean load -
    load_e)``, the load of an expert being the tokens that chose it.  Call
    it outside the layer, so that a recomputed layer has nothing to
    write."""
    with jax.named_scope("router"):
        load = (experts._value[..., None] == jnp.arange(
            bias.shape[0], dtype=experts._value.dtype)).sum(
                (0, 1)).astype(jnp.float32)
        bias._value = bias._value + rate * jnp.sign(load.mean() - load)


def observe_expert_tokens(expert_tokens, layers: Iterable[int]):
    """Writes ``moe_pairs_total{layer}``, ``moe_expert_tokens_max{layer}``
    and ``moe_expert_tokens_mean{layer}`` from the buffer
    ``expert_tokens`` (pairs of each held expert, a row for each of
    ``layers``).  The step returned it with its loss, so where the loss
    has been read this waits for nothing."""
    from ..observability import metrics
    reg = metrics.registry()
    tokens = jax.device_get(expert_tokens._value)
    for layer, row in zip(layers, tokens):
        labels = {"layer": str(layer)}
        reg.counter("moe_pairs_total",
                    "(token, expert) pairs computed by the experts "
                    "held here", labels=labels).inc(int(row.sum()))
        reg.gauge("moe_expert_tokens_max",
                  "pairs of the fullest held expert in the last step "
                  "observed", labels=labels).set(float(row.max()))
        reg.gauge("moe_expert_tokens_mean",
                  "pairs of a held expert in the last step observed, "
                  "on average", labels=labels).set(float(row.mean()))


class CausalLMCriterion(nn.Layer):
    """Mean cross-entropy over the rows held."""

    def __init__(self, config=None):
        super().__init__()
        self.loss_fn = ParallelCrossEntropy()

    @jax.named_scope("loss")
    def forward(self, logits, labels):
        return ops.mean(self.loss_fn(logits, labels))
