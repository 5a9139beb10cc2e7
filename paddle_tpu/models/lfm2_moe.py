"""LFM2-MoE (``model_type`` lfm2_moe; LiquidAI LFM2-8B-A1B): a pre-norm
RMSNorm decoder whose token mixer is chosen a layer from ``layer_types``,
a double-gated short convolution or grouped-query attention, with a
SiLU-gated MLP in the first ``num_dense_layers`` layers and a mixture of
SiLU-gated experts in every layer after them, under an output head tied
to the embedding.

    h = E[ids]
    h = h + op_l(RMSNorm(h; operator_norm_l))
    h = h + ff_l(RMSNorm(h; ffn_norm_l))
    logits = RMSNorm(h; embedding_norm) E^T

``conv``: ``[B | C | x] = u W_in`` (three blocks of ``hidden_size``),
``op(u) = (C * conv(B * x)) W_out`` with a causal depthwise convolution of
``conv_L_cache`` taps and no bias (``ops/short_conv.py``: its backward
pass keeps the projection's result and nothing else).
``full_attention``: grouped key/value heads; q and k are RMS-normalised a
head (their own weights over the head's width) and then rotated over the
whole head (rotate-half, ``rope_theta``); causal ``softmax(q k^T /
sqrt(head)) v`` through ``ops/pallas_ops.flash_attention``.  Dense ff:
``(silu(y W1) * (y W3)) W2`` at ``intermediate_size``.  Expert ff
(``incubate/distributed/models/moe/grouped.py``):

    s = sigmoid(y W_r) in float32;  T = the k largest of s + b
    g_e = routed_scaling_factor * s_e / (sum of s over T + 1e-6)
    out = sum over e in T of g_e (silu(y W1_e) * (y W3_e)) W2_e

``b`` (``expert_bias``) is a buffer: it chooses, does not weigh, and no
gradient reaches it.  What moves it is the balancing rule of
``models/nemotron_h.py``: after each forward pass in training mode, ``b_e
+= router_bias_update_rate * sign(mean load - load_e)`` over all experts,
held here or not (the rate is no key of ``config.json``; 0, the default,
leaves ``b`` where it is).

The config holds the published keys under their published names, plus
what one rank of a deployment is told: which layers of ``layer_types`` it
holds (``layers_held``: first, count; a layer is dense where its index in
the whole model is below ``num_dense_layers``), which routed experts
(``experts_held``), how many rows of the tied matrix
(``vocab_rows_held``), and ``recompute``: the held layers (by their index
among the held) that run through ``fleet.recompute``.  A recomputed
expert layer routes again: in float32 to the same experts, bit for bit;
under bf16 the recomputation, which XLA may fuse otherwise, can round the
stream otherwise and send a token at a border to another expert than the
forward pass did (PERF.md section 6, PR 40), so recompute a layer without
experts where one will do.  With ``routing_kept``
(the tokens of a pass, batch x sequence) the experts every such pass
chose are kept too, in the buffer ``experts_chosen`` ``[expert layers,
tokens, k]``, which a compiled step returns as it does ``expert_tokens``:
the gradients of a step can then be held against a reference that is
given the step's own choices.

Scopes: ``embed``, ``attn`` (a layer's operator with its norm), ``mlp``
(its ff with its norm), ``head``, ``loss``; inside ``attn`` the
sub-scopes ``conv_proj`` (both projections), ``short_conv`` (the
operator), ``attn_proj`` (projections, q/k norm, rotary) and ``gqa_core``
(the flash calls and the K/V repeat); inside ``mlp`` ``dense_mlp``,
``router`` and, from ``grouped.py``, ``dispatch``, ``experts``,
``combine``.  What a step counted is kept in the buffer ``expert_tokens``
(pairs of each held expert, by expert layer), which the compiled step
returns with its loss; :meth:`Lfm2MoeForCausalLM.observe_step` writes it
to the metrics registry.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..nn import initializer as I
from ..ops import pallas_ops, short_conv
from ..ops._primitive import apply_closure
from ..tensor import Tensor
from ..distributed.fleet.meta_parallel import ParallelCrossEntropy
from ..incubate.distributed.models.moe import grouped
from .keye_lm import (KeyeRMSNorm as RMSNorm, _linear, _normed_rotated,
                      rotary_angles)
from .mamba2 import _silu_gate

OPERATORS = ("conv", "full_attention")
ATTENTION_AT = (2, 6, 10, 14, 18, 21)       # of the published 24 layers
GATE_EPS = 1e-6                             # added to the chosen gates' sum


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = ()       # (): attention at ATTENTION_AT
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    router_bias_update_rate: float = 0.0
    layers_held: Tuple[int, int] = (0, 0)       # (first, count); 0: all
    experts_held: Tuple[int, int] = (0, 0)
    vocab_rows_held: int = 0
    recompute: Tuple[int, ...] = ()
    routing_kept: int = 0       # tokens a pass; 0: the choices are not kept

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                "full_attention" if i in ATTENTION_AT else "conv"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - set(OPERATORS):
            raise ValueError("layer_types names an operator, conv or "
                             "full_attention, for each of num_hidden_layers")
        if not self.layers_held[1]:
            self.layers_held = (0, self.num_hidden_layers)
        if not self.experts_held[1]:
            self.experts_held = (0, self.num_experts)
        if not self.vocab_rows_held:
            self.vocab_rows_held = self.vocab_size
        self.layers_held = tuple(self.layers_held)
        self.experts_held = tuple(self.experts_held)
        self.recompute = tuple(sorted(self.recompute))
        first, count = self.layers_held
        if not 0 <= first < first + count <= self.num_hidden_layers:
            raise ValueError(f"layers {first}..{first + count} of "
                             f"{self.num_hidden_layers}")
        if set(self.recompute) - set(range(count)):
            raise ValueError("recompute names layers by their index among "
                             f"the {count} held")
        if self.conv_bias or not self.norm_topk_prob \
                or not self.use_expert_bias:
            raise ValueError("this family's convolution has no bias, and its "
                             "router a bias and gates normalised over the "
                             "experts chosen")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("hidden_size is num_attention_heads heads, a "
                             "whole number of them a key/value head")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The held layers, operator and ff: ``conv_dense``, ``conv_moe``,
        ``attention_dense`` or ``attention_moe``."""
        first, count = self.layers_held
        return tuple(
            ("conv" if self.layer_types[i] == "conv" else "attention") + "_"
            + ("dense" if i < self.num_dense_layers else "moe")
            for i in range(first, first + count))


def lfm2_moe_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=4,
                layer_types=("conv", "conv", "full_attention", "conv"),
                num_dense_layers=1, num_attention_heads=4,
                num_key_value_heads=2, num_experts=8, num_experts_per_tok=2)
    base.update(kw)
    return Lfm2MoeConfig(**base)


# --------------------------------------------------------------------------
# operators
# --------------------------------------------------------------------------
class Lfm2ShortConv(nn.Layer):
    """``(C * conv(B * x)) W_out`` with ``[B | C | x] = u W_in``."""

    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        c, std = config, config.initializer_range
        self.layer_idx = layer_idx
        self.in_proj = _linear(c.hidden_size, 3 * c.hidden_size, std)
        # torch's Conv1d: uniform within 1 / sqrt(fan-in), the taps
        bound = 1.0 / math.sqrt(c.conv_L_cache)
        self.conv_weight = self.create_parameter(
            shape=[c.hidden_size, c.conv_L_cache],
            default_initializer=I.Uniform(-bound, bound))
        self.out_proj = _linear(c.hidden_size, c.hidden_size, std)

    def forward(self, u):
        """``u [B, S, hidden]`` -> the operator's output."""
        from ..observability import metrics
        metrics.registry().gauge(
            "short_conv_bytes", "bytes one call of the gated short "
            "convolution must move, forward and backward: bcx read and y "
            "written, bcx and dy read and dbcx written",
            labels={"layer": str(self.layer_idx)}).set(
                short_conv.gated_short_conv_bytes(
                    u.shape[1], u.shape[2], u._value.dtype.itemsize))

        def closure(u_, w_in, taps, w_out):
            with jax.named_scope("conv_proj"):
                bcx = u_ @ w_in
            with jax.named_scope("short_conv"):
                y = jnp.stack([short_conv.gated_short_conv(bcx[b], taps)
                               for b in range(bcx.shape[0])])
            with jax.named_scope("conv_proj"):
                return y @ w_out

        return apply_closure(
            closure, [u, self.in_proj.weight, self.conv_weight,
                      self.out_proj.weight], name="lfm2_short_conv")


class Lfm2Attention(nn.Layer):
    """Grouped-query attention, q and k normalised a head and rotated."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.config = c
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = _linear(c.hidden_size, c.hidden_size, std)
        self.k_proj = _linear(c.hidden_size, kv, std)
        self.v_proj = _linear(c.hidden_size, kv, std)
        self.out_proj = _linear(c.hidden_size, c.hidden_size, std)
        self.q_layernorm = RMSNorm(c.head_dim, c.norm_eps)
        self.k_layernorm = RMSNorm(c.head_dim, c.norm_eps)

    def forward(self, u):
        c = self.config

        def closure(u_, wq, wk, wv, wo, qn, kn):
            batch, seq = u_.shape[:2]
            heads = lambda a, n: a.reshape(batch, seq, n, c.head_dim)  # noqa
            with jax.named_scope("attn_proj"):
                angles = rotary_angles(jnp.arange(seq), c.head_dim,
                                       c.rope_theta)
                q, k = (jax.vmap(lambda a, w=w: _normed_rotated(
                    a, w, angles, c.norm_eps))(heads(u_ @ m, n))
                    for m, w, n in ((wq, qn, c.num_attention_heads),
                                    (wk, kn, c.num_key_value_heads)))
                v = heads(u_ @ wv, c.num_key_value_heads)
            with jax.named_scope("gqa_core"):
                out = pallas_ops.flash_attention.raw(q, k, v, causal=True)
            with jax.named_scope("attn_proj"):
                return out.reshape(batch, seq, -1) @ wo

        return apply_closure(
            closure, [u, self.q_proj.weight, self.k_proj.weight,
                      self.v_proj.weight, self.out_proj.weight,
                      self.q_layernorm.weight, self.k_layernorm.weight],
            name="lfm2_attention")


# --------------------------------------------------------------------------
# feed-forward blocks
# --------------------------------------------------------------------------
class Lfm2MLP(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.w1 = _linear(c.hidden_size, c.intermediate_size, std)
        self.w3 = _linear(c.hidden_size, c.intermediate_size, std)
        self.w2 = _linear(c.intermediate_size, c.hidden_size, std)

    def forward(self, y):
        def closure(y_, w1, w3, w2):
            with jax.named_scope("dense_mlp"):
                return _silu_gate(y_ @ w1, y_ @ w3) @ w2

        return apply_closure(
            closure, [y, self.w1.weight, self.w3.weight, self.w2.weight],
            name="lfm2_mlp")


class Lfm2SparseMoeBlock(nn.Layer):
    """The routed experts held here."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.top_k = c.num_experts_per_tok
        self.scale = c.routed_scaling_factor
        self.gate = _linear(c.hidden_size, c.num_experts, std)
        self.register_buffer("expert_bias", Tensor(
            jnp.zeros((c.num_experts,), jnp.float32)))
        first, held = c.experts_held
        self.experts = grouped.GroupedSwiGLUExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts, first,
            held, std)

    def forward(self, y):
        """``y [B, S, hidden]`` -> (this rank's part of the layer's result,
        pairs of each held expert ``[held]``, the experts chosen ``[B * S,
        k]``)."""
        shape = y.shape
        flat = ops.reshape(y, [-1, shape[-1]])
        top_k, scale = self.top_k, self.scale

        def router(flat_, gate_w, bias):
            with jax.named_scope("router"):
                # float32 operands and sums: a bf16 product flips the
                # choice of an expert at the border
                logits = jnp.matmul(
                    flat_.astype(jnp.float32), gate_w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
                return grouped.route_sigmoid(logits, bias, top_k, scale,
                                             eps=GATE_EPS)

        experts, gates = apply_closure(
            router, [flat, self.gate.weight, self.expert_bias],
            name="lfm2_router")
        out, sizes = self.experts(flat, experts, gates)
        return (ops.reshape(ops.cast(out, y.dtype), list(shape)), sizes,
                experts)


class Lfm2MoeDecoderLayer(nn.Layer):
    """``h + op(norm(h))``, then ``h + ff(norm(h))``; an expert layer also
    returns the pairs of each held expert and the experts chosen."""

    def __init__(self, config: Lfm2MoeConfig, held_idx: int):
        super().__init__()
        c = config
        self.layer_idx = c.layers_held[0] + held_idx
        self.kind = c.kinds[held_idx]
        self.is_moe = self.kind.endswith("_moe")
        self.operator_norm = RMSNorm(c.hidden_size, c.norm_eps)
        if self.kind.startswith("conv"):
            self.conv = Lfm2ShortConv(c, self.layer_idx)
        else:
            self.self_attn = Lfm2Attention(c)
        self.ffn_norm = RMSNorm(c.hidden_size, c.norm_eps)
        self.feed_forward = (Lfm2SparseMoeBlock(c) if self.is_moe
                             else Lfm2MLP(c))
        self._recompute = held_idx in c.recompute

    def _block(self, h):
        operator = getattr(self, "conv", None) or self.self_attn
        with jax.named_scope("attn"):
            h = h + operator(self.operator_norm(h))
        with jax.named_scope("mlp"):
            out = self.feed_forward(self.ffn_norm(h))
            if self.is_moe:
                return (h + out[0],) + tuple(out[1:])
            return h + out

    @property
    def recomputed(self) -> bool:
        return self._recompute and self.training

    def forward(self, h):
        if self.recomputed:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._block, h)
        return self._block(h)


class Lfm2MoeModel(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.embed_tokens = nn.Embedding(
            c.vocab_rows_held, c.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, c.initializer_range)))
        self.layers = nn.LayerList([Lfm2MoeDecoderLayer(c, i)
                                    for i in range(c.layers_held[1])])
        self.embedding_norm = RMSNorm(c.hidden_size, c.norm_eps)


class Lfm2MoeForCausalLM(nn.Layer):
    """The head multiplies by the embedding's own matrix, over the rows
    held."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.config = c
        self.model = Lfm2MoeModel(c)
        self.register_buffer("expert_tokens", Tensor(jnp.zeros(
            (len(self.moe_layers()), c.experts_held[1]), jnp.int32)))
        if c.routing_kept:
            self.register_buffer("experts_chosen", Tensor(jnp.zeros(
                (len(self.moe_layers()), c.routing_kept,
                 c.num_experts_per_tok), jnp.int32)))

    def forward(self, input_ids, output_routing=False):
        """``input_ids [B, S]`` over the rows held -> logits ``[B, S, rows
        held]``; with ``output_routing`` also the experts chosen ``[expert
        layers, B * S, k]`` and the pairs of each held expert ``[expert
        layers, held]``."""
        layers = self.model.layers
        from ..observability import metrics
        for kind in dict.fromkeys(self.config.kinds):
            metrics.registry().gauge(
                "recompute_layers", "layers of the model last traced that "
                "run their forward pass again in the backward pass, by "
                "their operator and ff", labels={"kind": kind}).set(sum(
                    l.recomputed for l in layers if l.kind == kind))
        with jax.named_scope("embed"):
            h = self.model.embed_tokens(input_ids)
        sizes, chosen = [], []
        for layer in layers:
            if layer.is_moe:
                h, pairs, experts = layer(h)
                sizes.append(pairs)
                chosen.append(experts)
                if self.training and self.config.router_bias_update_rate:
                    self._balance(layer.feed_forward, experts)
            else:
                h = layer(h)
        with jax.named_scope("head"):
            logits = ops.matmul(self.model.embedding_norm(h),
                                self.model.embed_tokens.weight,
                                transpose_y=True)
        routing = ops.stack(chosen, axis=0) if chosen else None
        if sizes:
            self.expert_tokens._value = ops.stack(sizes, axis=0)._value
            # a pass of another size leaves the buffer, and the compiled
            # step that returns it, as they are
            if routing.shape[1] == self.config.routing_kept:
                self.experts_chosen._value = routing._value
        if output_routing:
            return [logits, routing, Tensor(self.expert_tokens._value)]
        return logits

    @jax.named_scope("mlp")
    def _balance(self, block, experts):
        """The balancing rule on one router's bias, from the experts this
        pass's tokens chose ``[T, k]``: outside the layer, so that a
        recomputed layer has nothing to write."""
        rate = self.config.router_bias_update_rate
        bias = block.expert_bias
        with jax.named_scope("router"):
            load = (experts._value[..., None] == jnp.arange(
                bias.shape[0], dtype=experts._value.dtype)).sum(
                    (0, 1)).astype(jnp.float32)
            bias._value = bias._value + rate * jnp.sign(load.mean() - load)

    def moe_layers(self) -> Tuple[int, ...]:
        """The expert layers held, by their index in the whole model."""
        c = self.config
        return tuple(c.layers_held[0] + i for i, kind in enumerate(c.kinds)
                     if kind.endswith("_moe"))

    def observe_step(self):
        """Writes what the last step counted to the metrics registry:
        ``moe_pairs_total{layer}``, ``moe_expert_tokens_max{layer}``,
        ``moe_expert_tokens_mean{layer}``.  It reads a buffer the step
        returned with its loss, so where the loss has been read it waits
        for nothing."""
        from ..observability import metrics
        reg = metrics.registry()
        tokens = jax.device_get(self.expert_tokens._value)
        for layer, row in zip(self.moe_layers(), tokens):
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


class Lfm2MoePretrainingCriterion(nn.Layer):
    """Mean cross-entropy over the rows held."""

    def __init__(self, config: Optional[Lfm2MoeConfig] = None):
        super().__init__()
        self.loss_fn = ParallelCrossEntropy()

    @jax.named_scope("loss")
    def forward(self, logits, labels):
        return ops.mean(self.loss_fn(logits, labels))
