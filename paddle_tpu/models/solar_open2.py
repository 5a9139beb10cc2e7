"""Solar Open 2 (``model_type`` solar_open2; Upstage Solar-Open2-250B): a
pre-norm RMSNorm decoder whose token mixer is Kimi Delta Attention in three
layers of four and gated, position-free grouped-query attention in the
fourth (``gqa_layers``), each followed by a mixture of SiLU-gated experts
with one shared expert, under an untied output head.

    h = E[ids]
    h = h + mixer_l(RMSNorm(h; input_layernorm_l))
    h = h + moe_l(RMSNorm(h; post_attention_layernorm_l))
    logits = RMSNorm(h; norm) W_head

KDA (Kimi Linear, arXiv:2510.26692; fla's ``KimiDeltaAttention``), a head
of width d at a time:

    q~, k~, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
    q = q~ / |q~| d^-1/2;  k = k~ / |k~|
    log alpha = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)   a channel
    beta = 2 sigmoid(x W_b)                       (negative eigenvalues)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                               (``ops/delta_rule.py``)
    y = RMSNorm_head(o) * sigmoid(x W_ga W_gb + b_g);  out = y W_o

conv4 is a causal depthwise convolution of four taps without bias
(``ops/ssm.py``'s, with its kernels where they run).  GQA:
``models/blocks.PositionFreeAttention``, causal, scores over sqrt(d), no
positions, its output gated by ``sigmoid(x W_gate)`` before ``W_o``.
Experts (``incubate/distributed/models/moe/grouped.py``):

    s = sigmoid(y W_r) in float32;  T = the k largest of s + b
    g_e = routed_scaling_factor * s_e / (sum of s over T + 1e-20)
    out = sum over e in T of g_e (silu(y W1_e) * (y W3_e)) W2_e
          + (silu(y W1_s) * (y W3_s)) W2_s

``b`` (``e_score_correction_bias``) is a buffer: it chooses, does not
weigh, and no gradient reaches it; the balancing rule of
``models/blocks.py`` moves it after each forward pass in training mode
(``router_bias_update_rate``; 0, the default, leaves it).

The config holds the published keys under their published names, plus
what one rank of a deployment is told: which layers it holds
(``layers_held``: first, count), which routed experts (``experts_held``),
which heads of the mixers (``heads_held``: first, count of the 64; the
GQA layer holds the key/value heads its query heads read), how many rows
of embedding and head (``vocab_rows_held``), and ``recompute``: the held
layers (by their index among the held) whose *mixer* runs through
``fleet.recompute``.  The expert half of a layer never does: it would
route again.  A layer that holds a share of the heads returns, from its
``W_o``, its part of the mixer's result; on one chip it runs without the
exchange that would sum the parts.  With ``routing_kept`` (the tokens of
a pass) the experts every pass chose are kept in the buffer
``experts_chosen`` ``[layers, tokens, k]``, which a compiled step returns.

Scopes: ``embed``, ``attn`` (a layer's mixer with its norm), ``mlp`` (its
experts with their norm), ``head``, ``loss``; inside ``attn`` the
sub-scopes ``kda_proj`` (the four projections of KDA), ``kda_conv_gate``
(the convolutions, the q/k normalisation, the decay and beta, the output
norm and gate), ``kda_core`` (the delta rule) and ``gqa_core`` (the flash
calls); inside ``mlp`` ``router``, ``shared_expert`` and, from
``grouped.py``, ``dispatch``, ``experts``, ``combine``.  The buffer
``expert_tokens`` (pairs of each held expert, by layer) is returned by the
compiled step; :meth:`SolarOpen2ForCausalLM.observe_step` writes it to the
metrics registry.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..nn import initializer as I
from ..ops import delta_rule, ssm
from ..ops._primitive import apply_closure
from ..tensor import Tensor
from ..incubate.distributed.models.moe import grouped
from .blocks import (CausalLMCriterion, LogOfUniform, PositionFreeAttention,
                     Recomputable, RMSNorm, balance, embedding,
                     gauge_recomputed, linear, observe_expert_tokens, rms,
                     sigmoid_gate, sigmoid_router, silu_gate)

GATE_EPS = 1e-20                            # added to the chosen gates' sum
PUBLISHED_GQA_LAYERS = tuple(range(0, 48, 4))


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240          # no dense layer uses it
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: dict = field(default_factory=lambda: {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None})
    gqa_layers: Tuple[int, ...] = PUBLISHED_GQA_LAYERS
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    kda_gate_rank: int = 128                # the low-rank gates' inner width
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    embedding_range: float = 0.0            # 0: initializer_range
    router_bias_update_rate: float = 0.0
    layers_held: Tuple[int, int] = (0, 0)       # (first, count); 0: all
    experts_held: Tuple[int, int] = (0, 0)
    heads_held: Tuple[int, int] = (0, 0)
    vocab_rows_held: int = 0
    recompute: Tuple[int, ...] = ()
    routing_kept: int = 0       # tokens a pass; 0: the choices are not kept

    def __post_init__(self):
        c = self
        if not c.layers_held[1]:
            c.layers_held = (0, c.num_hidden_layers)
        if not c.experts_held[1]:
            c.experts_held = (0, c.n_routed_experts)
        if not c.heads_held[1]:
            c.heads_held = (0, c.num_attention_heads)
        if not c.vocab_rows_held:
            c.vocab_rows_held = c.vocab_size
        if not c.embedding_range:
            c.embedding_range = c.initializer_range
        c.layers_held, c.experts_held, c.heads_held = (
            tuple(x) for x in (c.layers_held, c.experts_held, c.heads_held))
        c.gqa_layers = tuple(c.gqa_layers)
        c.recompute = tuple(sorted(c.recompute))
        first, count = c.layers_held
        if not 0 <= first < first + count <= c.num_hidden_layers:
            raise ValueError(f"layers {first}..{first + count} of "
                             f"{c.num_hidden_layers}")
        if set(c.recompute) - set(range(count)):
            raise ValueError("recompute names layers by their index among "
                             f"the {count} held")
        lin = c.linear_attn_config
        heads_first, heads = c.heads_held
        group = c.num_attention_heads // c.num_key_value_heads
        if lin["num_heads"] != c.num_attention_heads \
                or lin["num_kv_heads"] is not None \
                or lin["head_dim"] != c.head_dim \
                or not 0 <= heads_first < heads_first + heads \
                <= c.num_attention_heads \
                or heads_first % group or heads % group:
            raise ValueError("KDA has as many heads as attention, of the same "
                             "width, q, k and v alike; the heads held are "
                             "whole groups of the query heads a key/value "
                             "head serves")
        if c.use_rope or not c.use_gqa_gate or c.kda_use_full_proj \
                or not c.kda_allow_neg_eigval or c.first_k_dense_replace \
                or c.n_shared_experts != 1 or not c.norm_topk_prob \
                or c.tie_word_embeddings:
            raise ValueError("this family's attention has no positions and "
                             "a gate, KDA low-rank gates and beta up to 2, "
                             "every layer experts and one shared expert, "
                             "gates over the chosen, an untied head")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The held layers' mixers: ``gqa`` or ``kda``."""
        first, count = self.layers_held
        return tuple("gqa" if i in self.gqa_layers else "kda"
                     for i in range(first, first + count))

    @property
    def kv_heads_held(self) -> Tuple[int, int]:
        group = self.num_attention_heads // self.num_key_value_heads
        return self.heads_held[0] // group, self.heads_held[1] // group


def solar_open2_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16,
                linear_attn_config={"short_conv_kernel_size": 4,
                                    "head_dim": 16, "num_heads": 4,
                                    "num_kv_heads": None},
                gqa_layers=(0,), kda_gate_rank=16, n_routed_experts=8,
                num_experts_per_tok=2)
    base.update(kw)
    return SolarOpen2Config(**base)


# --------------------------------------------------------------------------
# mixers
# --------------------------------------------------------------------------
def _l2_normed(x, scale: float = 1.0):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-6) \
        * scale


class KimiDeltaAttention(nn.Layer):
    """The heads of ``heads_held``: q, k, v through their short
    convolutions, the delta rule, the gated output norm, ``o_proj`` to
    this rank's part of the layer's output."""

    def __init__(self, config: SolarOpen2Config, layer_idx: int):
        super().__init__()
        c, std = config, config.initializer_range
        self.layer_idx = layer_idx
        self.heads, self.dim = c.heads_held[1], c.head_dim
        self.eps = c.rms_norm_eps
        width = self.heads * self.dim
        taps = c.linear_attn_config["short_conv_kernel_size"]
        self.q_proj = linear(c.hidden_size, width, std)
        self.k_proj = linear(c.hidden_size, width, std)
        self.v_proj = linear(c.hidden_size, width, std)
        # torch's Conv1d default, fan-in = the taps: the three convolutions'
        # taps side by side, q's channels first
        bound = 1.0 / math.sqrt(taps)
        self.conv_weight = self.create_parameter(
            shape=[3 * width, taps],
            default_initializer=I.Uniform(-bound, bound))
        self.f_a_proj = linear(c.hidden_size, c.kda_gate_rank, std)
        self.f_b_proj = linear(c.kda_gate_rank, width, std)
        self.A_log = self.create_parameter(
            shape=[self.heads], default_initializer=LogOfUniform(1.0, 16.0))
        self.dt_bias = self.create_parameter(
            shape=[width], default_initializer=I.Constant(0.0))
        self.b_proj = linear(c.hidden_size, self.heads, std)
        self.g_a_proj = linear(c.hidden_size, c.kda_gate_rank, std)
        self.g_b_proj = linear(c.kda_gate_rank, width, std)
        self.g_bias = self.create_parameter(
            shape=[width], default_initializer=I.Constant(0.0))
        self.o_norm = RMSNorm(self.dim, c.rms_norm_eps)
        self.o_proj = linear(width, c.hidden_size, std)

    def _one_sequence(self, x, wq, wk, wv, taps, wfa, wfb, a_log, dt_bias,
                      wb, wga, wgb, g_bias, norm_w, wo):
        seq, heads, dim = x.shape[0], self.heads, self.dim
        width = heads * dim
        by_head = lambda a: a.reshape(seq, heads, -1)           # noqa: E731
        with jax.named_scope("kda_proj"):
            qkv = jnp.concatenate([x @ wq, x @ wk, x @ wv], -1)
        with jax.named_scope("kda_conv_gate"):
            # ops/ssm.py's convolution of [x | B | C]: here [q | k | v],
            # no bias
            q, k, v = ssm.conv_silu_split(
                qkv, taps, jnp.zeros((3 * width,), taps.dtype), width, 1,
                width)
            q = _l2_normed(by_head(q), dim ** -0.5)
            k = _l2_normed(by_head(k))
            decay = jax.nn.softplus(
                ((x @ wfa) @ wfb).astype(jnp.float32)
                + dt_bias.astype(jnp.float32))
            log_alpha = -jnp.exp(a_log.astype(jnp.float32))[:, None] \
                * by_head(decay)
            beta = 2.0 * jax.nn.sigmoid((x @ wb).astype(jnp.float32))
        with jax.named_scope("kda_core"):
            o = delta_rule.gated_delta_rule(q, k, by_head(v), log_alpha, beta)
        with jax.named_scope("kda_conv_gate"):
            gate = (x @ wga) @ wgb + g_bias.astype(x.dtype)
            y = sigmoid_gate(rms(o, norm_w, self.eps).reshape(seq, width),
                             gate)
        with jax.named_scope("kda_proj"):
            return y @ wo

    def forward(self, x):
        """``x [B, S, hidden]`` -> this rank's part of the mixer's
        output."""
        weights = [self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                   self.conv_weight, self.f_a_proj.weight,
                   self.f_b_proj.weight, self.A_log, self.dt_bias,
                   self.b_proj.weight, self.g_a_proj.weight,
                   self.g_b_proj.weight, self.g_bias, self.o_norm.weight,
                   self.o_proj.weight]

        def closure(x_, *w):
            return jnp.stack([self._one_sequence(x_[b], *w)
                              for b in range(x_.shape[0])])

        return apply_closure(closure, [x] + weights, name="kda")


class SolarOpen2Mixer(Recomputable):
    """``h + mixer(RMSNorm(h))``, through ``fleet.recompute`` where the
    config names the layer."""

    def __init__(self, config: SolarOpen2Config, held_idx: int):
        super().__init__(held_idx in config.recompute)
        c = config
        self.kind = c.kinds[held_idx]
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if self.kind == "gqa":
            self.self_attn = PositionFreeAttention(
                c.hidden_size, c.heads_held[1], c.kv_heads_held[1],
                c.head_dim, c.initializer_range, c.initializer_range,
                gated=True)
        else:
            self.linear_attn = KimiDeltaAttention(
                c, c.layers_held[0] + held_idx)

    def _block(self, h):
        mixer = self.self_attn if self.kind == "gqa" else self.linear_attn
        with jax.named_scope("attn"):
            return h + mixer(self.input_layernorm(h))


class SolarOpen2MoE(nn.Layer):
    """The routed experts held here and the shared expert."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c, std = config, config.initializer_range
        self.top_k = c.num_experts_per_tok
        self.scale = c.routed_scaling_factor
        self.gate = linear(c.hidden_size, c.n_routed_experts, std)
        self.register_buffer("e_score_correction_bias", Tensor(
            jnp.zeros((c.n_routed_experts,), jnp.float32)))
        first, held = c.experts_held
        self.experts = grouped.GroupedSwiGLUExperts(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            first, held, std)
        width = c.moe_intermediate_size * c.n_shared_experts
        self.shared_gate = linear(c.hidden_size, width, std)
        self.shared_up = linear(c.hidden_size, width, std)
        self.shared_down = linear(width, c.hidden_size, std)

    def shared(self, flat):
        """``(silu(y W1_s) * (y W3_s)) W2_s`` of every token."""
        def closure(y, w1, w3, w2):
            with jax.named_scope("shared_expert"):
                return silu_gate(y @ w1, y @ w3) @ w2

        return apply_closure(closure, [flat, self.shared_gate.weight,
                                       self.shared_up.weight,
                                       self.shared_down.weight],
                             name="solar_open2_shared_expert")

    def forward(self, y):
        """``y [B, S, hidden]`` -> (this rank's part of the routed experts'
        result plus the shared expert's, pairs of each held expert
        ``[held]``, the experts chosen ``[B * S, k]``)."""
        shape = y.shape
        flat = ops.reshape(y, [-1, shape[-1]])
        experts, gates = sigmoid_router(
            flat, self.gate.weight, self.e_score_correction_bias, self.top_k,
            self.scale, GATE_EPS)
        routed, sizes = self.experts(flat, experts, gates)
        out = ops.cast(routed, y.dtype) + self.shared(flat)
        return ops.reshape(out, list(shape)), sizes, experts


class SolarOpen2DecoderLayer(nn.Layer):
    """The mixer half (recomputed where named), then ``h + moe(norm(h))``;
    returns the stream, the pairs of each held expert and the experts
    chosen."""

    def __init__(self, config: SolarOpen2Config, held_idx: int):
        super().__init__()
        c = config
        self.mixer = SolarOpen2Mixer(c, held_idx)
        self.kind = self.mixer.kind
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.mlp = SolarOpen2MoE(c)

    def forward(self, h):
        h = self.mixer(h)
        with jax.named_scope("mlp"):
            out, sizes, experts = self.mlp(self.post_attention_layernorm(h))
            return h + out, sizes, experts


class SolarOpen2Model(nn.Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = config
        self.embed_tokens = embedding(c.vocab_rows_held, c.hidden_size,
                                      c.embedding_range)
        self.layers = nn.LayerList([SolarOpen2DecoderLayer(c, i)
                                    for i in range(c.layers_held[1])])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)


class SolarOpen2ForCausalLM(nn.Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = config
        self.config = c
        self.model = SolarOpen2Model(c)
        self.lm_head = linear(c.hidden_size, c.vocab_rows_held,
                              c.initializer_range)
        layers = c.layers_held[1]
        self.register_buffer("expert_tokens", Tensor(jnp.zeros(
            (layers, c.experts_held[1]), jnp.int32)))
        if c.routing_kept:
            self.register_buffer("experts_chosen", Tensor(jnp.zeros(
                (layers, c.routing_kept, c.num_experts_per_tok), jnp.int32)))

    def forward(self, input_ids, output_routing=False):
        """``input_ids [B, S]`` over the rows held -> logits ``[B, S, rows
        held]``; with ``output_routing`` also the experts chosen ``[layers,
        B * S, k]`` and the pairs of each held expert ``[layers, held]``."""
        layers = self.model.layers
        gauge_recomputed([l.mixer for l in layers],
                         dict.fromkeys(self.config.kinds))
        with jax.named_scope("embed"):
            h = self.model.embed_tokens(input_ids)
        sizes, chosen = [], []
        rate = self.config.router_bias_update_rate
        for layer in layers:
            h, pairs, experts = layer(h)
            sizes.append(pairs)
            chosen.append(experts)
            if self.training and rate:
                balance(layer.mlp.e_score_correction_bias, experts, rate)
        h = self.model.norm(h)
        with jax.named_scope("head"):
            logits = self.lm_head(h)
        routing = ops.stack(chosen, axis=0)
        self.expert_tokens._value = ops.stack(sizes, axis=0)._value
        # a pass of another size leaves the buffer, and the compiled step
        # that returns it, as they are
        if routing.shape[1] == self.config.routing_kept:
            self.experts_chosen._value = routing._value
        if output_routing:
            return [logits, routing, Tensor(self.expert_tokens._value)]
        return logits

    def moe_layers(self) -> Tuple[int, ...]:
        """The layers held, by their index in the whole model: every one
        carries experts."""
        first, count = self.config.layers_held
        return tuple(range(first, first + count))

    def observe_step(self):
        """Writes what the last step counted to the metrics registry
        (:func:`blocks.observe_expert_tokens`)."""
        observe_expert_tokens(self.expert_tokens, self.moe_layers())


SolarOpen2PretrainingCriterion = CausalLMCriterion
