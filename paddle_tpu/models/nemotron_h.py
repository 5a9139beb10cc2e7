"""Nemotron-H (``model_type`` nemotron_h; NVIDIA-Nemotron-3-Nano-30B-A3B):
a pre-norm RMSNorm decoder whose block is *one* sublayer, its mixer drawn
from three kinds by ``hybrid_override_pattern``, a character a block,
under an untied output head.

    h = E[ids]
    h = h + mixer_l(RMSNorm(h; w_l))          each block l
    logits = RMSNorm(h; norm_f) W_head

``M``, Mamba-2: ``models/mamba2.py``'s mixer (causal depthwise
convolution, selective scan in chunks through ``ops/ssm.py``, gated
RMSNorm) with ``n_groups`` groups of B and C, the gated norm's mean
square taken over each group's channels.  ``*``, attention: grouped
key/value heads of their own width (``head_dim``, not hidden / heads),
causal, scores over ``sqrt(head_dim)``, no rotation and no other position
signal.  ``E``, experts (``incubate/distributed/models/moe/grouped.py``):

    s = sigmoid(u W_r) in float32;  T = the k largest of s + b
    g_e = routed_scaling_factor * s_e / (sum of s over T + 1e-20)
    out = sum over e in T of g_e W2_e relu(W1_e u)^2  +  W2_s relu(W1_s u)^2

``b`` (``e_score_correction_bias``) is a buffer: it chooses, does not
weigh, and no gradient reaches it.  What moves it is the balancing rule
of the family's training recipe: after each forward pass in training
mode, ``b_e += router_bias_update_rate * sign(mean load - load_e)``, the
load of an expert being the tokens of that pass that chose it, over all
experts, held here or not (the rate is no key of ``config.json``; 0, the
default, leaves ``b`` where it is).  ``n_group = topk_group = 1``: the
choice is over all experts at once.

The config holds the published keys under their published names, plus
what one rank of a deployment is told: which blocks of the pattern it
holds (``blocks_held``: first, count), which routed experts
(``experts_held``), how many rows of embedding and head
(``vocab_rows_held``), where the embedding starts if not at
``initializer_range`` (``embedding_range``), and ``recompute``: the held
blocks (by their index among the held) that run through
``fleet.recompute``, so their forward runs again in the backward pass and
only their input is kept.  A recomputed expert block routes again, to
the same experts: the router is a function of the block's input.  With
``rescale_prenorm_residual`` every projection that writes into the
stream (a Mamba-2 block's ``out_proj``, attention's ``o_proj``, the
experts' and the shared expert's second matrix) starts smaller by the
square root of the model's depth.

What a step counted is kept in the buffer ``expert_tokens`` (pairs of
each held expert, by expert block), which the compiled step returns with
its loss; :meth:`NemotronHForCausalLM.observe_step` writes it to the
metrics registry.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..ops._primitive import apply_closure
from ..tensor import Tensor
from ..incubate.distributed.models.moe import grouped
from .blocks import (CausalLMCriterion, PositionFreeAttention, Recomputable,
                     RMSNorm, balance, embedding, gauge_recomputed, linear,
                     observe_expert_tokens, sigmoid_router)
from .mamba2 import Mamba2Mixer

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
GATE_EPS = 1e-20                            # added to the chosen gates' sum


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    conv_kernel: int = 4
    n_groups: int = 8
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    embedding_range: float = 0.0                # 0: initializer_range
    rescale_prenorm_residual: bool = True
    router_bias_update_rate: float = 0.0
    blocks_held: Tuple[int, int] = (0, 0)       # (first, count); 0: all
    experts_held: Tuple[int, int] = (0, 0)
    vocab_rows_held: int = 0
    recompute: Tuple[int, ...] = ()

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers or \
                set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError("hybrid_override_pattern names a mixer, M, E "
                             "or *, for each of num_hidden_layers")
        if not self.blocks_held[1]:
            self.blocks_held = (0, self.num_hidden_layers)
        if not self.experts_held[1]:
            self.experts_held = (0, self.n_routed_experts)
        if not self.vocab_rows_held:
            self.vocab_rows_held = self.vocab_size
        if not self.embedding_range:
            self.embedding_range = self.initializer_range
        self.blocks_held = tuple(self.blocks_held)
        self.experts_held = tuple(self.experts_held)
        self.recompute = tuple(sorted(self.recompute))
        first, count = self.blocks_held
        if not 0 <= first < first + count <= self.num_hidden_layers:
            raise ValueError(f"blocks {first}..{first + count} of "
                             f"{self.num_hidden_layers}")
        if set(self.recompute) - set(range(count)):
            raise ValueError("recompute names blocks by their index among "
                             f"the {count} held")
        if self.n_group != 1 or self.topk_group != 1 \
                or self.n_shared_experts != 1 or not self.norm_topk_prob:
            raise ValueError("this family's router chooses over all experts "
                             "at once, normalises over the chosen, and has "
                             "one shared expert")
        if self.mamba_proj_bias or not self.use_conv_bias:
            raise ValueError("this family has a bias in the convolution "
                             "and none in the projections")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The held blocks' mixers: mamba, moe or attention."""
        first, count = self.blocks_held
        return tuple(KINDS[ch] for ch in
                     self.hybrid_override_pattern[first:first + count])

    @property
    def residual_range(self) -> float:
        """Where the projections that write into the stream start: with
        ``rescale_prenorm_residual`` smaller by the square root of the
        model's depth (GPT-2's scheme)."""
        if not self.rescale_prenorm_residual:
            return self.initializer_range
        return self.initializer_range / math.sqrt(self.num_hidden_layers)


def nemotron_h_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                hybrid_override_pattern="ME*E", num_attention_heads=4,
                num_key_value_heads=2, head_dim=8, mamba_num_heads=8,
                mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                chunk_size=16, moe_intermediate_size=24,
                moe_shared_expert_intermediate_size=48, n_routed_experts=8,
                num_experts_per_tok=2)
    base.update(kw)
    return NemotronHConfig(**base)


# --------------------------------------------------------------------------
# mixers
# --------------------------------------------------------------------------
class NemotronHMoE(nn.Layer):
    """The routed experts held here and the shared expert."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.top_k = c.num_experts_per_tok
        self.scale = c.routed_scaling_factor
        self.gate = linear(c.hidden_size, c.n_routed_experts, std)
        self.register_buffer("e_score_correction_bias", Tensor(
            jnp.zeros((c.n_routed_experts,), jnp.float32)))
        first, held = c.experts_held
        self.experts = grouped.GroupedRelu2Experts(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            first, held, std, c.residual_range)
        self.shared_up = linear(c.hidden_size,
                                c.moe_shared_expert_intermediate_size, std)
        self.shared_down = linear(c.moe_shared_expert_intermediate_size,
                                  c.hidden_size, c.residual_range)

    def shared(self, flat):
        """``W2_s relu(W1_s y)^2`` of every token."""
        def closure(y, up, down):
            with jax.named_scope("shared_expert"):
                return grouped._relu2(y @ up) @ down

        return apply_closure(closure, [flat, self.shared_up.weight,
                                       self.shared_down.weight],
                             name="nemotron_h_shared_expert")

    @jax.named_scope("mlp")
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


class NemotronHBlock(Recomputable):
    """``h + mixer(RMSNorm(h))``; an expert block also returns the pairs
    of each held expert and the experts chosen."""

    def __init__(self, config: NemotronHConfig, held_idx: int):
        super().__init__(held_idx in config.recompute)
        c = config
        self.kind = c.kinds[held_idx]
        self.norm = RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        if self.kind == "mamba":
            self.mixer = Mamba2Mixer(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                c.ssm_state_size, c.n_groups, c.conv_kernel, c.chunk_size,
                c.layer_norm_epsilon, c.initializer_range, c.residual_range,
                c.blocks_held[0] + held_idx)
        elif self.kind == "moe":
            self.mixer = NemotronHMoE(c)
        else:
            # the heads' width is the config's own
            self.mixer = PositionFreeAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, c.initializer_range, c.residual_range)

    def _block(self, h):
        out = self.mixer(self.norm(h))
        if self.kind == "moe":
            return (h + out[0],) + tuple(out[1:])
        return h + out


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.embeddings = embedding(c.vocab_rows_held, c.hidden_size,
                                    c.embedding_range)
        self.layers = nn.LayerList([NemotronHBlock(c, i)
                                    for i in range(c.blocks_held[1])])
        self.norm_f = RMSNorm(c.hidden_size, c.layer_norm_epsilon)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.config = c
        self.backbone = NemotronHModel(c)
        self.lm_head = linear(c.hidden_size, c.vocab_rows_held,
                              c.initializer_range)
        self.register_buffer("expert_tokens", Tensor(jnp.zeros(
            (c.kinds.count("moe"), c.experts_held[1]), jnp.int32)))

    def forward(self, input_ids, output_routing=False):
        """``input_ids [B, S]`` over the rows held -> logits ``[B, S, rows
        held]``; with ``output_routing`` also the experts chosen ``[expert
        blocks, B * S, k]`` and the pairs of each held expert ``[expert
        blocks, held]``."""
        blocks = self.backbone.layers
        gauge_recomputed(blocks, KINDS.values())
        with jax.named_scope("embed"):
            h = self.backbone.embeddings(input_ids)
        sizes, chosen = [], []
        for block in blocks:
            if block.kind == "moe":
                h, pairs, experts = block(h)
                sizes.append(pairs)
                chosen.append(experts)
                rate = self.config.router_bias_update_rate
                if self.training and rate:
                    balance(block.mixer.e_score_correction_bias, experts,
                            rate)
            else:
                h = block(h)
        h = self.backbone.norm_f(h)
        with jax.named_scope("head"):
            logits = self.lm_head(h)
        if sizes:
            self.expert_tokens._value = ops.stack(sizes, axis=0)._value
        if output_routing:
            return [logits, ops.stack(chosen, axis=0),
                    Tensor(self.expert_tokens._value)]
        return logits

    def moe_blocks(self) -> Tuple[int, ...]:
        """The expert blocks held, by their index in the whole model."""
        c = self.config
        return tuple(c.blocks_held[0] + i for i, kind in enumerate(c.kinds)
                     if kind == "moe")

    def observe_step(self):
        """Writes what the last step counted to the metrics registry
        (:func:`blocks.observe_expert_tokens`)."""
        observe_expert_tokens(self.expert_tokens, self.moe_blocks())


NemotronHPretrainingCriterion = CausalLMCriterion
