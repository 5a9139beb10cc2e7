"""Granite 4.0-H (``model_type`` granitemoehybrid) without routed experts:
a pre-norm RMSNorm decoder whose token mixer is chosen a layer from
``layer_types``, a Mamba-2 mixer (``ops/ssm.py``: causal depthwise
convolution, selective scan in chunks, gated RMSNorm; the scan runs as
the Mosaic kernels of ``ops/ssm_kernels.py`` on a TPU at shapes that
``ssm.scan_form`` gives them, the published ones among them, and as XLA
operations elsewhere) or grouped-query
attention with no positions at all, a SiLU-gated MLP in every layer, four
muP-style multipliers and an output head tied to the embedding.

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    h = h + residual_multiplier * MLP(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling

Mamba-2 mixer (``models/mamba2.py``, built from this config's sizes):
``[z, xBC, dt] = x W_in``; ``xBC = silu(conv(xBC))`` (width
``mamba_d_conv``, with bias); ``[x, B, C] = split(xBC)``; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``, a head; ``y = scan(x, dt,
A, B, C) + D x``; ``y = RMSNorm(y * silu(z)) w``, the mean square taken
over each of ``mamba_n_groups`` groups of channels (one group, the
published count: over all of d_inner); ``out = y W_out``.  Attention:
scores ``q . k * attention_multiplier``, causal, no rotation; the kernels' scale is ``1 / sqrt(head)``, so q is
multiplied by what is left (1/8 at the published sizes, a power of two).

The config holds the published keys under their published names, plus
how many rows of the tied matrix this rank holds (``vocab_rows_held``)
and ``recompute``: each layer through ``fleet.recompute``, so a layer's
forward runs again in the backward pass and only its input is kept.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..ops._primitive import apply_closure
from .blocks import (CausalLMCriterion, PositionFreeAttention, Recomputable,
                     RMSNorm, embedding, gated_mlp, linear)
from .mamba2 import Mamba2Mixer


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()       # () : mamba, attention at 5 of 10
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    vocab_rows_held: int = 0        # 0: all
    recompute: bool = False

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"mamba", "attention"}:
            raise ValueError("layer_types names a mixer, mamba or "
                             "attention, for each of num_hidden_layers")
        if not self.vocab_rows_held:
            self.vocab_rows_held = self.vocab_size
        if self.mamba_expand * self.hidden_size != \
                self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_expand x hidden_size is mamba_n_heads "
                             "x mamba_d_head")
        if self.mamba_proj_bias or not self.mamba_conv_bias:
            raise ValueError("this family has a bias in the convolution "
                             "and none in the projections")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


def granite_hybrid_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                layer_types=("mamba", "attention", "mamba"),
                num_attention_heads=4, num_key_value_heads=2,
                attention_multiplier=1.0 / 16, shared_intermediate_size=96,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                mamba_chunk_size=16)
    base.update(kw)
    return GraniteHybridConfig(**base)


class GraniteMLP(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.input_linear = linear(c.hidden_size,
                                   2 * c.shared_intermediate_size, std)
        self.output_linear = linear(c.shared_intermediate_size,
                                    c.hidden_size, std)

    @jax.named_scope("mlp")
    def forward(self, x):
        return gated_mlp(x, self.input_linear.weight,
                         self.output_linear.weight)


def _add_scaled(h, out, scale: float):
    return apply_closure(
        lambda h_, o: (h_.astype(jnp.float32) + scale * o.astype(
            jnp.float32)).astype(h_.dtype), [h, out], name="granite_residual")


class GraniteDecoderLayer(Recomputable):
    def __init__(self, config: GraniteHybridConfig, layer_idx: int):
        super().__init__(config.recompute)
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if c.layer_types[layer_idx] == "mamba":
            # out_proj starts as every other matrix does
            self.mamba = Mamba2Mixer(
                c.hidden_size, c.mamba_n_heads, c.mamba_d_head,
                c.mamba_d_state, c.mamba_n_groups, c.mamba_d_conv,
                c.mamba_chunk_size, c.rms_norm_eps, c.initializer_range,
                c.initializer_range, layer_idx)
        else:
            # q takes what the kernels' 1 / sqrt(head) leaves of
            # attention_multiplier
            self.self_attn = PositionFreeAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, c.initializer_range, c.initializer_range,
                c.attention_multiplier * math.sqrt(c.head_dim))
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.shared_mlp = GraniteMLP(c)
        self._scale = c.residual_multiplier

    def _block(self, h):
        mixer = getattr(self, "mamba", None) or self.self_attn
        h = _add_scaled(h, mixer(self.input_layernorm(h)), self._scale)
        return _add_scaled(
            h, self.shared_mlp(self.post_attention_layernorm(h)), self._scale)


class GraniteHybridModel(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        c = config
        self.embed_tokens = embedding(c.vocab_rows_held, c.hidden_size,
                                      c.initializer_range)
        self.layers = nn.LayerList([GraniteDecoderLayer(c, i)
                                    for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)


class GraniteHybridForCausalLM(nn.Layer):
    """The head multiplies by the embedding's own matrix, over the rows
    held."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteHybridModel(config)

    def forward(self, input_ids):
        """``input_ids [B, S]`` over the rows held -> logits ``[B, S, rows
        held]``."""
        c = self.config
        from ..observability import metrics
        metrics.registry().gauge(
            "recompute_layers", "layers of the model last traced that run "
            "their forward pass again in the backward pass").set(
            c.num_hidden_layers if c.recompute and self.training else 0)
        with jax.named_scope("embed"):
            h = self.model.embed_tokens(input_ids) * c.embedding_multiplier
        for layer in self.model.layers:
            h = layer(h)
        h = self.model.norm(h)
        with jax.named_scope("head"):
            logits = ops.matmul(h, self.model.embed_tokens.weight,
                                transpose_y=True)
            return logits * (1.0 / c.logits_scaling)


GraniteHybridPretrainingCriterion = CausalLMCriterion
