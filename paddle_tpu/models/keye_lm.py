"""The language model of Keye-VL-2.0-30B-A3B (``model_type`` KeyeVL2): a
pre-norm RMSNorm decoder with rotary positions in three sections,
grouped key/value heads, per-head RMS normalisation of q and k, a
learned sparse-attention indexer in every layer (DeepSeek-Sparse-
Attention kind: ``ops/sparse_attention.py``) and a dropless mixture of
SiLU-gated experts (``incubate/distributed/models/moe/grouped.py``),
under an untied output head.  The vision tower is not built.

The config holds the published keys under their published names, plus
what one rank of an expert-parallel deployment is told: which experts it
holds (``experts_held``: first, count) and how many rows of the
vocabulary (``vocab_rows_held``).  The router keeps ``num_experts``
outputs; embedding, head and loss run over the rows held.

Two losses.  The language-model loss reaches the indexer through
nothing (a top-k is piecewise constant), so the indexer learns from its
own: the KL divergence from the head-averaged attention probabilities
over the selected keys to the softmax of its scores over the same keys,
summed over the layers.  The indexer reads a detached input and a
detached target, so that loss moves the indexer's weights only and the
language-model loss moves everything else (``tests/test_keye_lm.py``
holds both borders).  The model returns ``[logits, indexer_loss]``;
:class:`KeyeLMPretrainingCriterion` adds the two.

What a step counted is kept in three buffers, which the compiled step
returns with its loss: ``indexer_loss``, ``expert_tokens`` (pairs of each
held expert, by layer) and ``selected_keys``.  :meth:`observe_step`
writes them to the metrics registry; call it where the loss is read.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..nn import initializer as I
from ..ops import sparse_attention as dsa
from ..ops._primitive import apply_closure
from ..tensor import Tensor
from ..distributed.fleet.meta_parallel import ParallelCrossEntropy
from ..incubate.distributed.models.moe import grouped


@dataclass
class KeyeLMConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    # sa_config
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    q_chunk_size: int = 512
    initializer_range: float = 0.02
    # this rank's share
    experts_held: Tuple[int, int] = field(default=(0, 0))   # first, count
    vocab_rows_held: int = 0                                # 0: all

    def __post_init__(self):
        if not self.experts_held[1]:
            self.experts_held = (0, self.num_experts)
        if not self.vocab_rows_held:
            self.vocab_rows_held = self.vocab_size
        if not self.norm_topk_prob:
            raise ValueError("the gates are normalised over the experts "
                             "chosen (norm_topk_prob) in this family")
        if 2 * sum(self.mrope_section) != self.head_dim:
            raise ValueError("mrope_section covers the head's frequency "
                             "pairs")


def keye_lm_tiny(**kw):
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, mrope_section=(2, 3, 3),
                indexer_num_heads=2, indexer_head_dim=8, topk=16,
                q_chunk_size=16)
    base.update(kw)
    return KeyeLMConfig(**base)


# --------------------------------------------------------------------------
# rotary positions
# --------------------------------------------------------------------------
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


def _rms(x, weight, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


class KeyeRMSNorm(nn.Layer):
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
            jax.checkpoint(lambda x_, w: _rms(x_, w, eps)),
            [x, self.weight], name="keye_rms_norm")


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _normed_rotated(x, weight, angles, eps):
    """Per-head RMS normalisation, then the rotation.  Its float32
    insides are cheap to compute again, so the backward pass keeps the
    bf16 input and nothing else."""
    return apply_rotary(_rms(x, weight, eps), angles)


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------
def _linear(fan_in, fan_out, std):
    return nn.Linear(fan_in, fan_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)))


class KeyeIndexer(nn.Layer):
    """qI = x W_q (heads x width), kI = LayerNorm(x W_k) (one head), both
    rotated over their whole width; w = x W_w (a weight a head)."""

    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.wq = _linear(c.hidden_size,
                          c.indexer_num_heads * c.indexer_head_dim, std)
        self.wk = _linear(c.hidden_size, c.indexer_head_dim, std)
        self.k_norm = nn.LayerNorm(c.indexer_head_dim)
        self.weights_proj = _linear(c.hidden_size, c.indexer_num_heads, std)

    def parameters_in_order(self):
        return [self.wq.weight, self.wk.weight, self.k_norm.weight,
                self.k_norm.bias, self.weights_proj.weight]


class KeyeSparseAttention(nn.Layer):
    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.config = c
        self.q_proj = _linear(c.hidden_size,
                              c.num_attention_heads * c.head_dim, std)
        self.k_proj = _linear(c.hidden_size,
                              c.num_key_value_heads * c.head_dim, std)
        self.v_proj = _linear(c.hidden_size,
                              c.num_key_value_heads * c.head_dim, std)
        self.o_proj = _linear(c.num_attention_heads * c.head_dim,
                              c.hidden_size, std)
        self.q_norm = KeyeRMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = KeyeRMSNorm(c.head_dim, c.rms_norm_eps)
        self.indexer = KeyeIndexer(c)

    def _indexed(self, x, positions, wq, wk, ln_w, ln_b, ww):
        """The indexer's three outputs for one sequence ``x [S, hidden]``
        (already detached)."""
        c = self.config
        seq = x.shape[0]
        angles = rotary_angles(positions[0], c.indexer_head_dim,
                               c.rope_theta)
        q_idx = apply_rotary((x @ wq).reshape(
            seq, c.indexer_num_heads, c.indexer_head_dim), angles)
        kf = (x @ wk).astype(jnp.float32)
        mean = kf.mean(-1, keepdims=True)
        var = ((kf - mean) ** 2).mean(-1, keepdims=True)
        k_idx = ((kf - mean) * jax.lax.rsqrt(var + self.indexer.k_norm
                                             ._epsilon)
                 * ln_w.astype(jnp.float32) + ln_b.astype(jnp.float32))
        k_idx = apply_rotary(k_idx.astype(x.dtype), angles)
        return q_idx, k_idx, x @ ww

    def _one_sequence(self, x, positions, trace, wq, wk, wv, wo, qn, kn,
                      *indexer):
        c = self.config
        seq = x.shape[0]
        q_idx, k_idx, w_idx = self._indexed(jax.lax.stop_gradient(x),
                                            positions, *indexer)
        selected = dsa.select(q_idx, k_idx, w_idx, c.topk, c.q_chunk_size,
                              with_scores=trace)
        mask, scores = selected if trace else (selected, None)
        angles = rotary_angles(positions, c.head_dim, c.rope_theta,
                               c.mrope_section)
        q = (x @ wq).reshape(seq, c.num_attention_heads, c.head_dim)
        k = (x @ wk).reshape(seq, c.num_key_value_heads, c.head_dim)
        v = (x @ wv).reshape(seq, c.num_key_value_heads, c.head_dim)
        q = _normed_rotated(q, qn, angles, c.rms_norm_eps)
        k = _normed_rotated(k, kn, angles, c.rms_norm_eps)
        with jax.named_scope("sparse_core"):
            out, lse = dsa.core(q, k, v, mask)
        y = out.reshape(seq, -1) @ wo
        return (y, q_idx, k_idx, w_idx, q, k, lse, mask) + (
            (scores,) if trace else ())

    @jax.named_scope("attn")
    def forward(self, x, positions, trace=False):
        """``x [B, S, hidden]``, ``positions [3, B, S]`` -> the block's
        output and what :meth:`indexer_loss` reads (and, traced, the index
        scores ``[B, S, S]``)."""
        weights = [self.q_proj.weight, self.k_proj.weight,
                   self.v_proj.weight, self.o_proj.weight,
                   self.q_norm.weight, self.k_norm.weight
                   ] + self.indexer.parameters_in_order()
        pos = positions._value

        def closure(x_, *w):
            per_seq = [self._one_sequence(x_[b], pos[:, b], trace, *w)
                       for b in range(x_.shape[0])]
            return tuple(jnp.stack(parts) for parts in zip(*per_seq))

        out = apply_closure(closure, [x] + weights, name="keye_attention")
        return out[0], out[1:]

    @jax.named_scope("loss")
    def indexer_loss(self, aux):
        """mean over the batch of ``dsa.indexer_kl`` and the number of
        keys selected."""
        chunk = self.config.q_chunk_size
        q, k, lse, mask = (t._value for t in aux[3:7])

        def closure(q_idx, k_idx, w_idx):
            with jax.named_scope("indexer_kl"):
                losses = []
                for b in range(q.shape[0]):
                    probs = dsa.mean_head_probs(q[b], k[b], lse[b], mask[b])
                    losses.append(dsa.indexer_kl(
                        q_idx[b], k_idx[b], w_idx[b], probs, mask[b], chunk))
                return jnp.stack(losses).mean()

        return apply_closure(closure, list(aux[:3]), name="keye_indexer_kl")


class KeyeSparseMoeBlock(nn.Layer):
    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c = config
        self.top_k = c.num_experts_per_tok
        self.gate = _linear(c.hidden_size, c.num_experts,
                            c.initializer_range)
        first, held = c.experts_held
        self.experts = grouped.GroupedSwiGLUExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts, first,
            held, c.initializer_range)

    @jax.named_scope("mlp")
    def forward(self, y):
        """``y [B, S, hidden]`` -> (this rank's part of the layer's result,
        pairs of each held expert ``[held]``, the experts chosen ``[B * S,
        k]``)."""
        shape = y.shape
        flat = ops.reshape(y, [-1, shape[-1]])
        top_k = self.top_k

        def router(flat_, gate_w):
            with jax.named_scope("router"):
                logits = jnp.matmul(flat_, gate_w,
                                    preferred_element_type=jnp.float32)
                return grouped.route(logits, top_k)

        experts, gates = apply_closure(router, [flat, self.gate.weight],
                                       name="keye_router")
        out, sizes = self.experts(flat, experts, gates)
        return (ops.reshape(ops.cast(out, y.dtype), list(shape)), sizes,
                experts)


class KeyeDecoderLayer(nn.Layer):
    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c = config
        self.input_layernorm = KeyeRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KeyeSparseAttention(c)
        self.post_attention_layernorm = KeyeRMSNorm(c.hidden_size,
                                                    c.rms_norm_eps)
        self.mlp = KeyeSparseMoeBlock(c)

    def forward(self, h, positions, trace=False):
        attn, aux = self.self_attn(self.input_layernorm(h), positions, trace)
        h = h + attn
        moe, sizes, experts = self.mlp(self.post_attention_layernorm(h))
        # the next layer waits for this one's indexer loss: left to
        # itself the compiler puts every layer's off to the end of the
        # forward pass and holds their [S, S] float32 arrays till then
        out, loss = apply_closure(
            lambda *both: jax.lax.optimization_barrier(both),
            [h + moe, self.self_attn.indexer_loss(aux)], name="keye_in_turn")
        return out, loss, sizes, experts, aux


class KeyeLMModel(nn.Layer):
    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c = config
        self.config = c
        # the embedding keeps nn.Embedding's own N(0, 1): a token's row
        # then outweighs what the blocks add at their start, and routing
        # and selection follow the token.  At 0.02 the attention's
        # output over thousands of keys, nearly the same at every
        # position, is as large as the row itself, and every token of a
        # random sequence picks the same experts (PERF.md section 6)
        self.embed_tokens = nn.Embedding(c.vocab_rows_held, c.hidden_size)
        self.layers = nn.LayerList([KeyeDecoderLayer(c)
                                    for _ in range(c.num_hidden_layers)])
        self.norm = KeyeRMSNorm(c.hidden_size, c.rms_norm_eps)


class KeyeLMForCausalLM(nn.Layer):
    def __init__(self, config: KeyeLMConfig):
        super().__init__()
        c = config
        self.config = c
        self.model = KeyeLMModel(c)
        self.lm_head = _linear(c.hidden_size, c.vocab_rows_held,
                               c.initializer_range)
        held = c.experts_held[1]
        self.register_buffer("indexer_loss", Tensor(
            jnp.zeros((), jnp.float32)))
        self.register_buffer("expert_tokens", Tensor(
            jnp.zeros((c.num_hidden_layers, held), jnp.int32)))
        self.register_buffer("selected_keys", Tensor(
            jnp.zeros((), jnp.float32)))

    def forward(self, input_ids, position_ids=None, output_selection=False):
        """``input_ids [B, S]`` over the rows held; ``position_ids``
        ``[3, B, S]`` for the three rotary streams (text: left out, the
        token's index in all three).  Returns ``[logits, indexer_loss]``
        and, with ``output_selection``, the index scores ``[L, B, S, S]``,
        the selection ``[L, B, S, S]`` int8, the experts chosen ``[L, B *
        S, k]`` and the pairs of each held expert ``[L, held]``."""
        batch, seq = input_ids.shape
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32), (3, batch, seq)))
        with jax.named_scope("embed"):
            h = self.model.embed_tokens(input_ids)
        indexer_loss, sizes, chosen, scores, masks = 0.0, [], [], [], []
        for layer in self.model.layers:
            h, loss, pairs, experts, aux = layer(h, position_ids,
                                                 output_selection)
            indexer_loss = loss + indexer_loss
            sizes.append(pairs)
            if output_selection:
                chosen.append(experts)
                masks.append(aux[6])
                scores.append(aux[7])
        h = self.model.norm(h)
        with jax.named_scope("head"):
            logits = self.lm_head(h)
        tokens = ops.stack(sizes, axis=0)
        self.indexer_loss._value = indexer_loss._value.astype(jnp.float32)
        self.expert_tokens._value = tokens._value
        self.selected_keys._value = jnp.float32(
            batch * len(sizes) * selected_keys(seq, self.config.topk))
        if output_selection:
            return [logits, indexer_loss, ops.stack(scores, axis=0),
                    ops.stack(masks, axis=0), ops.stack(chosen, axis=0),
                    tokens]
        return [logits, indexer_loss]

    def observe_step(self):
        """Writes what the last step counted to the metrics registry:
        ``moe_pairs_total{layer}``, ``moe_expert_tokens_max{layer}``,
        ``moe_expert_tokens_mean{layer}``, ``dsa_selected_keys_total``.
        It reads buffers the step returned with its loss, so where the
        loss has been read it waits for nothing."""
        from ..observability import metrics
        reg = metrics.registry()
        tokens = jax.device_get(self.expert_tokens._value)
        for i, row in enumerate(tokens):
            labels = {"layer": str(i)}
            reg.counter("moe_pairs_total",
                        "(token, expert) pairs computed by the experts "
                        "held here", labels=labels).inc(int(row.sum()))
            reg.gauge("moe_expert_tokens_max",
                      "pairs of the fullest held expert in the last step "
                      "observed", labels=labels).set(float(row.max()))
            reg.gauge("moe_expert_tokens_mean",
                      "pairs of a held expert in the last step observed, "
                      "on average", labels=labels).set(float(row.mean()))
        reg.counter("dsa_selected_keys_total",
                    "keys the sparse attention read, summed over queries "
                    "and layers").inc(float(self.selected_keys._value))


def selected_keys(seq: int, topk: int) -> int:
    """sum over t < seq of min(t + 1, topk)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


class KeyeLMPretrainingCriterion(nn.Layer):
    """``L_LM + L_I``: the mean cross-entropy over the rows held plus the
    indexer's loss as the model returns it (weight 1)."""

    def __init__(self, config: Optional[KeyeLMConfig] = None):
        super().__init__()
        self.loss_fn = ParallelCrossEntropy()

    @jax.named_scope("loss")
    def forward(self, logits, indexer_loss, labels):
        return ops.mean(self.loss_fn(logits, labels)) + indexer_loss
