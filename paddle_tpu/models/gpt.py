"""GPT family — flagship model (baseline config 4: GPT-3 1.3B hybrid
dp+mp+pp, BASELINE.json:10; upstream impl lives in PaddleNLP
gpt/modeling.py on top of core fleet.meta_parallel layers).

TPU-first: attention uses the flash kernel (Pallas on TPU), all linear
layers are the annotation-carrying mp layers so one model definition
serves serial / TP / PP execution; the pipeline variant expresses the
decoder stack as LayerDescs for the compiled 1F1B/GPipe schedule.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax

from .. import ops
from ..tensor import Tensor
from .. import nn
from ..nn import initializer as I
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, PipelineLayer, LayerDesc, SharedLayerDesc)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    recompute: bool = False
    # parallel knobs (informational; actual sharding comes from specs)
    tensor_parallel_degree: int = 1
    # context-parallel attention over the 'sep' mesh axis when its
    # degree > 1: "ring" (ppermute K/V rotation) or "ulysses"
    # (head-scatter all_to_all).  SURVEY.md §5.7.
    context_parallel: str = "ring"


def gpt_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                max_position_embeddings=128, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
    base.update(kw)
    return GPTConfig(**base)


def gpt2_small(**kw):
    return GPTConfig(**kw)


def gpt3_1p3b(**kw):
    base = dict(vocab_size=50304, hidden_size=2048,
                num_hidden_layers=24, num_attention_heads=16,
                intermediate_size=8192, max_position_embeddings=2048)
    base.update(kw)
    return GPTConfig(**base)


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    @jax.named_scope("embed")
    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            seq = input_ids.shape[1]
            position_ids = ops.arange(0, seq, 1, dtype="int64")
            position_ids = ops.unsqueeze(position_ids, 0)
            position_ids = ops.expand(position_ids,
                                      [input_ids.shape[0], seq])
        emb = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        return self.dropout(emb)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.hidden_size = config.hidden_size
        self.use_flash = config.use_flash_attention
        self.attn_drop = config.attention_probs_dropout_prob
        self.context_parallel = config.context_parallel
        init = nn.ParamAttr(initializer=I.Normal(
            0.0, config.initializer_range))
        self.qkv_proj = ColumnParallelLinear(
            config.hidden_size, 3 * config.hidden_size, weight_attr=init,
            gather_output=False)
        self.out_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size, weight_attr=init,
            input_is_parallel=True)

    @jax.named_scope("attn")
    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        from ..distributed import collective as coll
        mesh = coll.get_mesh()
        sep = int(mesh.shape.get("sep", 1)) if mesh is not None else 1
        if sep > 1:
            # context-parallel attention: the seq dim is sharded on 'sep'
            if self.attn_drop > 0.0 and self.training:
                raise ValueError(
                    "context-parallel attention does not support "
                    "attention dropout; set "
                    "attention_probs_dropout_prob=0.0 when sep_degree>1")
            from ..nn.functional import (ring_flash_attention,
                                         ulysses_attention)
            cp = (ulysses_attention if self.context_parallel == "ulysses"
                  else ring_flash_attention)
            out = cp(q, k, v, causal=True)
        elif self.use_flash:
            from ..nn.functional import flash_attention
            out, _ = flash_attention(q, k, v, causal=True,
                                     dropout=self.attn_drop,
                                     training=self.training)
        else:
            out = ops.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.attn_drop,
                training=self.training)
        out = ops.reshape(out, [b, s, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = nn.ParamAttr(initializer=I.Normal(
            0.0, config.initializer_range))
        self.fc1 = ColumnParallelLinear(config.hidden_size,
                                        config.intermediate_size,
                                        weight_attr=init,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(config.intermediate_size,
                                     config.hidden_size, weight_attr=init,
                                     input_is_parallel=True)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.fc2(ops.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout1 = nn.Dropout(config.hidden_dropout_prob)
        self.dropout2 = nn.Dropout(config.hidden_dropout_prob)
        self._recompute = config.recompute

    def _block(self, x):
        x = x + self.dropout1(self.attn(self.ln1(x)))
        x = x + self.dropout2(self.mlp(self.ln2(x)))
        return x

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._block, x)
        return self._block(x)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        for layer in self.layers:
            x = layer(x)
        return self.final_norm(x)


class GPTForCausalLM(nn.Layer):
    """LM head ties the vocab-parallel embedding weight (upstream
    parity: GPT lm head matmuls against word_embeddings.weight^T)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def lm_weight(self):
        return self.gpt.embeddings.word_embeddings.weight

    def forward(self, input_ids, position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        with jax.named_scope("head"):
            logits = ops.matmul(hidden, self.lm_weight(),
                                transpose_y=True)
        return logits


class GPTPretrainingCriterion(nn.Layer):
    """Causal LM loss (parallel cross entropy over the sharded vocab)."""

    def __init__(self, config: Optional[GPTConfig] = None):
        super().__init__()
        self.loss_fn = ParallelCrossEntropy()

    @jax.named_scope("loss")
    def forward(self, logits, labels, loss_mask=None):
        # logits [b, s, V]; labels [b, s] — shift-by-one is the caller's
        # responsibility
        loss = self.loss_fn(logits, labels)
        if loss_mask is not None:
            loss = loss * loss_mask
            return ops.sum(loss) / ops.maximum(
                ops.sum(loss_mask), ops.full([], 1e-9))
        return ops.mean(loss)


# ---------------------------------------------------------------------------
# Pipeline variant
# ---------------------------------------------------------------------------
class _EmbeddingPipe(GPTEmbeddings):
    def forward(self, input_ids):
        return super().forward(input_ids)


class _NormLogitsPipe(nn.Layer):
    """Final norm + tied-weight logits as the last pipeline stage."""

    def __init__(self, config: GPTConfig, embeddings_key="embed"):
        super().__init__()
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_epsilon)
        self.lm_weight = None  # bound by GPTForCausalLMPipe

    def forward(self, x):
        x = self.final_norm(x)
        with jax.named_scope("head"):
            return ops.matmul(x, self.lm_weight, transpose_y=True)


class GPTForCausalLMPipe(PipelineLayer):
    def __init__(self, config: GPTConfig, num_stages=1, topology=None,
                 recompute_interval=0):
        self.config = config
        descs = [LayerDesc(_EmbeddingPipe, config)]
        for _ in range(config.num_hidden_layers):
            descs.append(LayerDesc(GPTDecoderLayer, config))
        descs.append(LayerDesc(_NormLogitsPipe, config))
        super().__init__(descs, num_stages=num_stages, topology=topology,
                         loss_fn=GPTPretrainingCriterion(config),
                         seg_method="layer:GPTDecoderLayer",
                         recompute_interval=recompute_interval)
        # tie lm head to the embedding table
        emb = self.run_function[0]
        head = self.run_function[-1]
        head.lm_weight = emb.word_embeddings.weight
