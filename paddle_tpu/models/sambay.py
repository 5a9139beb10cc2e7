"""SambaY (Ren et al. 2025, arXiv:2507.06607; ``model_type`` phi4flash): a
decoder-hybrid-decoder.  A self-decoder of Mamba-1 layers alternating
with sliding-window attention; then one Mamba-1 layer whose scan output
is kept as the *memory* ``m``, and one full-attention layer whose keys
and values are kept; then a cross-decoder whose layers make neither: its
gated memory units gate ``m`` and its attention layers have a query and
an output projection only and read the kept K and V (YOCO).  Every
attention layer is differential attention (Ye et al. 2024,
arXiv:2410.05258).  Pre-norm blocks with ``LayerNorm`` (bias), a
SiLU-gated MLP in every layer, no positions anywhere, an output head
tied to the embedding.

    h += mixer(LN1(h));  h += W2 (up * silu(gate)), [gate, up] = W1 LN2(h)
    logits = LN(h) E^T

Layer ``l`` of ``n_self + 2 + n_cross`` (``mb_per_layer`` 2: even ``l``
is Mamba-kind, odd attention-kind):

    l <  n_self        mamba | swa (window ``sliding_window``)
    l == n_self        mamba, and hands on m = its scan's y, before the gate
    l == n_self + 1    full causal attention, and hands on its K and V
    l >  n_self + 1    gmu on m | cross-attention on K, V (causal, full)

Mamba-1 (Gu and Dao 2023): ``[x, z] = W_in u``; ``x = silu(conv(x) + b)``;
``[d, B, C] = W_x x``; ``dt = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``
a channel and state; ``y = selective_scan(x, dt, A, B, C, D)``
(``ops/ssm.py``); out ``W_out (y * silu(z))``.  Gated memory unit: ``W_out
(m * silu(W_in u))``.  Differential attention: adjacent heads pair; a
pair's output is ``(softmax(q1 k1^T) - lambda softmax(q2 k2^T)) [v1, v2]``,
as four calls of ``flash_attention`` at one head width (q1k1v1, q1k1v2,
q2k2v1, q2k2v2), RMS-normed over the pair's ``2 x head`` outputs with a
learned weight and scaled by ``1 - lambda0``; ``lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda0``, ``lambda0 = 0.8 - 0.6 exp(-0.3 l)``.

The handed-on ``m``, K and V are outputs of the layer that makes them and
inputs of the layers that read them, never module state: a reader that
goes through ``fleet.recompute`` is given them as arguments, and their
gradients flow back from every reader.  Inside ``attn`` the mixers open
the sub-scopes ``ssm_proj``, ``ssm_conv``, ``s6_scan``, ``gmu``,
``attn_proj``, ``swa_core``, ``full_core``, ``cross_core`` and
``diff_combine``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn, ops
from ..framework import dtype as dtypes
from ..nn import initializer as I
from ..ops import pallas_ops, ssm
from ..ops._primitive import apply_closure
from ..distributed.fleet.meta_parallel import ParallelCrossEntropy
from .keye_lm import _linear, _rms
from .mamba2 import (_Conv1d, _InverseSoftplusOfSteps, _silu_gate,
                     _step_sizes)

KINDS = ("mamba", "swa", "mamba_memory", "full_kv", "gmu", "cross")
SUBLN_EPS = 1e-5


@dataclass
class SambaYConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    n_self: int = 16                # layers of the self-decoder
    n_cross: int = 14               # of the cross-decoder, after the two producers
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0          # 0: hidden_size / 16
    initializer_range: float = 0.02
    lambda_std: float = 0.1
    vocab_rows_held: int = 0        # 0: all
    recompute: Tuple[int, ...] = ()  # the layers that go through fleet.recompute

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.n_self % 2 or self.n_cross % 2:
            raise ValueError("Mamba-kind and attention-kind layers "
                             "alternate (mb_per_layer 2): n_self and "
                             "n_cross are even")
        if not self.mamba_dt_rank:
            self.mamba_dt_rank = self.hidden_size // 16
        if not self.vocab_rows_held:
            self.vocab_rows_held = self.vocab_size
        if self.num_attention_heads % (2 * self.num_key_value_heads) and \
                self.num_attention_heads != self.num_key_value_heads:
            raise ValueError("query heads pair, and the pairs share the "
                             "key/value pairs evenly")
        self.recompute = tuple(sorted(self.recompute))
        if set(self.recompute) - set(range(self.num_hidden_layers)):
            raise ValueError("recompute names layers of the model")

    @property
    def num_hidden_layers(self) -> int:
        return self.n_self + 2 + self.n_cross

    @property
    def kinds(self) -> Tuple[str, ...]:
        def kind(l):
            if l < self.n_self:
                return "swa" if l % 2 else "mamba"
            if l < self.n_self + 2:
                return "full_kv" if l % 2 else "mamba_memory"
            return "cross" if l % 2 else "gmu"

        return tuple(kind(l) for l in range(self.num_hidden_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size


def sambay_tiny(**kw):
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_attention_heads=4, num_key_value_heads=2, n_self=2,
                n_cross=2, sliding_window=16, mamba_d_state=4,
                mamba_dt_rank=8)
    base.update(kw)
    return SambaYConfig(**base)


def lambda_init(layer_idx: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


# --------------------------------------------------------------------------
# pieces
# --------------------------------------------------------------------------
class SambaYLayerNorm(nn.LayerNorm):
    """``nn.LayerNorm`` (float32 under O2, as every LayerNorm;
    ``ops.layer_norm``'s arithmetic) whose backward pass keeps its input
    as it is stored and computes the float32 insides again."""

    def forward(self, x):
        shape, eps = tuple(self._normalized_shape), self._epsilon
        return apply_closure(
            jax.checkpoint(lambda x_, w, b: ops.layer_norm.raw(
                x_, shape, w, b, eps)),
            [x, self.weight, self.bias], name="sambay_layer_norm")


def _linear_with_bias(fan_in, fan_out, std):
    return nn.Linear(fan_in, fan_out,
                     weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)),
                     bias_attr=nn.ParamAttr(initializer=I.Constant(0.0)))


class _LogOfStates(I.Initializer):
    """``A_log [channels, N]``: the log of 1 .. N in every channel, the
    Mamba paper's start (S4D-real)."""

    def __call__(self, shape, dtype):
        row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, tuple(shape)).astype(
            dtypes.to_jax_dtype(dtype))


@jax.checkpoint
def _diff_combine(parts, lams, weight, lam0):
    """A pair's two maps' outputs ``[B, S, pairs, 2 x head]`` from the
    four calls' ``[B, S, pairs, head]``, their difference, its RMS norm
    over ``2 x head`` and the scale."""
    a11, a12, a21, a22 = (p.astype(jnp.float32) for p in parts)
    lq1, lk1, lq2, lk2 = (v.astype(jnp.float32) for v in lams)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    out = (jnp.concatenate([a11, a12], -1)
           - lam * jnp.concatenate([a21, a22], -1))
    return (_rms(out, weight, SUBLN_EPS) * (1.0 - lam0)).astype(
        parts[0].dtype)


def _halves(a):
    """``[B, S, heads, head] -> `` the even and the odd heads, ``[B, S,
    heads / 2, head]`` each: adjacent heads pair."""
    b, s, h, d = a.shape
    a = a.reshape(b, s, h // 2, 2, d)
    return a[:, :, :, 0], a[:, :, :, 1]


def differential_attention(q, k, v, lams, weight, lam0: float,
                           window: Optional[int], core: str):
    """``q [B, S, heads, head]`` over ``k, v [B, S, kv heads, head]``,
    causal, inside the ``window`` where there is one: ``[B, S, heads x
    head]``.  The four calls open the sub-scope ``core``."""
    q1, q2 = _halves(q)
    k1, k2 = _halves(k)
    v1, v2 = _halves(v)
    flash = pallas_ops.flash_attention.raw
    with jax.named_scope(core):
        parts = [flash(q_, k_, v_, causal=True, window=window)
                 for q_, k_, v_ in ((q1, k1, v1), (q1, k1, v2),
                                    (q2, k2, v1), (q2, k2, v2))]
    with jax.named_scope("diff_combine"):
        out = _diff_combine(parts, lams, weight, lam0)
    return out.reshape(q.shape[0], q.shape[1], -1)


# --------------------------------------------------------------------------
# mixers
# --------------------------------------------------------------------------
class SambaYMamba(nn.Layer):
    """The Mamba-1 mixer; ``hands_on`` also returns the scan's ``y``."""

    def __init__(self, config: SambaYConfig, layer_idx: int,
                 hands_on: bool):
        super().__init__()
        c, std = config, config.initializer_range
        self.config, self.layer_idx, self.hands_on = c, layer_idx, hands_on
        inner, rank, state = c.d_inner, c.mamba_dt_rank, c.mamba_d_state
        self.in_proj = _linear(c.hidden_size, 2 * inner, std)
        self.conv1d = _Conv1d(inner, c.mamba_d_conv)
        self.x_proj = _linear(inner, rank + 2 * state, std)
        bound = rank ** -0.5
        self.dt_proj = nn.Linear(
            rank, inner,
            weight_attr=nn.ParamAttr(initializer=I.Uniform(-bound, bound)),
            bias_attr=nn.ParamAttr(initializer=_InverseSoftplusOfSteps()))
        self.A_log = self.create_parameter(
            shape=[inner, state], default_initializer=_LogOfStates())
        self.D = self.create_parameter(
            shape=[inner], default_initializer=I.Constant(1.0))
        self.out_proj = _linear(inner, c.hidden_size, std)

    def _one_sequence(self, u, w_in, conv_w, conv_b, w_x, w_dt, b_dt, a_log,
                      d, w_out):
        c = self.config
        rank, state = c.mamba_dt_rank, c.mamba_d_state
        with jax.named_scope("ssm_proj"):
            x, z = jnp.split(u @ w_in, 2, axis=-1)
        with jax.named_scope("ssm_conv"):
            x = ssm.causal_conv_silu(x, conv_w, conv_b)
        with jax.named_scope("ssm_proj"):
            low, b, cc = jnp.split(x @ w_x, (rank, rank + state), axis=-1)
            steps = low @ w_dt
        with jax.named_scope("s6_scan"):
            y = ssm.selective_scan(
                x, _step_sizes(steps, b_dt),
                -jnp.exp(a_log.astype(jnp.float32)), b, cc,
                d.astype(jnp.float32))
        with jax.named_scope("ssm_proj"):
            return _silu_gate(z, y) @ w_out, y

    def _count(self, batch: int, seq: int):
        from ..observability import metrics
        c = self.config
        reg, labels = metrics.registry(), {"layer": str(self.layer_idx)}
        reg.counter("s6_scan_chunks_total",
                    "chunks of the selective scans (a decay a channel and "
                    "state), counted a call when the call is traced",
                    labels=labels).inc(
            batch * ssm.selective_scan_chunks(seq))
        reg.gauge("s6_scan_state_bytes",
                  "bytes of the float32 states one selective scan keeps "
                  "for its backward pass: the one each chunk starts from",
                  labels=labels).set(ssm.selective_scan_state_bytes(
                      seq, c.d_inner, c.mamba_d_state))

    @jax.named_scope("attn")
    def forward(self, u):
        """``u [B, S, hidden]`` -> the mixer's output, and where this
        layer hands on its memory also ``m [B, S, d_inner]``."""
        weights = [self.in_proj.weight, self.conv1d.weight, self.conv1d.bias,
                   self.x_proj.weight, self.dt_proj.weight,
                   self.dt_proj.bias, self.A_log, self.D,
                   self.out_proj.weight]
        self._count(u.shape[0], u.shape[1])
        hands_on = self.hands_on

        def closure(u_, *w):
            outs = [self._one_sequence(u_[b], *w) for b in range(u_.shape[0])]
            out = jnp.stack([o for o, _ in outs])
            return (out, jnp.stack([y for _, y in outs])) if hands_on \
                else out

        return apply_closure(closure, [u] + weights, name="sambay_mamba")


class SambaYGatedMemory(nn.Layer):
    """``W_out (m * silu(W_in u))``: no scan of its own."""

    def __init__(self, config: SambaYConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.in_proj = _linear(c.hidden_size, c.d_inner, std)
        self.out_proj = _linear(c.d_inner, c.hidden_size, std)

    @jax.named_scope("attn")
    def forward(self, u, memory):
        def closure(u_, m, w_in, w_out):
            with jax.named_scope("gmu"):
                return _silu_gate(u_ @ w_in, m) @ w_out

        return apply_closure(
            closure, [u, memory, self.in_proj.weight, self.out_proj.weight],
            name="sambay_gmu")


class SambaYAttention(nn.Layer):
    """Differential attention.  ``kind`` ``swa`` (inside the window) and
    ``full_kv`` (which also returns its K and V) project q, k and v;
    ``cross`` projects q only and is given K and V."""

    def __init__(self, config: SambaYConfig, layer_idx: int, kind: str):
        super().__init__()
        c, std = config, config.initializer_range
        self.config, self.kind = c, kind
        self.lambda_init = lambda_init(layer_idx)
        kv = c.num_key_value_heads * c.head_dim
        width = c.hidden_size + (0 if kind == "cross" else 2 * kv)
        self.Wqkv = _linear_with_bias(c.hidden_size, width, std)
        self.out_proj = _linear_with_bias(c.hidden_size, c.hidden_size, std)
        vector = dict(shape=[c.head_dim],
                      default_initializer=I.Normal(0.0, c.lambda_std))
        self.lambda_q1 = self.create_parameter(**vector)
        self.lambda_k1 = self.create_parameter(**vector)
        self.lambda_q2 = self.create_parameter(**vector)
        self.lambda_k2 = self.create_parameter(**vector)
        self.subln = self.create_parameter(
            shape=[2 * c.head_dim], default_initializer=I.Constant(1.0))

    @jax.named_scope("attn")
    def forward(self, u, key=None, value=None):
        c, kind = self.config, self.kind
        heads, kv_heads, dim = (c.num_attention_heads,
                                c.num_key_value_heads, c.head_dim)
        window = c.sliding_window if kind == "swa" else None
        core = {"swa": "swa_core", "full_kv": "full_core",
                "cross": "cross_core"}[kind]
        lam0 = self.lambda_init

        def closure(u_, w, b, wo, bo, lq1, lk1, lq2, lk2, subln, *handed):
            batch, seq = u_.shape[:2]
            with jax.named_scope("attn_proj"):
                qkv = u_ @ w + b
                q = qkv[..., :c.hidden_size].reshape(batch, seq, heads, dim)
                if handed:
                    k, v = handed
                else:
                    k, v = (a.reshape(batch, seq, kv_heads, dim)
                            for a in jnp.split(qkv[..., c.hidden_size:], 2,
                                               axis=-1))
            out = differential_attention(
                q, k, v, (lq1, lk1, lq2, lk2), subln, lam0, window, core)
            with jax.named_scope("attn_proj"):
                out = out @ wo + bo
            return (out, k, v) if kind == "full_kv" else out

        handed = [] if kind != "cross" else [key, value]
        return apply_closure(
            closure, [u, self.Wqkv.weight, self.Wqkv.bias,
                      self.out_proj.weight, self.out_proj.bias,
                      self.lambda_q1, self.lambda_k1, self.lambda_q2,
                      self.lambda_k2, self.subln] + handed,
            name="sambay_attention")


class SambaYMLP(nn.Layer):
    def __init__(self, config: SambaYConfig):
        super().__init__()
        c, std = config, config.initializer_range
        self.fc1 = _linear(c.hidden_size, 2 * c.intermediate_size, std)
        self.fc2 = _linear(c.intermediate_size, c.hidden_size, std)

    @jax.named_scope("mlp")
    def forward(self, x):
        def closure(x_, w1, w2):
            gate, up = jnp.split(x_ @ w1, 2, axis=-1)
            return _silu_gate(gate, up) @ w2

        return apply_closure(closure, [x, self.fc1.weight, self.fc2.weight],
                             name="sambay_mlp")


class SambaYDecoderLayer(nn.Layer):
    """A layer takes the stream and what its kind reads (``gmu``: m;
    ``cross``: K, V) and returns the stream and what its kind hands on
    (``mamba_memory``: m; ``full_kv``: K, V)."""

    def __init__(self, config: SambaYConfig, layer_idx: int):
        super().__init__()
        c = config
        self.kind = kind = c.kinds[layer_idx]
        self.input_layernorm = SambaYLayerNorm(c.hidden_size,
                                               c.layer_norm_eps)
        if kind in ("mamba", "mamba_memory"):
            self.mixer = SambaYMamba(c, layer_idx, kind == "mamba_memory")
        elif kind == "gmu":
            self.mixer = SambaYGatedMemory(c)
        else:
            self.mixer = SambaYAttention(c, layer_idx, kind)
        self.post_attention_layernorm = SambaYLayerNorm(c.hidden_size,
                                                        c.layer_norm_eps)
        self.mlp = SambaYMLP(c)
        self._recompute = layer_idx in c.recompute

    def _block(self, h, *read):
        # a block's norm and residual sum are the block's: the device
        # time by block reads them there and not as unscoped
        with jax.named_scope("attn"):
            x = self.input_layernorm(h)
        out = self.mixer(x, *read)
        out, handed = (out[0], tuple(out[1:])) if isinstance(
            out, tuple) else (out, ())
        with jax.named_scope("attn"):
            h = h + out
        with jax.named_scope("mlp"):
            x = self.post_attention_layernorm(h)
        out = self.mlp(x)
        with jax.named_scope("mlp"):
            h = h + out
        return (h,) + handed if handed else h

    @property
    def recomputed(self) -> bool:
        return self._recompute and self.training

    def forward(self, h, *read):
        if self.recomputed:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._block, h, *read)
        return self._block(h, *read)


class SambaYModel(nn.Layer):
    def __init__(self, config: SambaYConfig):
        super().__init__()
        c = config
        self.embed_tokens = nn.Embedding(
            c.vocab_rows_held, c.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, c.initializer_range)))
        self.layers = nn.LayerList([SambaYDecoderLayer(c, i)
                                    for i in range(c.num_hidden_layers)])
        self.final_layernorm = SambaYLayerNorm(c.hidden_size,
                                               c.layer_norm_eps)


def _bytes(tensor) -> int:
    return int(tensor._value.size * tensor._value.dtype.itemsize)


class SambaYForCausalLM(nn.Layer):
    """The head multiplies by the embedding's own matrix, over the rows
    held."""

    def __init__(self, config: SambaYConfig):
        super().__init__()
        self.config = config
        self.model = SambaYModel(config)

    def _count(self, memory, key, value):
        from ..observability import metrics
        reg = metrics.registry()
        layers = self.model.layers
        for kind in KINDS:
            reg.gauge("recompute_layers", "layers of the model last traced "
                      "that run their forward pass again in the backward "
                      "pass, by their kind", labels={"kind": kind}).set(sum(
                          l.recomputed for l in layers if l.kind == kind))
        reg.gauge("yoco_shared_kv_bytes", "bytes of the keys and values "
                  "one layer makes and the cross-decoder's attention "
                  "layers read, in the model last traced").set(
                      _bytes(key) + _bytes(value))
        reg.gauge("gmu_memory_bytes", "bytes of the scan output one layer "
                  "makes and the gated memory units read, in the model "
                  "last traced").set(_bytes(memory))

    def forward(self, input_ids):
        """``input_ids [B, S]`` over the rows held -> logits ``[B, S, rows
        held]``."""
        with jax.named_scope("embed"):
            h = self.model.embed_tokens(input_ids)
        memory = key = value = None
        for layer in self.model.layers:
            if layer.kind == "mamba_memory":
                h, memory = layer(h)
            elif layer.kind == "full_kv":
                h, key, value = layer(h)
            elif layer.kind == "gmu":
                h = layer(h, memory)
            elif layer.kind == "cross":
                h = layer(h, key, value)
            else:
                h = layer(h)
        self._count(memory, key, value)
        with jax.named_scope("head"):
            h = self.model.final_layernorm(h)
            return ops.matmul(h, self.model.embed_tokens.weight,
                              transpose_y=True)


class SambaYPretrainingCriterion(nn.Layer):
    """Mean cross-entropy over the rows held."""

    def __init__(self, config: Optional[SambaYConfig] = None):
        super().__init__()
        self.loss_fn = ParallelCrossEntropy()

    @jax.named_scope("loss")
    def forward(self, logits, labels):
        return ops.mean(self.loss_fn(logits, labels))
