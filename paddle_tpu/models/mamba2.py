"""The Mamba-2 mixer (Dao and Gu 2024) as ``models/granite_hybrid.py`` and
``models/nemotron_h.py`` build it, each from its own config's sizes:

    [z, xBC, dt] = x W_in;  xBC = silu(conv(xBC))     depthwise, causal, with bias
    [x, B, C] = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)   a head
    y = scan(x, dt, A, B, C) + D x                    ``ops/ssm.py``, in chunks
    y = RMSNorm(y * silu(z)) w;  out = y W_out

B and C come in ``groups`` groups, head h reading group ``h // (heads /
groups)``, and the gated norm takes its mean square over each group's
channels: one group is a norm over all of ``d_inner``.  The second line
is one call, ``ssm.conv_silu_split``, which is told where xBC lies in
the projection's result and hands x, B and C to the scan apart: on a TPU the
Mosaic kernels of ``ops/ssm_conv_kernels.py``, elsewhere
``ssm.causal_conv1d``, SiLU and a split (``ssm.conv_form`` says which,
as ``ssm.scan_form`` does of the scan).  The mixer opens the sub-scopes
``ssm_proj``, ``ssm_conv``, ``ssm_scan`` and ``ssm_norm`` inside ``attn``
and counts its scans (``ssm_scan_chunks_total{layer}``,
``ssm_scan_state_bytes{layer}``) as they are traced; the kernels count
their own calls (``ssm_scan_kernel_visits_total{kind}``,
``ssm_conv_kernel_calls_total{kind}``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from ..ops import ssm
from ..ops._primitive import apply_closure
from .blocks import (Conv1d, InverseSoftplusOfSteps, LogOfUniform, RMSNorm,
                     linear, rms, silu_gate, step_sizes)


# jitted: the groups of a norm, and every norm of a model, share one trace
# and one lowering of it (a step of four blocks of eight groups traced
# and lowered 680 equations more without); the step calls the program
# ``_rms``
@jax.jit
def _rms(x, weight, eps):
    return rms(x, weight, eps)


def _rms_by_group(x, weight, eps, groups: int):
    """:func:`rms` with the mean square over each of ``groups`` groups of
    channels; one group is ``rms`` itself.  A group at a time, each a
    block of whole columns, which the chip takes as it lies: rows-major,
    as the mixer's arrays lie since its convolution is a kernel, it lays
    a float32 ``[S, groups, n]`` out anew, a copy each way (8.3 ms a step
    in the Nemotron cell: ``PERF.md`` section 6, PR 37)."""
    if groups == 1:
        return rms(x, weight, eps)
    size = x.shape[-1] // groups
    return jnp.concatenate(
        [_rms(x[..., g * size:(g + 1) * size],
              weight[g * size:(g + 1) * size], eps)
         for g in range(groups)], axis=-1)


def _count_scan(layer: int, seq: int, calls: int, heads: int, width: int,
                state: int, chunk: int):
    """As a mixer's scans are traced: the chunks x heads they walk and
    the states they pass from chunk to chunk."""
    from ..observability import metrics
    reg, labels = metrics.registry(), {"layer": str(layer)}
    reg.counter("ssm_scan_chunks_total",
                "chunks x heads of the state-space scans, counted a call "
                "when the call is traced", labels=labels).inc(
        calls * ssm.scan_chunks(seq, heads, chunk))
    reg.gauge("ssm_scan_state_bytes",
              "bytes of the float32 states one scan passes from chunk to "
              "chunk", labels=labels).set(ssm.scan_state_bytes(
                  seq, heads, width, state, chunk))


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------
class Mamba2Mixer(nn.Layer):
    """``heads`` heads of width ``head_dim`` over ``groups`` groups of B
    and C with ``state`` states each; ``out_proj`` starts at
    ``out_range``, every other matrix at ``initializer_range``."""

    def __init__(self, hidden_size: int, heads: int, head_dim: int,
                 state: int, groups: int, conv_width: int, chunk: int,
                 eps: float, initializer_range: float, out_range: float,
                 layer_idx: int):
        super().__init__()
        self.heads, self.head_dim, self.state = heads, head_dim, state
        self.groups, self.chunk, self.eps = groups, chunk, eps
        self.layer_idx = layer_idx
        self.d_inner = heads * head_dim
        self.conv_dim = self.d_inner + 2 * groups * state
        self.in_proj = linear(hidden_size,
                              self.d_inner + self.conv_dim + heads,
                              initializer_range)
        self.conv1d = Conv1d(self.conv_dim, conv_width)
        # a step drawn log-uniformly from [0.001, 0.1] and put through
        # the inverse of softplus; A uniform in [1, 16]; D = 1: Mamba-2's
        # own start
        self.dt_bias = self.create_parameter(
            shape=[heads], default_initializer=InverseSoftplusOfSteps())
        self.A_log = self.create_parameter(
            shape=[heads], default_initializer=LogOfUniform(1.0, 16.0))
        self.D = self.create_parameter(
            shape=[heads], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(self.d_inner, eps)
        self.out_proj = linear(self.d_inner, hidden_size, out_range)

    def _one_sequence(self, x, w_in, conv_w, conv_b, dt_bias, a_log, d,
                      norm_w, w_out):
        seq, inner = x.shape[0], self.d_inner
        with jax.named_scope("ssm_proj"):
            proj = x @ w_in
            z, xbc, dt = jnp.split(
                proj, (inner, inner + self.conv_dim), axis=-1)
        with jax.named_scope("ssm_conv"):
            xs, b, cc = ssm.conv_silu_split(
                xbc, conv_w, conv_b, inner, self.groups, self.state,
                lies_in=(proj, inner))
        with jax.named_scope("ssm_scan"):
            # [seq, heads, width] is a view of the projection's own
            # [seq, d_inner]: the kernels read and write it as it lies
            y = ssm.ssd_scan(
                xs.reshape(seq, self.heads, self.head_dim),
                step_sizes(dt, dt_bias), -jnp.exp(a_log.astype(jnp.float32)),
                b.reshape(seq, self.groups, self.state),
                cc.reshape(seq, self.groups, self.state),
                d.astype(jnp.float32), self.chunk)
        with jax.named_scope("ssm_norm"):
            y = _rms_by_group(silu_gate(z, y.reshape(seq, inner)), norm_w,
                              self.eps, self.groups)
        with jax.named_scope("ssm_proj"):
            return y @ w_out

    @jax.named_scope("attn")
    def forward(self, x):
        """``x [B, S, hidden]`` -> the mixer's output."""
        weights = [self.in_proj.weight, self.conv1d.weight, self.conv1d.bias,
                   self.dt_bias, self.A_log, self.D, self.norm.weight,
                   self.out_proj.weight]
        _count_scan(self.layer_idx, x.shape[1], x.shape[0], self.heads,
                    self.head_dim, self.state, self.chunk)

        def closure(x_, *w):
            return jnp.stack([self._one_sequence(x_[b], *w)
                              for b in range(x_.shape[0])])

        return apply_closure(closure, [x] + weights, name="mamba2_mixer")
