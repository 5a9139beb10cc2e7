"""The double-gated short convolution of LFM2: a causal depthwise
convolution of a few taps with a gate before it and a gate after it, all
three read from one projection's result, a sequence at a time.

    [B | C | x] = bcx          three equal blocks of ``channels`` columns,
                               in this order, as the in-projection wrote them
    z = B * x
    c[t] = sum_k weight[:, k] * z[t - (W - 1) + k]     (z before the start = 0)
    y = C * c

No bias and no activation.  The operator moves bytes and computes next to
nothing (2 W + 2 operations a channel and position against eight bytes
read and written), between two projections the MXU bounds.  Sums are
float32; y is in bcx's dtype.

The backward pass is the operator's own (``jax.custom_vjp``): it is given
``bcx``, ``weight`` and ``dy``, makes ``z`` and ``c`` again and keeps
nothing else, so a layer holds the projection's result and no float32
copy of it.  With ``dc = dy * C``:

    dC = dy * c;   dz[s] = sum_k weight[:, k] * dc[s + (W - 1) - k]
    dB = dz * x;   dx = dz * B
    dweight[:, k] = sum_t dc[t] * z[t - (W - 1) + k]

Two forms, which :func:`gated_short_conv_form` names from platform and
shape, as ``ssm.conv_form`` does of Mamba's convolution: ``"kernels"``,
two Mosaic calls (``ops/short_conv_kernels.py``: ``bcx`` read where the
in-projection wrote it, the taps' shifted terms in VMEM only, forward in
one call and the walk back in one), on a TPU or under the interpreter
where the shape suits them; ``"xla"``, the XLA operations below,
everywhere else and as the kernels' reference.  Either is the one
``custom_vjp`` here, given the same inputs.  The calls count themselves as
they are traced: ``short_conv_calls_total{kind=forward|backward}``
whatever the form, and ``short_conv_kernel_calls_total{kind=fwd|bwd}``
the kernels' calls alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pallas_ops, short_conv_kernels


def gated_short_conv_form(seq: int, channels: int, width: int) -> str:
    """Which form of :func:`gated_short_conv` runs, from platform and
    shape: ``"kernels"`` on a TPU (or under the interpreter) where the
    channels are whole lane groups, the sequence a whole number of sublane
    tiles, the taps reach no further back than the eight rows a visit is
    given, and a visit's blocks fit the VMEM the call asks for; ``"xla"``
    everywhere else."""
    fits = (channels % short_conv_kernels._LANES == 0
            and 1 <= width <= short_conv_kernels.REACH + 1
            and short_conv_kernels.fits_vmem(seq, channels, width, 4))
    return "kernels" if fits and pallas_ops._kernels_enabled() else "xla"


def _kernels(bcx, weight) -> bool:
    return gated_short_conv_form(bcx.shape[0], *weight.shape) == "kernels"


def gated_short_conv_bytes(seq: int, channels: int, itemsize: int = 2) -> int:
    """Bytes one call must move, forward and backward, whatever implements
    it: ``bcx`` read and ``y`` written; ``bcx`` and ``dy`` read and
    ``dbcx`` written (the taps and their gradient are ``channels x
    width`` numbers and count for nothing)."""
    return seq * channels * itemsize * ((3 + 1) + (3 + 1 + 3))


def _note_call(kind: str) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "short_conv_calls_total",
        "calls of the gated short convolution, counted when the call is "
        "traced: the operator itself, and its own backward pass",
        labels={"kind": kind}).inc()


def _shifted(a, by: int):
    """``out[t] = a[t - by]`` with zeros where ``t - by`` lies outside the
    sequence (``by`` may be negative)."""
    if by == 0:
        return a
    seq = a.shape[0]
    pad = jnp.zeros((min(abs(by), seq),) + a.shape[1:], a.dtype)
    return (jnp.concatenate([pad, a[:seq - by]]) if by > 0
            else jnp.concatenate([a[-by:], pad]))


def _gates(bcx):
    """B, C and x in float32."""
    return tuple(part.astype(jnp.float32) for part in jnp.split(bcx, 3, -1))


def _taps(z, w):
    """``c[t] = sum_k w[:, k] z[t - (W - 1) + k]``, float32."""
    width = w.shape[1]
    return sum(_shifted(z, width - 1 - k) * w[:, k] for k in range(width))


def _forward(bcx, weight):
    b, c_gate, x = _gates(bcx)
    conv = _taps(b * x, weight.astype(jnp.float32))
    return (c_gate * conv).astype(bcx.dtype)


def _either(bcx, weight):
    if _kernels(bcx, weight):
        return short_conv_kernels.forward(bcx, weight)
    return _forward(bcx, weight)


@jax.custom_vjp
def _gated_short_conv(bcx, weight):
    return _either(bcx, weight)


def _fwd(bcx, weight):
    # the inputs are all the backward pass is given
    return _either(bcx, weight), (bcx, weight)


def _bwd(kept, dy):
    _note_call("backward")
    bcx, weight = kept
    if _kernels(bcx, weight):
        return short_conv_kernels.backward(bcx, weight, dy)
    width = weight.shape[1]
    w = weight.astype(jnp.float32)
    b, c_gate, x = _gates(bcx)
    z = b * x
    dyf = dy.astype(jnp.float32)
    dc = dyf * c_gate
    lagged = [_shifted(z, width - 1 - k) for k in range(width)]
    d_gate = dyf * sum(l * w[:, k] for k, l in enumerate(lagged))
    dz = sum(_shifted(dc, -(width - 1 - k)) * w[:, k] for k in range(width))
    d_weight = jnp.stack([(dc * l).sum(0) for l in lagged], axis=1)
    d_bcx = jnp.concatenate([dz * x, d_gate, dz * b], -1)
    return d_bcx.astype(bcx.dtype), d_weight.astype(weight.dtype)


_gated_short_conv.defvjp(_fwd, _bwd)


def gated_short_conv(bcx, weight):
    """``C * conv(B * x)`` ``[S, channels]`` of one sequence: ``bcx [S, 3
    x channels]`` holds B, C and x side by side as the in-projection wrote
    them, ``weight [channels, W]`` the taps, the last for the position
    itself.  Sums in float32, y in bcx's dtype; the backward pass keeps
    ``bcx`` and ``weight`` only."""
    columns, channels = bcx.shape[1], weight.shape[0]
    if columns != 3 * channels:
        raise ValueError(
            f"gated_short_conv: bcx has {columns} columns, three blocks of "
            f"{channels} channels are {3 * channels}")
    _note_call("forward")
    return _gated_short_conv(bcx, weight)
