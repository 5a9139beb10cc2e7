"""State-space layers: the selective scan of Mamba-2 in its chunked
(state-space duality) form, the selective scan of Mamba-1 (a step size a
channel and a decay a channel and state: :func:`selective_scan`, at the
end of this file), and the causal depthwise convolution in front of
either; a sequence at a time.  Each has two forms, and
:func:`scan_form`, :func:`selective_scan_form` and :func:`conv_form` name
the one that runs, from platform and shape: on a TPU, for shapes that
fill lane groups and whole chunks or tiles, the Mosaic kernels of
``ops/ssm_kernels.py`` (a chunk's matrices and the running state in VMEM
only), of ``ops/ssm_s6_kernels.py`` (Mamba-1: a block of channels' state
in VMEM from the first position to the last, forward in one call and the
walk back in one) and of ``ops/ssm_conv_kernels.py`` (the taps, the bias,
the SiLU and the split into x, B and C in one call, read where the
in-projection wrote them; the backward pass's shifted terms in VMEM
only); everywhere else (the CPU, odd shapes) the plain XLA operations
below, which are also what the kernels are checked against.

The recurrence, for head h with state ``H [P, N]`` (P the head's width,
N the state's), ``a_t = dt_t A`` (A < 0):

    H_t = exp(a_t) H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t + D x_t

In chunks of Q positions it is four products.  With ``cum_l`` the sum of
a over a chunk's positions up to l, and ``Hin`` the state a chunk starts
from:

    inside a chunk   y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s
    a chunk's state  S    = sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s
    chunk to chunk   Hin' = exp(cum_Q) Hin + S
    from the state   y_l += exp(cum_l) Hin C_l

Decays are float32; products take their inputs in x's dtype (bf16 under
O2) and sum in float32.  The ``[heads, Q, Q]`` matrices of the first
line are the large ones (``[64, 32, 256, 256]`` float32 is 537 MB at
8192 positions): the XLA form makes them ``CHUNKS_AT_ONCE`` chunks at a
time and keeps none of them for the backward pass, which makes them
again, as many at a time; the kernels make one head's at a time, in
VMEM, forward and backward.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import pallas_ops, ssm_conv_kernels, ssm_kernels, ssm_s6_kernels

_LANES = 128
# the XLA form's: chunks whose [heads, Q, Q] matrices are alive together,
# forward and backward: 64 heads x 256 x 256 float32 is 16.8 MB a chunk,
# and the backward pass holds about six such arrays
CHUNKS_AT_ONCE = 4


# --------------------------------------------------------------------------
# the convolution
# --------------------------------------------------------------------------
def causal_conv1d(x, weight, bias=None):
    """``x [S, C]``, depthwise: ``out[t, c] = bias[c] + sum_k weight[c, k]
    x[t - (W - 1) + k, c]`` with ``weight [C, W]``; positions before the
    sequence read 0.  Sums in float32, returns x's dtype."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(padded[k:k + seq].astype(jnp.float32) * w[:, k]
              for k in range(width))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


@jax.checkpoint
def _conv_silu(x, weight, bias):
    """Keeps its input and makes its float32 insides again in the
    backward pass."""
    out = causal_conv1d(x, weight, bias).astype(jnp.float32)
    return (out * jax.nn.sigmoid(out)).astype(x.dtype)


def conv_form(seq: int, channels: int, inner: int, groups: int, state: int,
              width: int) -> str:
    """Which form of the mixer's convolution runs, from platform and
    shape: ``"kernels"`` (``ops/ssm_conv_kernels.py``) on a TPU (or under
    the interpreter) where x and B and C are whole lane groups, ``inner``
    a whole number of B's blocks, the sequence a whole number of sublane
    tiles, the taps reach no further back than the eight rows a visit is
    given, and a visit's blocks fit the VMEM the call asks for; ``"xla"``
    (:func:`causal_conv1d`, SiLU, ``jnp.split``) everywhere else."""
    bc = groups * state
    fits = (channels == inner + 2 * bc and inner % _LANES == 0
            and bc % _LANES == 0 and inner % bc == 0 and 1 <= width <= 9
            and ssm_conv_kernels.fits_vmem(seq, channels, width, 4))
    return "kernels" if fits and pallas_ops._kernels_enabled() else "xla"


def conv_silu_split(xbc, weight, bias, inner: int, groups: int, state: int,
                    lies_in=None):
    """x ``[S, inner]``, B and C ``[S, groups * state]`` of one sequence:
    ``silu(conv(xbc) + bias)`` split, ``xbc [S, C]``, ``weight [C, W]``,
    ``bias [C]``.  ``lies_in = (proj, start)`` says that xbc is the
    columns ``[start, start + C)`` of ``proj [S, P]`` (the in-projection's
    result): the kernels then read them where they lie and the slice is
    read by nothing, where ``start`` is a whole number of x's blocks.
    The backward pass of either form is given its inputs and makes the
    convolution again."""
    channels, bc = weight.shape[0], groups * state
    if conv_form(xbc.shape[0], channels, inner, groups, state,
                 weight.shape[1]) == "kernels":
        source, start = lies_in or (xbc, 0)
        if start % inner:
            source, start = xbc, 0
        return ssm_conv_kernels.conv_silu_split(xbc, weight, bias, source,
                                                start, inner, bc)
    return tuple(jnp.split(_conv_silu(xbc, weight, bias),
                           (inner, inner + bc), -1))


# --------------------------------------------------------------------------
# the scan: the XLA form
# --------------------------------------------------------------------------
def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _chunk_states(x, dt, cum, B):
    """``S [c, g, r, P, N]`` of every chunk, from ``x [c, Q, g, r, P]``,
    ``dt``, ``cum [c, Q, g, r]`` and ``B [c, Q, g, N]``."""
    to_end = jnp.exp(cum[:, -1:] - cum) * dt
    scaled = (x.astype(jnp.float32) * to_end[..., None]).astype(x.dtype)
    return _dot("cqgrp,cqgn->cgrpn", scaled, B)


def _starting_states(x, dt, cum, B):
    """The state each chunk starts from, ``[c, g, r, P, N]`` float32, the
    first from nothing: ``Hin' = exp(cum_Q) Hin + S``, chunk to chunk."""
    states = _chunk_states(x, dt, cum, B)

    def step(h, args):
        s, total = args
        return jnp.exp(total)[..., None, None] * h + s, h

    return jax.lax.scan(step, jnp.zeros_like(states[0]),
                        (states, cum[:, -1]))[1]


def _chunk_outputs(x, dt, cum, B, C, starts):
    """y without the D term, ``[c, Q, g, r, P]`` float32, of some chunks:
    the decayed lower triangle inside each and what its starting state
    adds."""
    q = x.shape[1]
    scores = _dot("clgn,csgn->cgls", C, B)
    lower = jnp.tril(jnp.ones((q, q), bool))
    by_head = cum.transpose(0, 2, 3, 1)                  # [c, g, r, Q]
    # masked before the exponential: above the diagonal cum_l - cum_s
    # is positive and may overflow
    decay = jnp.exp(jnp.where(
        lower, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    local = (decay * dt.transpose(0, 2, 3, 1)[..., None, :]
             * scores[:, :, None])                       # [c, g, r, l, s]
    y = _dot("cgrls,csgrp->clgrp", local.astype(x.dtype), x)
    from_state = _dot("clgn,cgrpn->clgrp", C, starts.astype(x.dtype))
    return y + jnp.exp(cum)[..., None] * from_state


def _groups_of(array, size):
    return array.reshape((array.shape[0] // size, size) + array.shape[1:])


def _chunked(x, dt, A, B, C, chunk):
    """The inputs by chunk and by group of heads: x ``[c, Q, g, r, P]``,
    dt and cum ``[c, Q, g, r]`` float32, B and C ``[c, Q, g, N]``."""
    seq, heads, width = x.shape
    groups = B.shape[1]
    if seq % chunk:
        raise ValueError(
            f"ssd_scan: a sequence of {seq} positions is no whole number "
            f"of chunks of {chunk}; pad it (dt = 0 leaves the state as "
            "it is) or choose a chunk that divides it")
    if heads % groups:
        raise ValueError(f"ssd_scan: {heads} heads in {groups} groups")
    n = seq // chunk
    shape = (n, chunk, groups, heads // groups)
    dt = dt.astype(jnp.float32).reshape(shape)
    cum = jnp.cumsum(dt * A.astype(jnp.float32).reshape(shape[2:]), axis=1)
    return (x.reshape(shape + (width,)), dt, cum,
            B.reshape(n, chunk, groups, -1).astype(x.dtype),
            C.reshape(n, chunk, groups, -1).astype(x.dtype))


def _at_once(n_chunks: int, at_once: int) -> int:
    """The largest divisor of ``n_chunks`` that is at most ``at_once``."""
    return max(k for k in range(1, min(at_once, n_chunks) + 1)
               if n_chunks % k == 0)


def _scan_forward(x, dt, A, B, C, D, chunk, at_once):
    xc, dtc, cum, Bc, Cc = _chunked(x, dt, A, B, C, chunk)
    starts = _starting_states(xc, dtc, cum, Bc)
    k = _at_once(xc.shape[0], at_once)
    y = jax.lax.map(lambda args: _chunk_outputs(*args), tuple(
        _groups_of(a, k) for a in (xc, dtc, cum, Bc, Cc, starts)))
    y = y.reshape(x.shape) + D.astype(jnp.float32)[:, None] * x.astype(
        jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_scan(x, dt, A, B, C, D, chunk, at_once):
    return _scan_forward(x, dt, A, B, C, D, chunk, at_once)


def _ssd_scan_fwd(x, dt, A, B, C, D, chunk, at_once):
    # the inputs are all the backward pass is given
    return (_scan_forward(x, dt, A, B, C, D, chunk, at_once),
            (x, dt, A, B, C, D))


def _ssd_scan_bwd(chunk, at_once, inputs, dy):
    """The chunk states and the starting states again (no ``[Q, Q]``
    matrix in them), then ``CHUNKS_AT_ONCE`` chunks at a time: their
    matrices again and, through them, the gradients; then back through
    the states, last chunk first."""
    x, dt, A, B, C, D = inputs

    def states_of(x_, dt_, A_, B_):
        return _starting_states(*_chunked(x_, dt_, A_, B_, C, chunk)[:4])

    starts, back_through_states = jax.vjp(states_of, x, dt, A, B)
    k = _at_once(starts.shape[0], at_once)

    def some_chunks(args):
        x_, dt_, B_, C_, starts_, dy_ = args

        def outputs(x__, dt__, A__, B__, C__, starts__):
            shape = dt__.shape[2:]
            cum = jnp.cumsum(dt__ * A__.reshape(shape), axis=1)
            return _chunk_outputs(x__, dt__, cum, B__, C__, starts__)

        _, vjp = jax.vjp(outputs, x_, dt_, A.astype(jnp.float32), B_, C_,
                         starts_)
        return vjp(dy_.astype(jnp.float32))

    xc, dtc, _, Bc, Cc = _chunked(x, dt, A, B, C, chunk)
    dx, ddt, dA, dB, dC, dstarts = jax.lax.map(some_chunks, tuple(
        _groups_of(a, k) for a in (xc, dtc, Bc, Cc, starts,
                                   dy.reshape(xc.shape))))
    dx2, ddt2, dA2, dB2 = back_through_states(
        dstarts.reshape(starts.shape))
    xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
    dx = (dx.reshape(x.shape).astype(jnp.float32) + dx2.astype(jnp.float32)
          + D.astype(jnp.float32)[:, None] * dyf)
    return (dx.astype(x.dtype),
            (ddt.reshape(dt.shape) + ddt2.astype(jnp.float32)).astype(
                dt.dtype),
            (dA.sum(0).reshape(A.shape) + dA2).astype(A.dtype),
            (dB.reshape(B.shape).astype(jnp.float32)
             + dB2.astype(jnp.float32)).astype(B.dtype),
            dC.reshape(C.shape).astype(C.dtype),
            (dyf * xf).sum((0, 2)).astype(D.dtype))


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


# --------------------------------------------------------------------------
# the scan: which form runs
# --------------------------------------------------------------------------
def scan_form(seq: int, heads: int, width: int, groups: int, state: int,
              chunk: int) -> str:
    """Which form of the scan runs, from platform and shape:
    ``"kernels"`` (``ops/ssm_kernels.py``) on a TPU (or under the
    interpreter) where the sequence is a whole number of chunks, the
    chunk and the state are multiples of 128, whole heads fill a lane
    group that one group of B and C serves, and a chunk of all heads
    fits VMEM; ``"xla"`` everywhere else."""
    share = _LANES // width if _LANES % width == 0 else 0
    fits = (seq % chunk == 0 and chunk % _LANES == 0
            and state % _LANES == 0 and share > 0
            and heads % groups == 0 and (heads // groups) % share == 0
            and ssm_kernels.fits_vmem(heads, width, state, chunk))
    return "kernels" if fits and pallas_ops._kernels_enabled() else "xla"


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``y [S, H, P]`` of one sequence: ``x [S, H, P]``, ``dt [S, H]``
    (positive: after its softplus), ``A [H]`` (negative), ``B`` and ``C
    [S, G, N]`` (G groups of H / G heads share them), ``D [H]``.  S is a
    whole number of chunks: anything else is refused, since what a
    caller pads with decides what the state sees."""
    chunk = int(chunk)
    if scan_form(*x.shape, *B.shape[1:], chunk) == "kernels":
        return ssm_kernels.scan(x, dt, A, B, C, D, chunk)
    return _ssd_scan(x, dt, A, B, C, D, chunk, CHUNKS_AT_ONCE)


def scan_chunks(seq: int, heads: int, chunk: int) -> int:
    """Chunks x heads a call of :func:`ssd_scan` walks."""
    return seq // chunk * heads


def scan_state_bytes(seq: int, heads: int, width: int, state: int,
                     chunk: int) -> int:
    """Bytes of the float32 states a call passes from chunk to chunk."""
    return seq // chunk * heads * width * state * 4


# --------------------------------------------------------------------------
# the scan of Mamba-1: a step size a channel, a decay a channel and state
# --------------------------------------------------------------------------
# For channel c with state ``s [N]``, ``a_t = dt_t[c] A[c]`` (A < 0):
#
#     s_t = exp(a_t) s_{t-1} + dt_t[c] x_t[c] B_t        y_t = s_t . C_t + D[c] x_t[c]
#
# B and C are shared by all channels, every channel has its own N decays,
# and so no product of matrices computes it (the state-space duality
# needs one decay a head).  On a TPU it is the two Mosaic kernels of
# ``ops/ssm_s6_kernels.py``: the recurrence itself, a position after
# another, with a block of channels' state in VMEM and registers from
# the first position to the last, and one walk back that carries the
# state's gradient the same way.  Everywhere else, and as what the
# kernels are tested against, it is XLA operations in chunks of Q
# positions, all chunks side by side: Q steps of the recurrence from
# nothing, each over ``[chunks, N, channels]``; the state each chunk
# starts from, chunk to chunk; and what that state adds to y, ``exp(A
# cumsum(dt))`` of it, which needs no step.  That form's backward pass is
# given the inputs and the chunks' starting states and nothing else (as
# the kernels' is): the adjoint recurrence from nothing and chunk to
# chunk likewise, then some chunks at a time (``SELECTIVE_BYTES_AT_ONCE``
# of states) their states again and, walking back through them, the
# gradients.  Everything is float32 but what is read and written.
SELECTIVE_BYTES_AT_ONCE = 1 << 28


def selective_chunk(seq: int) -> int:
    """Q, from the sequence's length.  Where the kernels run at all (a
    TPU, or the interpreter), the longest of their chunks that divides it
    (128 at 8192 positions: a visit's blocks of all 5120 channels fill 32
    MB of VMEM), for a shape they refuse too: the XLA form read the same
    time on the chip from 32 to 128 (PERF.md section 6, PR 38).
    Everywhere else, and for a length no kernel chunk divides, the power
    of two at or under its square root: the XLA form takes Q steps inside
    the chunks and ``seq / Q`` from chunk to chunk one after another,
    fewest at the root (64 at 8192 positions).  Either way ``seq / Q``
    states are kept for the walk back."""
    seq = int(seq)
    kernels = ssm_s6_kernels.chunk_of(seq) if pallas_ops._kernels_enabled() \
        else 0
    return kernels or 1 << (math.isqrt(max(seq, 1)).bit_length() - 1)


def selective_scan_form(seq: int, chunk: int, channels: int = _LANES,
                        state: int = 16, itemsize: int = 2) -> str:
    """Which form of :func:`selective_scan` runs, from platform and
    shape: ``"kernels"`` (``ops/ssm_s6_kernels.py``) on a TPU (or under
    the interpreter) where the channels are whole lane groups, the states
    whole sublane tiles, the sequence a whole number of chunks of whole
    sixteen rows, and a visit's blocks fit VMEM
    (``ssm_s6_kernels.fits``); else ``"chunked"`` where the sequence is
    more than one whole chunk, and ``"sequential"`` (the recurrence a
    position at a time, and jax's own derivative of it) for anything
    else: XLA operations both.  Channels, states and the bytes of an
    element of x default to a shape the kernels take, so that the length
    and the chunk alone answer for such a shape."""
    if pallas_ops._kernels_enabled() and ssm_s6_kernels.fits(
            seq, channels, state, chunk, itemsize):
        return "kernels"
    return "chunked" if seq % chunk == 0 and seq > chunk else "sequential"


def _by_chunk(a, chunk):
    """``[S, ...] -> [Q, chunks, ...]``: a step of the recurrence takes
    one position of every chunk."""
    return a.reshape((a.shape[0] // chunk, chunk) + a.shape[1:]).swapaxes(
        0, 1)


def _from_chunks(a):
    """``[Q, chunks, ...] -> [S, ...]``."""
    a = a.swapaxes(0, 1)
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


def _outer(row, col):
    """``[n, C]`` and ``[n, N] -> [n, N, C]``."""
    return col[:, :, None] * row[:, None, :]


def _selective_step(At, s, dt, x, B):
    """One position of every chunk: ``s [n, N, C]`` float32, ``At [N,
    C]``, ``dt`` and ``x [n, C]``, ``B [n, N]``."""
    return jnp.exp(At * dt[:, None, :]) * s + _outer(dt * x, B)


def _carry_states(totals, ends, reverse=False):
    """The state each chunk starts from (or, ``reverse``, the adjoint
    each chunk ends with): ``h' = totals_k h + ends_k``, from nothing."""
    def step(h, args):
        total, end = args
        return total * h + end, h

    return jax.lax.scan(step, jnp.zeros_like(ends[0]), (totals, ends),
                        reverse=reverse)[1]


def _selective_sequential(x, dt, A, B, C, D):
    f32 = jnp.float32
    At = A.astype(f32).T

    def step(s, args):
        dt_t, x_t, b_t, c_t = args
        s = _selective_step(At, s, dt_t[None], x_t[None], b_t[None])
        return s, (s[0] * c_t[:, None]).sum(0)

    xf = x.astype(f32)
    _, y = jax.lax.scan(step, jnp.zeros((1,) + At.shape, f32), (
        dt.astype(f32), xf, B.astype(f32), C.astype(f32)))
    return (y + D.astype(f32) * xf).astype(x.dtype)


def _selective_forward(x, dt, A, B, C, D, chunk):
    """(y, the state each chunk starts from ``[chunks, N, C]``)."""
    f32 = jnp.float32
    At = A.astype(f32).T                                   # [N, C]
    xc, dtc, Bc, Cc = (_by_chunk(a.astype(f32), chunk)
                       for a in (x, dt, B, C))

    def step(s, args):
        dt_l, x_l, b_l, c_l = args
        s = _selective_step(At, s, dt_l, x_l, b_l)
        return s, (s * c_l[:, :, None]).sum(1)

    n = xc.shape[1]
    ends, y = jax.lax.scan(step, jnp.zeros((n,) + At.shape, f32),
                           (dtc, xc, Bc, Cc))
    cum = jnp.cumsum(dtc, axis=0)                          # [Q, n, C]
    starts = _carry_states(jnp.exp(At * cum[-1][:, None, :]), ends)

    def from_start(args):
        cum_l, c_l = args
        return (jnp.exp(At * cum_l[:, None, :]) * starts
                * c_l[:, :, None]).sum(1)

    y = y + jax.lax.map(from_start, (cum, Cc))
    y = _from_chunks(y) + D.astype(f32) * x.astype(f32)
    return y.astype(x.dtype), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _selective_scan(x, dt, A, B, C, D, chunk):
    return _selective_forward(x, dt, A, B, C, D, chunk)[0]


def _selective_scan_fwd(x, dt, A, B, C, D, chunk):
    y, starts = _selective_forward(x, dt, A, B, C, D, chunk)
    return y, (x, dt, A, B, C, D, starts)


def _selective_scan_bwd(chunk, res, dy):
    """With ``g_t`` the gradient by ``s_t``: ``g_t = C_t dy_t + exp(a_{t+1})
    g_{t+1}``, the same recurrence walked back.  From nothing in every
    chunk, then chunk to chunk, it gives what each chunk's last state is
    owed by the chunks after it; then, some chunks at a time, the states
    again from the kept starts, and back through them ``g_t`` itself and
    every gradient: ``da_t = g_t (s_t - u_t)`` with ``u_t = dt_t x_t
    B_t``, since ``exp(a_t) s_{t-1} = s_t - u_t``."""
    x, dt, A, B, C, D, starts = res
    f32 = jnp.float32
    At = A.astype(f32).T
    xf, dyf = x.astype(f32), dy.astype(f32)
    xc, dtc, Bc, Cc, dyc = (_by_chunk(a.astype(f32), chunk)
                            for a in (x, dt, B, C, dy))
    n = xc.shape[1]

    def back_from_nothing(g, args):
        dt_l, c_l, dy_l = args
        return jnp.exp(At * dt_l[:, None, :]) * (g + _outer(dy_l, c_l)), None

    owed_inside, _ = jax.lax.scan(
        back_from_nothing, jnp.zeros((n,) + At.shape, f32), (dtc, Cc, dyc),
        reverse=True)
    totals = jnp.exp(At * dtc.sum(0)[:, None, :])
    owed = _carry_states(totals, owed_inside, reverse=True)

    k = _at_once(n, max(1, SELECTIVE_BYTES_AT_ONCE
                        // (chunk * At.size * 4)))

    def some_chunks(args):
        x_, dt_, B_, C_, dy_, starts_, owed_ = args        # [Q, k, ...]

        def ahead(s, a):
            s = _selective_step(At, s, *a)
            return s, s

        _, states = jax.lax.scan(ahead, starts_, (dt_, x_, B_))

        def back(carry, a):
            g, dA = carry
            dt_l, x_l, b_l, c_l, dy_l, s_l = a
            g = g + _outer(dy_l, c_l)
            to_x = (g * b_l[:, :, None]).sum(1)             # [k, C]
            da = g * (s_l - _outer(dt_l * x_l, b_l))
            out = (dt_l * to_x, (da * At).sum(1) + x_l * to_x,
                   (g * (dt_l * x_l)[:, None, :]).sum(2),
                   (s_l * dy_l[:, None, :]).sum(2))
            return (jnp.exp(At * dt_l[:, None, :]) * g,
                    dA + da * dt_l[:, None, :]), out

        (_, dA), grads = jax.lax.scan(
            back, (owed_, jnp.zeros_like(owed_)),
            (dt_, x_, B_, C_, dy_, states), reverse=True)
        return grads + (dA.sum(0),)

    def grouped(a, axis):
        """``[..., n, ...] -> [n / k, ..., k, ...]``."""
        shape = a.shape[:axis] + (n // k, k) + a.shape[axis + 1:]
        return jnp.moveaxis(a.reshape(shape), axis, 0)

    dx, ddt, dB, dC, dA = jax.lax.map(some_chunks, tuple(
        grouped(a, 1) for a in (xc, dtc, Bc, Cc, dyc)) + (
        grouped(starts, 0), grouped(owed, 0)))

    def whole(a):
        """``[n / k, Q, k, ...] -> [S, ...]``."""
        a = jnp.moveaxis(a, 0, 1)                           # [Q, n/k, k, ...]
        return _from_chunks(a.reshape((a.shape[0], n) + a.shape[3:]))

    return ((whole(dx) + D.astype(f32) * dyf).astype(x.dtype),
            whole(ddt).astype(dt.dtype), dA.sum(0).T.astype(A.dtype),
            whole(dB).astype(B.dtype), whole(dC).astype(C.dtype),
            (dyf * xf).sum(0).astype(D.dtype))


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def selective_scan(x, dt, A, B, C, D, chunk: Optional[int] = None):
    """``y [S, C]`` of one sequence: ``x [S, C]``, ``dt [S, C]`` (positive:
    after its softplus), ``A [C, N]`` (negative), ``B`` and ``C [S, N]``
    (all channels share them), ``D [C]``.  y in x's dtype; the state and
    every sum float32.  ``chunk`` is :func:`selective_chunk`'s unless a
    test gives another: it changes how y is computed and not y."""
    chunk = selective_chunk(x.shape[0]) if chunk is None else int(chunk)
    form = selective_scan_form(x.shape[0], chunk, x.shape[1], A.shape[1],
                               x.dtype.itemsize)
    if form == "kernels":
        return ssm_s6_kernels.scan(x, dt, A, B, C, D, chunk)
    if form == "chunked":
        return _selective_scan(x, dt, A, B, C, D, chunk)
    return _selective_sequential(x, dt, A, B, C, D)


def selective_scan_chunks(seq: int) -> int:
    """Chunks a call of :func:`selective_scan` walks, whichever form (1:
    sequential)."""
    chunk = selective_chunk(seq)
    return 1 if seq % chunk else seq // chunk


def selective_scan_state_bytes(seq: int, channels: int, state: int) -> int:
    """Bytes of the float32 states a call keeps for its backward pass:
    the one each chunk starts from."""
    return selective_scan_chunks(seq) * channels * state * 4


def causal_conv_silu(x, weight, bias):
    """``silu(conv(x) + bias)`` of ``x [S, C]`` alone (Mamba-1: B and C
    come from a projection after it): :func:`causal_conv1d` and SiLU,
    keeping their input only."""
    return _conv_silu(x, weight, bias)
