"""LFM2's double-gated short convolution (``ops/short_conv.py``) as Mosaic
kernels: forward in one call and the walk back in one, rows-major in and
out.

The operand is the in-projection's own result ``bcx [S, 3 C]``: the calls
read B, C and x where they lie, as three column blocks of it at offsets
0, C and 2 C, so no ``split`` copies anything; the backward call writes
``d_bcx [S, 3 C]`` in the same layout, so it flows into the projection's
two gradient products with no ``concatenate``.

A visit (one grid step) is a tile of ``rows`` positions for all
channels; the tiles are the grid's one, sequential axis, and inside a
visit a loop walks the lane groups of channels, B, C and x of a group
together.  The forward call makes ``z = B x`` in float32 and carries its
last eight rows in scratch to the next visit (zeros before the
sequence); a tap's operand is the tile with those rows laid above,
rolled down the sublanes, so the taps' shifted terms exist in VMEM only:

    c[t] = sum_k w[k] z[t - (W - 1) + k]          y = C c

The backward call keeps nothing but ``bcx`` and the taps: a walk back,
last tile first, that makes a tile's ``z`` and ``c`` again (the rows
before the tile come as a block of their own, sixteen rows of B and of
x), and from ``dc = dy C``

    dC = dy c;   dz[t] = sum_k w[k] dc[t + W - 1 - k]   (rows after the
    dB = dz x;   dx = dz B                              sequence 0)
    dw[k] = sum_t dc[t] z[t - (W - 1) + k]

with dc's first eight rows carried in scratch to the tile before, and
the taps' sums resident in their ``[W, C]`` float32 block over the whole
walk.

Sums are float32, results the operand's dtype, in the XLA form's order:
the mathematics and the precision are ``short_conv``'s.  Which shapes
take these kernels is ``short_conv.gated_short_conv_form``'s to say.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_ops
from .ssm_conv_kernels import (_EDGE, _HALO, _LANES, _ROOM, _call, _down,
                               _rows, _specs, _up, rows_of)

REACH = _EDGE                # rows before a position the taps may read


def vmem_bytes(rows: int, channels: int, width: int, itemsize: int) -> int:
    """What a visit of the backward call, the larger, holds: the three
    blocks of ``bcx``, dy's and d_bcx's (three wide) twice each, the rows
    before the tile of B and x twice, the taps and their sums twice each,
    the carried rows, and a dozen float32 values a lane group wide."""
    return (2 * itemsize * channels * (7 * rows + 2 * _HALO)
            + 4 * channels * (4 * width + _EDGE)
            + 12 * 4 * rows * _LANES)


def fits_vmem(seq: int, channels: int, width: int, itemsize: int) -> bool:
    rows = rows_of(seq)
    return rows > 0 and vmem_bytes(rows, channels, width, itemsize) <= _ROOM


def _note_call(kind: str) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "short_conv_kernel_calls_total",
        "calls of the gated short convolution's Mosaic kernels, counted a "
        "call when the call is traced: fwd the operator, bwd its gradients; "
        "0 where the XLA form ran",
        labels={"kind": kind}).inc()


# --------------------------------------------------------------------------
# what both kernels make of a tile
# --------------------------------------------------------------------------
def _each_lane_group(channels: int, fn):
    """``fn(part)`` for every lane group of the channels, where
    ``part(p)`` gives the group's lanes in the p-th block of ``channels``
    columns (``part(0)`` its lanes of a block one part wide)."""
    from jax.experimental import pallas as pl
    lane_groups = channels // _LANES
    if lane_groups == 1:
        return fn(lambda p: slice(p * _LANES, (p + 1) * _LANES))

    # one lane group a turn (an int32 of its own: a Python int is an int64
    # under the package's x64): the body is traced and lowered once a call
    def step(g, _):
        lanes = g * np.int32(_LANES)
        fn(lambda p: pl.ds(pl.multiple_of(np.int32(p * channels) + lanes,
                                          _LANES), _LANES))
        return g + np.int32(1), None

    jax.lax.scan(step, np.int32(0), None, length=lane_groups)


def _conv(lagged, w):
    """``sum_k w[k] lagged[k]``, k in order, as the XLA form sums."""
    out = lagged[0] * w[0]
    for tap, operand in zip(w[1:], lagged[1:]):
        out = out + operand * tap
    return out


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(b_ref, c_ref, x_ref, w_ref, y_ref, edge_scr):
    """A tile of ``C conv(B x)``, a lane group at a time; the tile's last
    eight rows of ``z`` stay in ``edge_scr [8, C]`` for the next visit."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    rows, width = y_ref.shape[0], w_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _start():
        edge_scr[...] = jnp.zeros_like(edge_scr[...])

    def lane_group(part):
        cols = part(0)
        z = b_ref[:, cols].astype(f32) * x_ref[:, cols].astype(f32)
        down = _down(z, edge_scr[:, cols])
        edge_scr[:, cols] = _rows(z, rows - _EDGE, rows)
        conv = _conv([down(width - 1 - k) for k in range(width)],
                     [w_ref[k:k + 1, cols] for k in range(width)])
        y_ref[:, cols] = (c_ref[:, cols].astype(f32) * conv).astype(
            y_ref.dtype)

    _each_lane_group(y_ref.shape[1], lane_group)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kernel(b_ref, c_ref, x_ref, bh_ref, xh_ref, dy_ref, w_ref, dbcx_ref,
                dw_ref, edge_scr):
    """A tile's gradients, last tile first: ``z`` and ``c`` again, dc, and
    dz from dc and the first eight rows of the tile after, which
    ``edge_scr [8, C]`` carries; the taps' sums add up in their block over
    the walk."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    width = w_ref.shape[0]
    first_tile = pl.program_id(0) == pl.num_programs(0) - 1

    @pl.when(pl.program_id(0) == 0)
    def _start():
        edge_scr[...] = jnp.zeros_like(edge_scr[...])
        dw_ref[...] = jnp.zeros_like(dw_ref[...])

    def lane_group(part):
        cols = part(0)
        b, x = b_ref[:, cols].astype(f32), x_ref[:, cols].astype(f32)
        # the block before the sequence's first tile is the tile's own
        # first rows: they read 0
        before = jnp.where(
            first_tile, f32(0.0),
            _rows(bh_ref[:, cols].astype(f32) * xh_ref[:, cols].astype(f32),
                  _HALO - _EDGE, _HALO))
        down = _down(b * x, before)
        lagged = [down(width - 1 - k) for k in range(width)]
        w = [w_ref[k:k + 1, cols] for k in range(width)]
        dy = dy_ref[:, cols].astype(f32)
        dc = dy * c_ref[:, cols].astype(f32)
        # what reads the lagged z first, so that it is dead before dz
        dbcx_ref[:, part(1)] = (dy * _conv(lagged, w)).astype(dbcx_ref.dtype)
        for k in range(width):
            dw_ref[k:k + 1, cols] += jnp.sum(dc * lagged[k], axis=0,
                                             keepdims=True)
        up = _up(dc, edge_scr[:, cols])
        edge_scr[:, cols] = _rows(dc, 0, _EDGE)
        dz = _conv([up(width - 1 - k) for k in range(width)], w)
        dbcx_ref[:, part(0)] = (dz * x).astype(dbcx_ref.dtype)
        dbcx_ref[:, part(2)] = (dz * b).astype(dbcx_ref.dtype)

    _each_lane_group(dy_ref.shape[1], lane_group)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------
# jitted on their own, as the Mamba convolution's: a model's layers share
# one trace and one lowering a program; the jitted functions' names are
# the calls' names in the compiled step and in a trace
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_conv_fwd(bcx, weight, *, interpret: bool):
    seq, (channels, width) = bcx.shape[0], weight.shape
    rows = rows_of(seq)
    tile, _, whole = _specs(seq // rows, rows, back=False)
    return _call(
        _fwd_kernel, seq // rows,
        vmem_bytes(rows, channels, width, bcx.dtype.itemsize), interpret,
        [tile(channels, part) for part in range(3)]
        + [whole(width, channels)],
        tile(channels), jax.ShapeDtypeStruct((seq, channels), bcx.dtype),
        channels, bcx, bcx, bcx, weight.astype(jnp.float32).T)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_conv_bwd(bcx, weight, dy, *, interpret: bool):
    seq, (channels, width) = bcx.shape[0], weight.shape
    rows, f32 = rows_of(seq), jnp.float32
    tile, halo, whole = _specs(seq // rows, rows, back=True)
    d_bcx, d_w = _call(
        _bwd_kernel, seq // rows,
        vmem_bytes(rows, channels, width, bcx.dtype.itemsize), interpret,
        [tile(channels, part) for part in range(3)]
        + [halo(channels, 0), halo(channels, 2), tile(channels),
           whole(width, channels)],
        [tile(3 * channels), whole(width, channels)],
        [jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
         jax.ShapeDtypeStruct((width, channels), f32)],
        channels, *(bcx,) * 5, dy.astype(bcx.dtype),
        weight.astype(f32).T)
    return d_bcx, d_w.T.astype(weight.dtype)


def forward(bcx, weight):
    """y ``[S, C]`` of ``bcx [S, 3 C]`` and the taps ``weight [C, W]``,
    for the shapes ``gated_short_conv_form`` gives the kernels."""
    _note_call("fwd")
    return _gated_conv_fwd(bcx, weight, interpret=pallas_ops._interpret())


def backward(bcx, weight, dy):
    """``(d_bcx [S, 3 C], d_weight [C, W])`` from the forward pass's
    inputs and y's gradient."""
    _note_call("bwd")
    return _gated_conv_bwd(bcx, weight, dy,
                           interpret=pallas_ops._interpret())
