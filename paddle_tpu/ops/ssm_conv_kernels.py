"""The Mamba-2 mixer's causal convolution as Mosaic kernels: the taps,
the bias, the SiLU and the split into x, B and C in one call forward and
one backward, rows-major in and out.

The operand is the in-projection's own result ``[S, P]``: the columns
``[start, start + C)`` of it are xBC, and the calls read them where they
lie, as three column blocks (x ``inner`` wide, B and C ``G * N`` wide
each) whose offsets are whole blocks, so no ``split`` copies anything.
The gradient is taken by the slice ``xbc [S, C]`` all the same, which
the forward pass leaves unread (``conv_silu_split`` says how), so that
d_xBC goes back into the projection's gradient through the slice's own
transpose, under the caller's scope for it.
The results are three arrays, x ``[S, inner]``, B and C ``[S, G * N]``,
as ``ssm.ssd_scan``'s kernels take them.

A visit (one grid step) is a tile of ``rows`` positions for all
channels; the tiles are the grid's one, sequential axis, and inside a
visit a loop walks the lane groups of channels.  A tap's operand is the
tile, with the eight rows that stand before it laid above, rolled down
the sublanes; the forward call carries those rows in scratch from the
visit before (zeros before the sequence): nothing padded exists in HBM.

The backward call keeps nothing but xBC: a walk back, last tile first,
that makes a tile's convolution again (the rows before the tile come as
a block of their own, sixteen rows of each part), ``g = dy SiLU'(out)``,
and from it

    d_xBC[t]      = sum_k w[k] g[t + W - 1 - k]      rows after the sequence 0
    d_weight[k]   = sum_t g[t] x[t - (W - 1) + k]
    d_bias        = sum_t g[t]

with g's first eight rows carried in scratch to the tile before, and the
weight's and the bias's sums resident in their ``[W, C]`` and ``[1, C]``
float32 blocks over the whole walk.  The four shifted terms of d_xBC
exist in VMEM only.

Sums, SiLU and SiLU' are float32, results the operand's dtype, as
``ssm.causal_conv1d`` and the XLA form have them.  Which shapes take
these kernels is ``ssm.conv_form``'s to say.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_ops

_LANES = 128
_EDGE = 8                    # rows of a neighbouring tile a visit is given
_HALO = 16                   # rows of the block that holds them: a bf16 tile
_ROWS = (256, 128, 64, 32, 16)     # a visit's rows: the first that divides
_ROOM = 48 << 20             # the most a call may hold in VMEM, of 128 MiB


def rows_of(seq: int) -> int:
    """Rows a visit, 0 where the sequence is no whole number of sublane
    tiles."""
    return next((r for r in _ROWS if seq % r == 0), 0)


def vmem_bytes(rows: int, channels: int, width: int, itemsize: int) -> int:
    """What a visit of the backward call, the larger, holds: xBC, dy and
    d_xBC's blocks twice each, the rows before the tile twice, weight,
    bias and their sums twice each, the carried rows, and a dozen float32
    values a lane group wide."""
    return (2 * itemsize * channels * (3 * rows + _HALO)
            + 4 * channels * (4 * (width + 1) + _EDGE)
            + 12 * 4 * rows * _LANES)


def fits_vmem(seq: int, channels: int, width: int, itemsize: int) -> bool:
    rows = rows_of(seq)
    return rows > 0 and vmem_bytes(rows, channels, width, itemsize) <= _ROOM


def _limit(needed: int) -> int:
    """The VMEM a call asks for: what it holds and a quarter more, as
    ``grouped_matmul._limit`` (what a call reserves the compiler cannot
    use around it)."""
    return needed * 5 // 4 + (2 << 20)


def _note_call(kind: str) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "ssm_conv_kernel_calls_total",
        "calls of the causal convolution's Mosaic kernels, counted a call "
        "when the call is traced: fwd taps, bias, SiLU and split, bwd "
        "their gradients; 0 where the XLA form ran",
        labels={"kind": kind}).inc()


# --------------------------------------------------------------------------
# what both kernels make of a tile
# --------------------------------------------------------------------------
def _roll(values, s: int):
    """Down the sublanes by s (an int32 of its own: a Python int is an
    int64 under the package's x64, which the rotate refuses)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(values, np.int32(s), 0)


def _rows(values, start: int, stop: int):
    """``values[start:stop]`` (a ``lax.slice``: jax.numpy's indexing of a
    value takes ten times as long to trace, and a kernel's body holds
    dozens)."""
    return jax.lax.slice_in_dim(values, start, stop, axis=0)


def _down(tile, before):
    """``s -> tile[t - s]`` with the rows that stand before the tile above
    it: ``before [8, n]`` holds the last eight of them.  The two laid
    end to end are rolled whole, so a shift is a rotate and an aligned
    cut."""
    rows = tile.shape[0]
    both = jnp.concatenate([before, tile], axis=0)
    return lambda s: tile if s == 0 else _rows(_roll(both, s), _EDGE,
                                               _EDGE + rows)


def _up(tile, after):
    """``s -> tile[t + s]`` with the rows that stand after the tile below
    it: ``after [8, n]`` holds the first eight of them."""
    rows = tile.shape[0]
    both = jnp.concatenate([tile, after], axis=0)
    return lambda s: tile if s == 0 else _rows(
        _roll(both, rows + _EDGE - s), 0, rows)


def _taps(tile, before, w, bias):
    """The taps' operands, ``x[t - (W - 1) + k]`` for every k, and the
    convolution with its bias, float32: ``w`` the taps' rows, ``[1, n]``
    each, as ``bias``."""
    down = _down(tile, before)
    shifted = [down(len(w) - 1 - k) for k in range(len(w))]
    out = bias
    for tap, operand in zip(w, shifted):
        out = out + tap * operand
    return shifted, out


def _tap_rows(w_ref, at):
    return [w_ref[k:k + 1, at] for k in range(w_ref.shape[0])]


def _each_lane_group(ref, base: int, fn):
    """``fn(cols, at)`` for every lane group of a part's block: ``cols``
    its lanes of the block, ``at`` its lanes of all channels, the part
    starting at channel ``base``."""
    from jax.experimental import pallas as pl
    lane_groups = ref.shape[1] // _LANES
    if lane_groups == 1:
        return fn(slice(0, _LANES), slice(base, base + _LANES))

    # one lane group a turn (an int32 of its own, as ``_each_head``'s):
    # a body is traced and lowered once a part, a call and a program, and
    # a [rows, 128] tile is work enough for a turn
    def step(g, _):
        lanes = g * np.int32(_LANES)
        fn(pl.ds(pl.multiple_of(lanes, _LANES), _LANES),
           pl.ds(pl.multiple_of(np.int32(base) + lanes, _LANES), _LANES))
        return g + np.int32(1), None

    jax.lax.scan(step, np.int32(0), None, length=lane_groups)


def _bases(refs):
    """The channel each part's block starts at."""
    widths = [r.shape[1] for r in refs]
    return [sum(widths[:i]) for i in range(len(widths))]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(x_ref, b_ref, c_ref, w_ref, bias_ref, xo_ref, bo_ref, co_ref,
                edge_scr):
    """A tile of silu(conv(xBC)), a part and a lane group at a time, each
    part into its own result; the tile's last eight rows stay in
    ``edge_scr [8, C]`` for the next visit."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    rows = x_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _start():
        edge_scr[...] = jnp.zeros_like(edge_scr[...])

    sources = (x_ref, b_ref, c_ref)
    for src, dst, base in zip(sources, (xo_ref, bo_ref, co_ref),
                              _bases(sources)):
        def lane_group(cols, at, src=src, dst=dst):
            tile = src[:, cols].astype(f32)
            before = edge_scr[:, at]
            edge_scr[:, at] = _rows(tile, rows - _EDGE, rows)
            _, out = _taps(tile, before, _tap_rows(w_ref, at),
                           bias_ref[:, at])
            dst[:, cols] = (out * jax.nn.sigmoid(out)).astype(dst.dtype)

        _each_lane_group(src, base, lane_group)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kernel(x_ref, b_ref, c_ref, xh_ref, bh_ref, ch_ref, dx_ref, db_ref,
                dc_ref, w_ref, bias_ref, dxbc_ref, dw_ref, dbias_ref,
                edge_scr):
    """A tile's gradients, last tile first: the convolution again, g, and
    d_xBC from g and the first eight rows of the tile after, which
    ``edge_scr [8, C]`` carries; the weight's and the bias's sums add up
    in their blocks over the walk."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    width = w_ref.shape[0]
    first_tile = pl.program_id(0) == pl.num_programs(0) - 1

    @pl.when(pl.program_id(0) == 0)
    def _start():
        edge_scr[...] = jnp.zeros_like(edge_scr[...])
        dw_ref[...] = jnp.zeros_like(dw_ref[...])
        dbias_ref[...] = jnp.zeros_like(dbias_ref[...])

    sources = (x_ref, b_ref, c_ref)
    for src, halo, dy_ref, base in zip(sources, (xh_ref, bh_ref, ch_ref),
                                       (dx_ref, db_ref, dc_ref),
                                       _bases(sources)):
        def lane_group(cols, at, src=src, halo=halo, dy_ref=dy_ref):
            tile = src[:, cols].astype(f32)
            # the block before the sequence's first tile is the tile's
            # own first rows: they read 0
            before = jnp.where(
                first_tile, f32(0.0),
                _rows(halo[:, cols].astype(f32), _HALO - _EDGE, _HALO))
            w = _tap_rows(w_ref, at)
            shifted, out = _taps(tile, before, w, bias_ref[:, at])
            sig = jax.nn.sigmoid(out)
            g = dy_ref[:, cols].astype(f32) * (
                sig * (1.0 + out * (1.0 - sig)))
            after = edge_scr[:, at]
            edge_scr[:, at] = _rows(g, 0, _EDGE)
            up = _up(g, after)
            d_tile = w[width - 1] * g
            for k in range(width - 1):
                d_tile = d_tile + w[k] * up(width - 1 - k)
            dxbc_ref[:, at] = d_tile.astype(dxbc_ref.dtype)
            for k in range(width):
                dw_ref[k:k + 1, at] += jnp.sum(g * shifted[k], axis=0,
                                               keepdims=True)
            dbias_ref[:, at] += jnp.sum(g, axis=0, keepdims=True)

        _each_lane_group(src, base, lane_group)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------
def _specs(tiles: int, rows: int, back: bool):
    """Block specs over the tiles, last first where ``back``: a tile's
    rows of the columns ``[column * width, (column + 1) * width)`` of an
    array ``[S, n]``; the sixteen rows before a tile, of the same columns
    (the first tile is given its own first rows, which the kernel reads
    as 0); and the whole of ``[height, n]``."""
    from jax.experimental import pallas as pl
    n, per = np.int32(tiles), np.int32(rows // _HALO)
    at = (lambda i: n - 1 - i) if back else (lambda i: i)
    return (lambda width, column=0: pl.BlockSpec(
                (rows, width), lambda i: (at(i), i * 0 + np.int32(column))),
            lambda width, column: pl.BlockSpec(
                (_HALO, width), lambda i: (jnp.maximum(at(i) * per - 1, 0),
                                           i * 0 + np.int32(column))),
            lambda height, width: pl.BlockSpec((height, width),
                                               lambda i: (i * 0, i * 0)))


def _parts(start: int, inner: int, bc: int):
    """Width and column block of x, B and C where they lie in ``[S, P]``."""
    return [(inner, start // inner), (bc, (start + inner) // bc),
            (bc, (start + inner + bc) // bc)]


def _call(kernel, tiles: int, needed: int, interpret: bool, in_specs,
          out_specs, out_shape, channels: int, *args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(tiles,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((_EDGE, channels), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_limit(needed)),
        interpret=interpret)(*args)


# jitted on their own, as the scan's two calls are: a model's layers and
# the forward pass run again share one trace and one lowering a program
@functools.partial(jax.jit, static_argnames=("start", "inner", "bc",
                                             "interpret"))
def _forward_call(proj, weight, bias, *, start: int, inner: int, bc: int,
                  interpret: bool):
    seq, (channels, width) = proj.shape[0], weight.shape
    rows, f32 = rows_of(seq), jnp.float32
    tile, _, whole = _specs(seq // rows, rows, back=False)
    parts = _parts(start, inner, bc)
    return _call(
        _fwd_kernel, seq // rows,
        vmem_bytes(rows, channels, width, proj.dtype.itemsize), interpret,
        [tile(*p) for p in parts] + [whole(width, channels),
                                     whole(1, channels)],
        [tile(w) for w, _ in parts],
        [jax.ShapeDtypeStruct((seq, w), proj.dtype) for w, _ in parts],
        channels, proj, proj, proj, weight.astype(f32).T,
        bias.astype(f32)[None])


@functools.partial(jax.jit, static_argnames=("start", "inner", "bc",
                                             "interpret"))
def _backward_call(proj, weight, bias, dx, db, dc, *, start: int, inner: int,
                   bc: int, interpret: bool):
    seq, (channels, width) = proj.shape[0], weight.shape
    rows, f32 = rows_of(seq), jnp.float32
    tile, halo, whole = _specs(seq // rows, rows, back=True)
    parts = _parts(start, inner, bc)
    sums = [whole(width, channels), whole(1, channels)]
    d_xbc, d_w, d_bias = _call(
        _bwd_kernel, seq // rows,
        vmem_bytes(rows, channels, width, proj.dtype.itemsize), interpret,
        [tile(*p) for p in parts] + [halo(*p) for p in parts]
        + [tile(w) for w, _ in parts] + sums,
        [tile(channels)] + sums,
        [jax.ShapeDtypeStruct((seq, channels), proj.dtype),
         jax.ShapeDtypeStruct((width, channels), f32),
         jax.ShapeDtypeStruct((1, channels), f32)],
        channels, *(proj,) * 6, dx.astype(proj.dtype), db.astype(proj.dtype),
        dc.astype(proj.dtype), weight.astype(f32).T, bias.astype(f32)[None])
    return d_xbc, d_w.T.astype(weight.dtype), d_bias[0].astype(bias.dtype)


def _forward(source, weight, bias, start, inner, bc):
    _note_call("fwd")
    return tuple(_forward_call(source, weight, bias, start=start,
                               inner=inner, bc=bc,
                               interpret=pallas_ops._interpret()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def conv_silu_split(xbc, weight, bias, source, start: int, inner: int,
                    bc: int):
    """x ``[S, inner]``, B and C ``[S, bc]`` of ``silu(conv(xBC) + bias)``,
    ``weight [C, W]``, ``bias [C]``, for the shapes ``ssm.conv_form`` gives
    the kernels.  ``xbc [S, C]`` is what the gradient is taken by;
    what the calls read is ``source [S, P]``, an array that holds the
    same values in its columns ``[start, start + C)``, ``start`` a
    multiple of ``inner``: the in-projection's result, of which ``xbc``
    is a slice that then nothing reads, or ``xbc`` itself at 0."""
    del xbc
    return _forward(source, weight, bias, start, inner, bc)


def _conv_fwd(xbc, weight, bias, source, start, inner, bc):
    # the backward pass is given the inputs: it makes the convolution again
    del xbc
    return (_forward(source, weight, bias, start, inner, bc),
            (source, weight, bias))


def _conv_bwd(start, inner, bc, kept, dys):
    _note_call("bwd")
    return _backward_call(*kept, *dys, start=start, inner=inner, bc=bc,
                          interpret=pallas_ops._interpret()) + (None,)


conv_silu_split.defvjp(_conv_fwd, _conv_bwd)
