"""The selective scan of Mamba-1 (``ops/ssm.py:selective_scan``) as Mosaic
kernels: a block of channels' state stays in VMEM from the first position
to the last, forward in one call and the walk back in one.

The arrays keep the layer's own layout: x, dt, y and their gradients ``[S,
C]``, positions down the sublanes and channels along the lanes, nothing
re-laid in HBM.  The state of a lane group of 128 channels is ``[N, 128]``
float32, the N states down the sublanes (two vector registers at N = 16),
so a position's update takes its operands as whole registers:

    a = exp(dt_t A)        dt_t a row of the tile, laid down the sublanes
    s = a s + B_t (dt_t x_t)
    y_t = sum_n C_t s      the one sum across sublanes, a row of the result

``A`` comes transposed, ``[N, C]``; ``B_t`` and ``C_t`` come as ``[N, 128]``
tiles with the value of state n in every lane of row n (``[S * N, 128]`` in
x's dtype, written once a call by XLA: 32 MB each at the cell's shape and
a packed register a position), so nothing is broadcast along the lanes
inside a kernel.

A visit (one grid step) is a chunk of Q positions for a block of channels.
The grid is (blocks, chunks), the chunks sequential; inside a visit a loop
walks the block a few lane groups at a time (their states are independent
chains that hide each other's latency) and, inside that, the positions,
sixteen a turn, the states carried in registers.  Between visits the
block's state lies in scratch, ``[N, block]``.

Differentiated, the forward call also writes the state each chunk starts
from (``[chunks * N, C]`` float32), and the backward pass is one call, last
chunk first.  A visit makes its lane groups' states and decays again
into scratch (``[(Q + 1) * N, lanes]`` and ``[Q * N, lanes]``), then walks
back through them carrying ``g``, the gradient by the state:

    g   += C_t dy_t
    dx_t, ddt_t from  sum_n g B_t  and  sum_n da A     (rows, as y)
    dB_t += g (dt_t x_t)      dC_t += s_t dy_t         ([N, 128], by lane)
    g    = a g                da = g s_{t-1}           dA += da dt_t

``da`` is ``g_t (s_t - u_t)`` written with the state before, so a step that
forgets everything (``a`` underflows to 0) gives finite gradients.  ``g``
carries from chunk to chunk in scratch, as the state does forward: no
pass computes what a chunk is owed beforehand.  dB and dC sum over
channels: by lane group in scratch ``[Q * N, 128]`` during the walk, across
the lanes by one product with ones on the otherwise idle MXU at the end of
a visit, and across the blocks of channels outside (``[blocks, S, N]``
partials).  dA adds up in its ``[N, block]`` output block over the walk;
dD is one XLA reduction outside.

State, decays, ``exp`` and every sum are float32; x, B, C and dy are read
and y, dx, dB and dC written in their own dtypes; dt and ddt float32.
Which shapes take these kernels is ``ssm.selective_scan_form``'s to say.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import pallas_ops
from .ssm_conv_kernels import _limit

_LANES = 128
_ROWS = 16                   # positions a turn of the inner loop: a bf16 tile
_CHUNKS = (128, 64, 32, 16)   # a visit's positions: the first that divides
_ROOM = 40 << 20             # the most a call may hold in VMEM, of 128 MiB
_SIDE = 4                    # lane groups walked side by side, at most


def chunk_of(seq: int) -> int:
    """Positions a visit, from the sequence alone; 0 where it is no whole
    number of sixteen rows."""
    return next((q for q in _CHUNKS if seq % q == 0), 0)


def _side_by_side(block: int) -> int:
    """Lane groups of a block walked side by side: the largest power of
    two at most ``_SIDE`` that divides them."""
    return math.gcd(block // _LANES, _SIDE)


def vmem_bytes(chunk: int, block: int, state: int, itemsize: int) -> int:
    """What a visit of the backward call, the larger, holds: x, dy, dx
    and dt, ddt's blocks twice each; B's and C's tiles twice each; the
    starting state, A, D and dA's blocks twice and ``g``; the states
    again, their decays and a turn's rows; dB's and dC's sums by lane and
    their results, eight sublanes each, twice."""
    lanes = _side_by_side(block) * _LANES
    return (2 * chunk * block * (3 * itemsize + 8)
            + 4 * chunk * state * _LANES * itemsize
            + 4 * block * (7 * state + 16)
            + 4 * (2 * chunk + 1) * state * lanes + 4 * 2 * _ROWS * lanes
            + 2 * 4 * chunk * state * _LANES
            + 4 * 4 * 8 * chunk * state)


def block_of(chunk: int, channels: int, state: int, itemsize: int) -> int:
    """Channels a visit: the most whole lane groups, a divisor of them
    all, whose visit fits ``_ROOM``; 0 where none does."""
    groups = channels // _LANES
    return next((d * _LANES for d in range(groups, 0, -1)
                 if groups % d == 0
                 and vmem_bytes(chunk, d * _LANES, state, itemsize)
                 <= _ROOM), 0)


def fits(seq: int, channels: int, state: int, chunk: int,
         itemsize: int) -> bool:
    """Whether the kernels take the shape: whole lane groups of channels,
    whole sublane tiles of states, a whole number of chunks of whole
    turns, and a block of channels that fits VMEM."""
    return (channels % _LANES == 0 and state % (32 // itemsize) == 0
            and chunk % _ROWS == 0 and seq % chunk == 0
            and block_of(chunk, channels, state, itemsize) > 0)


def _note_visits(kind: str, visits: int) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "s6_scan_kernel_visits_total",
        "visits of the selective scan's Mosaic kernels, counted a call "
        "when the call is traced: a chunk of positions for a block of "
        "channels; 0 where an XLA form ran", labels={"kind": kind}).inc(
            visits)


# --------------------------------------------------------------------------
# what both kernels make of a turn's rows.  Every index is an int32 of its
# own: a Python int is an int64 under the package's x64, which Mosaic's
# index arithmetic refuses.  The lane groups walked side by side are the
# lanes of one array, ``[N, lanes]``, and what is done a position is
# written with ``jax.lax``'s own operations: every ``jax.numpy`` function
# and every ``*`` and ``+`` of two traced values is a jitted call of its
# own, 4 ms of tracing each where the benchmark runs, and a body holds
# hundreds (6.6 s of a 52 s set-up went to tracing the walk back so
# written: PERF.md section 6, PR 39)
# --------------------------------------------------------------------------
def _f32(values):
    return lax.convert_element_type(values, jnp.float32)


def _down(ref, row: int, state: int):
    """Row ``row`` of ``ref [rows, lanes]`` laid down the sublanes, ``[N,
    lanes]``.  A lane group at a time: the load of one register's row
    repeats it itself (a sublane stride of 0) and no vector operation is
    spent, where a wider row is loaded once and permuted."""
    return lax.concatenate([
        lax.broadcast_in_dim(ref[row:row + 1, i:i + _LANES], (state, _LANES),
                             (0, 1))
        for i in range(0, ref.shape[1], _LANES)], 1)


def _across(tile, lanes: int):
    """B's or C's tile ``[N, 128]`` against ``lanes`` lanes, float32: the
    same registers again, a lane group after another."""
    return lax.concatenate([_f32(tile)] * (lanes // _LANES), 1)


def _fold(values):
    """``[N, lanes] -> [N, 128]``: the lane groups added to each other, no
    lane crossed."""
    return functools.reduce(lax.add, (
        lax.slice_in_dim(values, i, i + _LANES, axis=1)
        for i in range(0, values.shape[1], _LANES)))


def _advance(s, a_t, dt, k: int, b, row_scr):
    """Position k of a turn: (its decay ``exp(dt_k A)``, the state after
    it), from the state before it ``s [N, lanes]``, ``a_t [N, lanes]``,
    the turn's window ``dt`` of dt's block, B's tile ``b`` and ``dt x``
    under ``row_scr[k]``."""
    state = s.shape[0]
    decay = lax.exp(lax.mul(_down(dt, k, state), a_t))
    return decay, lax.add(lax.mul(decay, s),
                          lax.mul(b, _down(row_scr.at[0:_ROWS], k, state)))


def _roll(values, shift: int):
    """Down the sublanes by ``shift``."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(values, np.int32(shift), 0)


def _sums_to_tile(parts):
    """Eight ``[N, lanes]`` arrays' sums over the states as the rows of one
    ``[8, lanes]`` tile: the sublane tiles of each added, then three rounds
    that halve the registers, each keeping one array's partial sums in the
    sublanes whose bit ``d`` is clear and the other's in those where it is
    set.  31 operations a lane group for eight rows, where eight separate
    sums take 56."""
    sub = lax.broadcasted_iota(jnp.int32, (8, parts[0].shape[1]), 0)
    parts = [functools.reduce(lax.add, (
        lax.slice_in_dim(p, i, i + 8, axis=0)
        for i in range(0, p.shape[0], 8))) for p in parts]
    for d in (4, 2, 1):
        clear = lax.eq(lax.bitwise_and(sub, lax.full_like(sub, d)),
                       lax.full_like(sub, 0))
        half = len(parts) // 2
        if d == 4:      # four up and four down are the same rotation
            parts = [lax.add(lax.select(clear, u, v),
                             _roll(lax.select(clear, v, u), 4))
                     for u, v in zip(parts[:half], parts[half:])]
        else:
            parts = [lax.select(clear, lax.add(u, _roll(u, 8 - d)),
                                lax.add(v, _roll(v, d)))
                     for u, v in zip(parts[:half], parts[half:])]
    return parts[0]


def _rows_to_tile(parts):
    """Sixteen ``[N, lanes]`` arrays' sums over the states as a tile ``[16,
    lanes]``, row k the k-th array's."""
    return lax.concatenate(
        [_sums_to_tile(parts[:8]), _sums_to_tile(parts[8:])], 0)


def _each_turn(n: int, fn, carry):
    """``carry = fn(i, carry)`` for ``i`` in ``range(n)`` as a loop inside
    the kernel (as ``sparse_attention._each_head``)."""
    def step(state, _):
        i, inner = state
        return (i + np.int32(1), fn(i, inner)), None

    return lax.scan(step, (np.int32(0), carry), None, length=n)[0][1]


def _lanes_from(i, lanes: int):
    """The i-th ``lanes`` lanes of a block."""
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(i * np.int32(lanes), lanes), lanes)


def _tile_rows(row0):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(row0, _ROWS), _ROWS)


def _state_rows(position, state: int):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(position * np.int32(state), state), state)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(x_ref, dt_ref, at_ref, bb_ref, cb_ref, d_ref, y_ref, *rest,
                states: bool):
    """A chunk of y for a block of channels, ``row_scr``'s lanes of it at
    a time, and the state the next chunk starts from; with ``states``, the
    state this chunk started from goes out too.  ``row_scr`` holds a
    turn's ``dt x``, to be read back a row at a time."""
    from jax.experimental import pallas as pl
    st_ref = rest[0] if states else None
    h_scr, row_scr = rest[-2:]
    chunk, block = x_ref.shape
    state, lanes = at_ref.shape[0], row_scr.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr[...])

    if states:
        st_ref[...] = h_scr[...]

    def lane_groups(i, _):
        at = _lanes_from(i, lanes)
        a_t = at_ref[:, at]

        def turn(r, s):
            row0 = r * np.int32(_ROWS)
            rows = _tile_rows(row0)
            x, dt = _f32(x_ref[rows, at]), dt_ref.at[rows, at]
            row_scr[...] = dt[...] * x
            out = []
            for k in range(_ROWS):
                here = _state_rows(row0 + np.int32(k), state)
                _, s = _advance(s, a_t, dt, k, _across(bb_ref[here, :], lanes),
                                row_scr)
                out.append(lax.mul(_across(cb_ref[here, :], lanes), s))
            y = _rows_to_tile(out) + d_ref[:, at] * x
            y_ref[rows, at] = y.astype(y_ref.dtype)
            return s

        h_scr[:, at] = _each_turn(chunk // _ROWS, turn, h_scr[:, at])

    _each_turn(block // lanes, lane_groups, None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kernel(x_ref, dt_ref, at_ref, bb_ref, cb_ref, d_ref, dy_ref, st_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, g_scr, s_scr,
                a_scr, row_scr, sb_scr, sc_scr):
    """A chunk's gradients for a block of channels, last chunk first,
    ``row_scr``'s lanes of it at a time: their states again into ``s_scr``
    (position t's under row ``(t + 1) N``, the chunk's starting state
    under row 0) and their decays into ``a_scr``, then the walk back,
    which writes dx and ddt, adds dA up in its block and dB and dC by
    lane in ``sb_scr`` and ``sc_scr``, and leaves ``g`` in ``g_scr`` for
    the chunk before.  ``row_scr`` holds a turn's ``dt x`` and dy, to be
    read back a row at a time."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    chunk, block = x_ref.shape
    state, turns, lanes = at_ref.shape[0], chunk // _ROWS, row_scr.shape[1]
    dtx_rows, dy_rows = row_scr.at[0:_ROWS], row_scr.at[_ROWS:2 * _ROWS]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        g_scr[...] = jnp.zeros_like(g_scr[...])
        da_ref[...] = jnp.zeros_like(da_ref[...])

    sb_scr[...] = jnp.zeros_like(sb_scr[...])
    sc_scr[...] = jnp.zeros_like(sc_scr[...])

    def lane_groups(i, _):
        at = _lanes_from(i, lanes)
        a_t = at_ref[:, at]

        def ahead(r, s):
            row0 = r * np.int32(_ROWS)
            rows = _tile_rows(row0)
            dt = dt_ref.at[rows, at]
            dtx_rows[...] = dt[...] * _f32(x_ref[rows, at])
            for k in range(_ROWS):
                here = _state_rows(row0 + np.int32(k), state)
                a_scr[here, :], s = _advance(
                    s, a_t, dt, k, _across(bb_ref[here, :], lanes), row_scr)
                s_scr[_state_rows(row0 + np.int32(k + 1), state), :] = s
            return s

        s_scr[0:state, :] = st_ref[:, at]
        last = _each_turn(turns, ahead, st_ref[:, at])

        def back(i_, carry):
            row0 = (np.int32(turns - 1) - i_) * np.int32(_ROWS)
            rows = _tile_rows(row0)
            g, s_now, d_a = carry
            x, dy = _f32(x_ref[rows, at]), _f32(dy_ref[rows, at])
            dt = dt_ref.at[rows, at]
            dtx_rows[...] = dt[...] * x
            dy_rows[...] = dy
            to_x, to_dt = [], []
            for k in reversed(range(_ROWS)):
                here = _state_rows(row0 + np.int32(k), state)
                dy_k = _down(dy_rows, k, state)
                g = lax.add(g, lax.mul(_across(cb_ref[here, :], lanes), dy_k))
                to_x.append(lax.mul(g, _across(bb_ref[here, :], lanes)))
                sb_scr[here, :] = lax.add(sb_scr[here, :], _fold(
                    lax.mul(g, _down(dtx_rows, k, state))))
                sc_scr[here, :] = lax.add(sc_scr[here, :],
                                          _fold(lax.mul(s_now, dy_k)))
                g = lax.mul(a_scr[here, :], g)
                s_now = s_scr[here, :]
                by_decay = lax.mul(g, s_now)
                to_dt.append(lax.mul(by_decay, a_t))
                d_a = lax.add(d_a, lax.mul(by_decay, _down(dt, k, state)))
            by_x = _rows_to_tile(to_x[::-1])
            dx_ref[rows, at] = (dt[...] * by_x + d_ref[:, at] * dy).astype(
                dx_ref.dtype)
            ddt_ref[rows, at] = _rows_to_tile(to_dt[::-1]) + x * by_x
            return g, s_now, d_a

        g, _, d_a = _each_turn(turns, back, (
            g_scr[:, at], last, jnp.zeros((state, lanes), f32)))
        g_scr[:, at] = g
        da_ref[:, at] += d_a

    _each_turn(block // lanes, lane_groups, None)
    # across the lanes: a product with ones, each result in all eight rows
    ones = jnp.ones((8, _LANES), f32)
    for src, dst in ((sb_scr, db_ref), (sc_scr, dc_ref)):
        dst[...] = lax.dot_general(
            ones, src[...], (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=f32)[0:1]


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------
def _specs(chunks: int, chunk: int, block: int, state: int, back: bool):
    """Block specs over (blocks, chunks), the chunks last first where
    ``back``: a chunk's rows of a block's columns of ``[S, C]``; a chunk's
    tiles of ``[S * N, 128]``; a block's columns of ``[height, C]``; and
    a chunk's ``height`` rows of a block's columns of ``[chunks * height,
    C]``."""
    from jax.experimental import pallas as pl
    n = np.int32(chunks)
    at = (lambda c: n - 1 - c) if back else (lambda c: c)
    return (pl.BlockSpec((chunk, block), lambda i, c: (at(c), i)),
            pl.BlockSpec((chunk * state, _LANES), lambda i, c: (at(c), i * 0)),
            lambda height: pl.BlockSpec((height, block),
                                        lambda i, c: (c * 0, i)),
            lambda height: pl.BlockSpec((height, block),
                                        lambda i, c: (at(c), i)))


def _by_lane(values, state: int):
    """``[S, N] -> [S * N, 128]``: the value of state n of position t in
    every lane of row ``t * N + n``."""
    seq = values.shape[0]
    return jnp.broadcast_to(values[:, :, None],
                            (seq, state, _LANES)).reshape(seq * state, _LANES)


@functools.lru_cache(maxsize=None)
def _program(back: bool, states: bool, interpret: bool, chunk: int,
             block: int, seq: int, channels: int, state: int, dtype):
    """A kernel call as a jaxpr, traced once a shape and process: the
    forward call (with ``states``, the chunks' starting states a second
    result) or, ``back``, the walk back, on x ``[seq, channels]`` of
    ``dtype``, ``block`` channels a visit.  Kept here and not left to
    ``jax.jit``'s own cache, which holds a trace a type of operand: under
    the runner's mesh a cotangent comes typed with the mesh or without, a
    check outside the mesh with neither, and each would trace a body of
    some thousand operations again (5 s of the benchmark's set-up,
    PERF.md section 6, PR 39)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32, itemsize = jnp.float32, np.dtype(dtype).itemsize
    blocks, chunks = channels // block, seq // chunk
    lanes = _side_by_side(block) * _LANES
    rows, tiles, whole, starts = _specs(chunks, chunk, block, state, back)
    shape = jax.ShapeDtypeStruct
    wide, by_lane = shape((seq, channels), dtype), shape(
        (seq * state, _LANES), dtype)
    kept = shape((chunks * state, channels), f32)
    operands = [wide, shape((seq, channels), f32),
                shape((state, channels), f32), by_lane, by_lane,
                shape((1, channels), f32)]
    in_specs = [rows, rows, whole(state), tiles, tiles, whole(1)]
    if back:
        n = np.int32(chunks)
        sums = pl.BlockSpec((None, None, 1, chunk * state),
                            lambda i, c: (i, n - 1 - c, c * 0, c * 0))
        summed = shape((blocks, chunks, 1, chunk * state), f32)
        kernel = _bwd_kernel
        operands += [wide, kept]
        in_specs += [rows, starts(state)]
        out_specs = [rows, rows, whole(state), sums, sums]
        out_shape = [wide, shape((seq, channels), f32),
                     shape((state, channels), f32), summed, summed]
        scratch = [pltpu.VMEM((state, block), f32),
                   pltpu.VMEM(((chunk + 1) * state, lanes), f32),
                   pltpu.VMEM((chunk * state, lanes), f32),
                   pltpu.VMEM((2 * _ROWS, lanes), f32),
                   pltpu.VMEM((chunk * state, _LANES), f32),
                   pltpu.VMEM((chunk * state, _LANES), f32)]
    else:
        kernel = functools.partial(_fwd_kernel, states=states)
        out_specs = [rows] + [starts(state)] * states
        out_shape = [wide] + [kept] * states
        scratch = [pltpu.VMEM((state, block), f32),
                   pltpu.VMEM((_ROWS, lanes), f32)]
    call = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(blocks, chunks), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_limit(vmem_bytes(chunk, block, state,
                                               itemsize))),
        interpret=interpret)
    return jax.make_jaxpr(call)(*operands)


def _run(back: bool, states: bool, interpret: bool, chunk: int, x, dt, A, B,
         C, D, *more):
    """The kernel call on the arrays as it reads them: dt float32, A
    transposed, B and C by lane in x's dtype, D a row."""
    f32, state = jnp.float32, A.shape[1]
    program = _program(
        back, states, interpret, chunk,
        block_of(chunk, x.shape[1], state, x.dtype.itemsize), *x.shape, state,
        x.dtype)
    return jax.core.eval_jaxpr(
        program.jaxpr, program.consts, x, dt.astype(f32), A.astype(f32).T,
        _by_lane(B.astype(x.dtype), state), _by_lane(C.astype(x.dtype), state),
        D.astype(f32)[None], *more)


# jitted on their own, as the Mamba-2 scan's two calls are: a model's
# layers and the forward pass run again share one lowering of a kernel a
# program
@functools.partial(jax.jit, static_argnames=("chunk", "states", "interpret"))
def _forward_call(x, dt, A, B, C, D, *, chunk: int, states: bool,
                  interpret: bool):
    return tuple(_run(False, states, interpret, chunk, x, dt, A, B, C, D))


def _visits(x, A, chunk: int) -> int:
    """The grid of a call: blocks of channels x chunks."""
    block = block_of(chunk, x.shape[1], A.shape[1], x.dtype.itemsize)
    return x.shape[1] // block * (x.shape[0] // chunk)


def _forward(x, dt, A, B, C, D, chunk: int, states: bool):
    """y ``[S, C]`` and, with ``states``, the states the chunks start
    from, ``[chunks * N, C]`` float32."""
    _note_visits("forward", _visits(x, A, chunk))
    return _forward_call(x, dt, A, B, C, D, chunk=chunk, states=states,
                         interpret=pallas_ops._interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, A, B, C, D, chunk: int):
    """``ssm.selective_scan`` through the kernels, for the shapes
    ``ssm.selective_scan_form`` gives them."""
    return _forward(x, dt, A, B, C, D, chunk, False)[0]


def _scan_fwd(x, dt, A, B, C, D, chunk):
    # the backward pass is given the inputs and the states the chunks
    # start from, as the XLA form's is
    y, starts = _forward(x, dt, A, B, C, D, chunk, True)
    return y, (x, dt, A, B, C, D, starts)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward_call(x, dt, A, B, C, D, starts, dy, *, chunk: int,
                   interpret: bool):
    (seq, state), f32 = B.shape, jnp.float32
    dy = dy.astype(x.dtype)
    dx, ddt, dA, dB, dC = _run(True, True, interpret, chunk, x, dt, A, B, C,
                               D, dy, starts)
    dD = (dy.astype(f32) * x.astype(f32)).sum(0)
    return (dx, ddt.astype(dt.dtype), dA.T.astype(A.dtype),
            dB.sum(0).reshape(seq, state).astype(B.dtype),
            dC.sum(0).reshape(seq, state).astype(C.dtype), dD.astype(D.dtype))


def _scan_bwd(chunk, kept, dy):
    _note_visits("backward", _visits(kept[0], kept[2], chunk))
    return _backward_call(*kept, dy, chunk=chunk,
                          interpret=pallas_ops._interpret())


scan.defvjp(_scan_fwd, _scan_bwd)
