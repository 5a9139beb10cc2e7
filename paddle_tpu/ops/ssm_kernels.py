"""The chunked scan of ``ops/ssm.py`` as Mosaic kernels: a chunk's decay
matrix, its products and the running state live in VMEM only.

The arrays keep the layer's own layout: x, dy, y and dx ``[S, H * P]``
(a lane group of 128 holds ``128 / P`` heads), B and C ``[S, G * N]``,
dt and its running sum ``cum`` inside a chunk ``[S, H]`` float32 and
once more as ``[H, S]``, so that a head's values are at hand down the
rows (from the first) and along the lanes (from the second).  Nothing is
re-laid by chunk.

A visit (one grid step) is a chunk of Q positions for all heads; the
chunks are the grid's one, sequential axis, and inside a visit a loop
walks the lane groups of heads (a grid step with nine operands costs 0.5
us, which 1 024 of them a call would pay: PERF.md section 6, PR 33).
``C . B^T [Q, Q]`` is made at a group's first lane group and shared by
the rest.  The step size is folded into x (``xd = dt x``), so a head's
matrix is ``M = exp(cum_l - cum_s) (C_l . B_s)`` under the lower
triangle, masked before the exponential.  A head's product takes the
whole lane group of ``xd`` with the other heads' lanes zeroed: nothing P
wide is sliced, and a product P wide fills as much of the MXU as one 128
wide.  The state stays in scratch from chunk to chunk, transposed, ``[N,
H * P]`` float32, so that no product takes its left side transposed on
the state's account.

Differentiated, the forward call also writes the state each chunk
starts from (``[chunks * N, H * P]`` float32, 67 MB at the published
sizes), and the backward pass is one call: a walk back, last chunk
first, that makes a chunk's matrices again and carries the state's
gradient.  The sums over a matrix's rows and columns that ``cum``'s
gradient needs are taken from ``[Q, P]`` arrays instead: ``sum_s dM M =
sum_p dy y`` and ``sum_l dM M = sum_p xd dxd``, both with the same
rounded ``xd``, since they cancel in A's gradient.  dB and dC add up
over a group's heads in their output blocks, ``C . B^T``'s gradient in
scratch.  The gradients of dt and A are finished outside, from two ``[S,
H]`` arrays.

Product inputs are x's dtype (bf16 under O2); decays, sums and the state
float32.  Which shapes take these kernels is ``ssm.scan_form``'s to say.
Two of them have run on the chip, both ``[8192, 64 heads, 64]`` at state
128: one group of B and C at chunk 256 (32 visits a call; the cell
``granite4h-micro-stage0-s8192``, PR 33) and eight groups at chunk 128
(64 visits of half the rows, ``C . B^T`` made eight times a chunk, at
each group's first of four lane groups; the cell
``nemotron3-nano-ep16stage0-s8192``, PR 34), each against the recurrence
a position at a time (``PERF.md`` section 2); the other shapes
``scan_form`` admits have run under the interpreter only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_ops
from .pallas_ops import _across
from .sparse_attention import _each_head

_LANES = 128
_ROOM = 64 << 20             # VMEM a call may use


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_AB, _ABT, _ATB = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def fits_vmem(heads: int, width: int, state: int, chunk: int) -> bool:
    """Whether the walk back's resident blocks leave a quarter of ``_ROOM``
    to a visit's own values: x, dy and dx ``[Q, H * P]`` twice each (at 4
    bytes, the most), the states' block twice and their gradient ``[N,
    H * P]`` float32, three ``[Q, Q]``.  The cell's shape holds 31 MB."""
    flat = heads * width
    return (24 * chunk * flat + 12 * state * flat + 12 * chunk * chunk
            <= _ROOM * 3 // 4)


def _note_visits(kind: str, visits: int) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "ssm_scan_kernel_visits_total",
        "visits of the state-space scan's Mosaic kernels, counted a call "
        "when the call is traced: a chunk, for all its heads; 0 where "
        "the XLA form ran", labels={"kind": kind}).inc(visits)


# --------------------------------------------------------------------------
# what every kernel makes of a visit's blocks
# --------------------------------------------------------------------------
def _columns(ref, g, width: int):
    """Of ``ref [Q, H]`` the columns of lane group ``g``'s heads, each
    held in every lane ``[Q, 128]``, and all of them by lane: lane l
    holds its head's."""
    tile = ref[...]
    q, share = tile.shape[0], _LANES // width
    head = _iota(tile.shape, 1)
    lane = _iota((q, _LANES), 1)
    each = [jnp.broadcast_to(jnp.sum(
        jnp.where(head == g * np.int32(share) + np.int32(j), tile, 0.0),
        axis=1, keepdims=True), (q, _LANES)) for j in range(share)]
    by_lane = each[0]
    for j in range(1, share):
        by_lane = jnp.where(lane >= j * width, each[j], by_lane)
    return each, by_lane


def _of_head(values, j: int, width: int):
    """``values`` where head j of the lane group has its lanes, 0
    elsewhere."""
    if width == _LANES:
        return values
    lane = _iota(values.shape, 1)
    inside = (lane >= j * width) & (lane < (j + 1) * width)
    return jnp.where(inside, values, jnp.zeros_like(values))


def _head_sums(values, width: int):
    """The sums over each head's lanes of ``[Q, 128]``, ``[Q, 1]`` a
    head."""
    return [jnp.sum(_of_head(values, j, width), axis=1, keepdims=True)
            for j in range(_LANES // width)]


def _above_the_diagonal(q: int):
    """``[Q, Q]`` float32: 0 on and under the diagonal, -inf above it."""
    return jnp.where(_iota((q, q), 0) >= _iota((q, q), 1),
                     jnp.float32(0.0), jnp.float32(-jnp.inf))


def _decays(cum_each, cumT_ref, g, j: int, neg_scr):
    """A head's ``exp(cum_l - cum_s)`` under the lower triangle, ``[Q,
    Q]`` float32: masked before the exponential, since above the
    diagonal the difference is positive and may overflow."""
    from jax.experimental import pallas as pl
    q = neg_scr.shape[0]
    head = g * np.int32(len(cum_each)) + np.int32(j)
    along = cumT_ref[pl.ds(head, 1), :]
    return jnp.exp(_across(cum_each[j], q) - along + neg_scr[...])


def _group(g, per_group: int, groups: int, state: int):
    """Where lane group ``g``'s B and C lie (the lanes of ``[Q, G * N]``
    and the rows of ``[G * N, Q]``), and whether it is its group's first
    and last lane group: None where every lane group is both."""
    from jax.experimental import pallas as pl
    at = slice(0, state)
    if groups > 1:
        start = jax.lax.div(g, np.int32(per_group)) * np.int32(state)
        at = pl.ds(pl.multiple_of(start, state), state)
    if per_group == 1:
        return at, None, None
    rest = jax.lax.rem(g, np.int32(per_group))
    return at, rest == 0, rest == per_group - 1


def _when(condition, fn):
    from jax.experimental import pallas as pl
    if condition is None:
        return fn()
    pl.when(condition)(fn)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(x_ref, dt_ref, cum_ref, cumT_ref, b_ref, c_ref, bT_ref,
                d_ref, y_ref, *rest, width: int, groups: int, states: bool):
    """A chunk of y for every head, a lane group of heads at a time, and
    the state the next chunk starts from, held transposed, ``[N, H *
    P]``; with ``states``, the state this chunk started from goes out
    too, for the backward pass."""
    from jax.experimental import pallas as pl
    st_ref = rest[0] if states else None
    h_scr, cb_scr, neg_scr = rest[-3:]
    q, share, dtype = x_ref.shape[0], _LANES // width, x_ref.dtype
    lane_groups = x_ref.shape[1] // _LANES
    state = b_ref.shape[1] // groups

    @pl.when(pl.program_id(0) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr[...])
        neg_scr[...] = _above_the_diagonal(q)

    def lane_group(g, cols):
        at, first, _ = _group(g, lane_groups // groups, groups, state)

        def shared():
            cb_scr[...] = _dot(c_ref[:, at], b_ref[:, at], _ABT)
        _when(first, shared)

        _, dt = _columns(dt_ref, g, width)
        cum_each, cum = _columns(cum_ref, g, width)
        xf = x_ref[:, cols].astype(jnp.float32)
        xdt = xf * dt
        xd = xdt.astype(dtype)
        h_in = h_scr[:, cols]
        if states:
            st_ref[:, cols] = h_in
        y = (jnp.exp(cum) * _dot(c_ref[:, at], h_in.astype(dtype), _AB)
             + d_ref[:, cols] * xf)
        for j in range(share):
            m = _decays(cum_each, cumT_ref, g, j, neg_scr) * cb_scr[...]
            y = y + _dot(m.astype(dtype), _of_head(xd, j, width), _AB)
        y_ref[:, cols] = y.astype(y_ref.dtype)
        total = cum[q - 1:q, :]
        to_end = (xdt * jnp.exp(total - cum)).astype(dtype)
        h_scr[:, cols] = (jnp.exp(total) * h_in
                          + _dot(bT_ref[at, :], to_end, _AB))

    _each_head(lane_groups, _LANES, lane_group)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, cumT_ref, b_ref, c_ref,
                cT_ref, d_ref, st_ref, dx_ref, ddt_ref, dcum_ref, db_ref,
                dc_ref, dd_ref, dh_scr, cb_scr, neg_scr, dg_scr, *,
                width: int, groups: int):
    """A chunk's gradients, last chunk first, a lane group of heads at a
    time: dx; by head, dt's direct part and cum's; dB and dC summed over
    a group's heads; D's by lane; and the gradient of the state the
    chunk started from, carried to the chunk before."""
    from jax.experimental import pallas as pl
    q, share, dtype = x_ref.shape[0], _LANES // width, x_ref.dtype
    lane_groups = x_ref.shape[1] // _LANES
    state = b_ref.shape[1] // groups

    @pl.when(pl.program_id(0) == 0)
    def _start():
        dh_scr[...] = jnp.zeros_like(dh_scr[...])
        dd_ref[...] = jnp.zeros_like(dd_ref[...])
        neg_scr[...] = _above_the_diagonal(q)

    db_ref[...] = jnp.zeros_like(db_ref[...])
    dc_ref[...] = jnp.zeros_like(dc_ref[...])

    def lane_group(g, cols):
        at, first, last_of_group = _group(g, lane_groups // groups, groups,
                                          state)
        b, cc = b_ref[:, at], c_ref[:, at]

        def shared():
            cb_scr[...] = _dot(cc, b, _ABT)
            dg_scr[...] = jnp.zeros_like(dg_scr[...])
        _when(first, shared)

        _, dt = _columns(dt_ref, g, width)
        cum_each, cum = _columns(cum_ref, g, width)
        xf, dy = x_ref[:, cols].astype(jnp.float32), dy_ref[:, cols]
        dyf = dy.astype(jnp.float32)
        xdt = xf * dt
        xd = xdt.astype(dtype)
        total = cum[q - 1:q, :]
        decay_to_end = jnp.exp(total - cum)
        to_end = xdt * decay_to_end
        grown, carried = jnp.exp(cum), jnp.exp(total)

        # through the state: what the chunk started from, what it
        # passed on
        h_in, dh_out = st_ref[:, cols], dh_scr[:, cols]
        hb, dhb = h_in.astype(dtype), dh_out.astype(dtype)
        from_state = grown * _dot(cc, hb, _AB)
        d_from = (dyf * grown).astype(dtype)
        dc_ref[:, at] += _dot(d_from, hb, _ABT)
        db_ref[:, at] += _dot(to_end.astype(dtype), dhb, _ABT)
        d_to_end = _dot(b, dhb, _AB)
        dh_scr[:, cols] = (carried * dh_out
                           + _dot(cT_ref[at, :], d_from, _AB))
        # d exp(total), by lane
        kept = jnp.sum(dh_out * h_in, axis=0, keepdims=True) * carried

        # inside the chunk, a head at a time
        y_local = jnp.zeros((q, _LANES), jnp.float32)
        dxd = jnp.zeros((q, _LANES), jnp.float32)
        dg = None
        for j in range(share):
            decay = _decays(cum_each, cumT_ref, g, j, neg_scr)
            mb = (decay * cb_scr[...]).astype(dtype)
            dy_j = _of_head(dy, j, width)
            y_local = y_local + _dot(mb, _of_head(xd, j, width), _AB)
            dxd = dxd + _dot(mb, dy_j, _ATB)
            dm = _dot(dy_j, xd, _ABT) * decay
            dg = dm if dg is None else dg + dm
        dg_scr[...] += dg

        def scores():
            dg_b = dg_scr[...].astype(dtype)
            dc_ref[:, at] += _dot(dg_b, b, _AB)
            db_ref[:, at] += _dot(dg_b, cc, _ATB)
        _when(last_of_group, scores)

        dxdt = dxd + d_to_end * decay_to_end
        dx_ref[:, cols] = (dt * dxdt + d_ref[:, cols] * dyf).astype(
            dx_ref.dtype)
        dd_ref[:, cols] += jnp.sum(dyf * xf, axis=0, keepdims=True)

        # by head: dt's direct part; cum's, whose last row takes the
        # total's
        to_total = d_to_end * to_end
        ddt = _head_sums(xf * dxdt, width)
        dcum = _head_sums(dyf * (y_local + from_state)
                          - xd.astype(jnp.float32) * dxd - to_total, width)
        dtotal = _head_sums(
            jnp.sum(to_total, axis=0, keepdims=True) + kept, width)
        head = _iota(ddt_ref.shape, 1)
        last = _iota((q, 1), 0) == q - 1
        for j in range(share):
            here = head == g * np.int32(share) + np.int32(j)
            ddt_ref[...] = jnp.where(here, ddt[j], ddt_ref[...])
            dcum_ref[...] = jnp.where(
                here, dcum[j] + jnp.where(last, dtotal[j], 0.0),
                dcum_ref[...])

    _each_head(lane_groups, _LANES, lane_group)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------
def _specs(chunks: int, chunk: int, back: bool):
    """Block specs over the chunks, last first where ``back``: a chunk's
    rows of an array ``[S, n]`` (or, ``height`` rows a chunk, of the
    states ``[chunks * N, n]``), its columns of ``[n, S]``, and the whole
    of ``[1, n]``."""
    from jax.experimental import pallas as pl
    n = np.int32(chunks)
    at = (lambda c: n - 1 - c) if back else (lambda c: c)
    return (lambda width, height=chunk: pl.BlockSpec(
                (height, width), lambda c: (at(c), c * 0)),
            lambda height: pl.BlockSpec((height, chunk),
                                        lambda c: (c * 0, at(c))),
            lambda width: pl.BlockSpec((1, width), lambda c: (c * 0, c * 0)))


def _operands(x, dt, A, B, C, D, chunk: int):
    """The arrays as the kernels read them (the docstring's layouts): x,
    dt, cum, cum transposed, B, C, both transposed, D by lane."""
    seq, heads, width = x.shape
    dt = dt.astype(jnp.float32)
    steps = (dt * A.astype(jnp.float32)).reshape(seq // chunk, chunk, heads)
    cum = jnp.cumsum(steps, axis=1).reshape(seq, heads)
    b2 = B.reshape(seq, -1).astype(x.dtype)
    c2 = C.reshape(seq, -1).astype(x.dtype)
    by_lane = jnp.repeat(D.astype(jnp.float32), width)[None]
    return (x.reshape(seq, heads * width), dt, cum, cum.T, b2, c2, b2.T,
            c2.T, by_lane)


def _call(kernel, chunks: int, interpret: bool, in_specs, out_specs,
          out_shape, scratch, *args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(chunks,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_ROOM),
        interpret=interpret)(*args)


# The two calls are jitted on their own: a model's layers, the forward
# pass run again and the reference checks then share one trace of a
# kernel and one lowering of it a program, where each call site would
# trace and lower its own (0.36 s a site, 38 sites in the Granite cell's
# set-up: PERF.md section 6, PR 33).
@functools.partial(jax.jit, static_argnames=("chunk", "states", "interpret"))
def _forward_call(x, dt, A, B, C, D, *, chunk: int, states: bool,
                  interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    (seq, heads, width), (groups, state) = x.shape, B.shape[1:]
    chunks, flat, f32 = seq // chunk, heads * width, jnp.float32
    rows, cols, whole = _specs(chunks, chunk, back=False)
    x2, dt32, cum, cumT, b2, c2, bT, _, by_lane = _operands(
        x, dt, A, B, C, D, chunk)
    out = _call(
        functools.partial(_fwd_kernel, width=width, groups=groups,
                          states=states), chunks, interpret,
        [rows(flat), rows(heads), rows(heads), cols(heads),
         rows(groups * state), rows(groups * state), cols(groups * state),
         whole(flat)],
        [rows(flat)] + [rows(flat, state)] * states,
        [jax.ShapeDtypeStruct((seq, flat), x.dtype)]
        + [jax.ShapeDtypeStruct((chunks * state, flat), f32)] * states,
        [pltpu.VMEM((state, flat), f32), pltpu.VMEM((chunk, chunk), f32),
         pltpu.VMEM((chunk, chunk), f32)],
        x2, dt32, cum, cumT, b2, c2, bT, by_lane)
    return (out[0].reshape(x.shape),) + tuple(out[1:])


def _forward(x, dt, A, B, C, D, chunk: int, states: bool):
    """y ``[S, H, P]`` and, with ``states``, the states the chunks start
    from, ``[chunks * N, H * P]`` float32."""
    _note_visits("fwd", x.shape[0] // chunk)
    return _forward_call(x, dt, A, B, C, D, chunk=chunk, states=states,
                         interpret=pallas_ops._interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, A, B, C, D, chunk: int):
    """``ssm.ssd_scan`` through the kernels, for the shapes
    ``ssm.scan_form`` gives them."""
    return _forward(x, dt, A, B, C, D, chunk, False)[0]


def _scan_fwd(x, dt, A, B, C, D, chunk):
    # the backward pass is given the inputs and the states the chunks
    # start from (a chunk's matrices it makes again)
    y, starts = _forward(x, dt, A, B, C, D, chunk, True)
    return y, (x, dt, A, B, C, D, starts)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward_call(x, dt, A, B, C, D, starts, dy, *, chunk: int,
                   interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    (seq, heads, width), (groups, state) = x.shape, B.shape[1:]
    chunks, flat, f32 = seq // chunk, heads * width, jnp.float32
    rows, cols, whole = _specs(chunks, chunk, back=True)
    x2, dt32, cum, cumT, b2, c2, _, cT, by_lane = _operands(
        x, dt, A, B, C, D, chunk)
    by_head, bc = rows(heads), rows(groups * state)
    dx, ddt, dcum, dB, dC, dD = _call(
        functools.partial(_bwd_kernel, width=width, groups=groups), chunks,
        interpret,
        [rows(flat), rows(flat), by_head, by_head, cols(heads), bc, bc,
         cols(groups * state), whole(flat), rows(flat, state)],
        [rows(flat), by_head, by_head, bc, bc, whole(flat)],
        [jax.ShapeDtypeStruct(x2.shape, x.dtype),
         jax.ShapeDtypeStruct(dt32.shape, f32),
         jax.ShapeDtypeStruct(dt32.shape, f32),
         jax.ShapeDtypeStruct(b2.shape, f32),
         jax.ShapeDtypeStruct(c2.shape, f32),
         jax.ShapeDtypeStruct((1, flat), f32)],
        [pltpu.VMEM((state, flat), f32)]
        + [pltpu.VMEM((chunk, chunk), f32)] * 3,
        x2, dy.reshape(x2.shape).astype(x.dtype), dt32, cum, cumT, b2, c2,
        cT, by_lane, starts)
    # cum is the running sum of dt A inside a chunk: its gradient runs
    # back from a chunk's end
    by_chunk = dcum.reshape(chunks, chunk, heads)
    dsteps = jnp.flip(jnp.cumsum(jnp.flip(by_chunk, 1), axis=1), 1).reshape(
        dt32.shape)
    return (dx.reshape(x.shape),
            (ddt + dsteps * A.astype(f32)).astype(dt.dtype),
            (dsteps * dt32).sum(0).astype(A.dtype),
            dB.reshape(B.shape).astype(B.dtype),
            dC.reshape(C.shape).astype(C.dtype),
            dD.reshape(heads, width).sum(1).astype(D.dtype))


def _scan_bwd(chunk, kept, dy):
    _note_visits("bwd", kept[0].shape[0] // chunk)
    return _backward_call(*kept, dy, chunk=chunk,
                          interpret=pallas_ops._interpret())


scan.defvjp(_scan_fwd, _scan_bwd)
