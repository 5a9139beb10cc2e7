"""The routed experts' grouped matrix products as Mosaic kernels.

Rows sorted by group (``moe/grouped.py``: the pairs of a window, sorted
by expert), ``sizes [held]`` rows a group, the rows past their sum
belonging to no group.  Three products, the contract of
``jax.lax.ragged_dot`` and of its two gradients:

    dot(lhs, rhs, sizes)                    out[r] = lhs[r] . rhs[group(r)]
    dot(lhs, rhs, sizes, transposed=True)   out[r] = lhs[r] . rhs[group(r)]^T
    dot_weights(lhs, d_out, sizes)          out[e] = lhs_e^T . d_out_e

with ``rhs [held, K, N]`` read as it is stored in both directions (no
transposed copy of a weight), rows of no group 0 in the first two and
left out of the third, operands in their own dtype (bf16 under O2),
sums in float32 over the whole contraction, one rounding at the end.

A visit (one grid step) is a tile of ``tm`` rows for one group, with
the group's whole matrix in VMEM: the matrix's block index only moves
when the group does, so a call reads each expert's matrix once, and K
and N are whole-axis blocks, so a width that is no whole lane group
(1856) needs no padded copy of anything.  Which (group, tile) pairs a
call visits is worked out from ``sizes`` before the call
(:func:`_visits`) and handed in as scalar prefetch: only the tiles that
hold rows of a group, a tile that two groups share once for each with
the other's rows masked.  The grid is static (``tiles + held - 1``
visits, the most a call can need); what is left of it after the last
pair writes the tiles past the count as zeros, without a product or a
fetch, and then does nothing.  The weights' product visits every group
at least once, an empty one to write its zeros.

Which shapes take these kernels is ``form``'s to say, from the rows, K,
N and the dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_ops
from .ssm_kernels import _AB, _ABT, _ATB, _dot

_LANES = 128
_ROOM = 72 << 20             # the most a call may hold in VMEM, of 128 MiB
# Rows a visit.  On the chip 128 and 256 take the same time at both
# cells' shapes and 512 a third more (the rows that a visit multiplies
# for nothing grow with the tile); a call site's code grows with the
# tile, 0.66 MB at 128 and 1.24 at 256 for [6144, 2688] by [8, 2688,
# 1856], and a step holds 56 sites (PERF.md section 6, PR 35).
_TILE = 128


def _rows_vmem(tm: int, k: int, n: int, itemsize: int) -> int:
    """What a visit of the rows' products holds: its blocks twice each
    (the rows, the group's matrix, the result) and two float32 values of
    the result's shape."""
    return 2 * itemsize * (tm * k + k * n + tm * n) + 8 * tm * n


def _weights_vmem(tm: int, k: int, n: int, itemsize: int) -> int:
    """What a visit of the weights' product holds: the rows' blocks
    twice each and one of them once more, the result's block twice and
    its float32 sum in scratch."""
    return (itemsize * (2 * tm * (k + n) + tm * max(k, n))
            + k * n * (4 + 2 * itemsize))


def _limit(needed: int) -> int:
    """The VMEM a call asks for: what it holds and a quarter more (the
    chip's compiler took the two products of ``[6144, 2688]`` by ``[8,
    2688, 1856]`` at 28 and 40 MiB with tiles of 256 rows, where this
    gave 36 and 57).  No more, because what a call reserves is taken
    from what the compiler may keep in VMEM around it: with 100 MiB
    reserved the ``[6144, 2688]`` result (33 MB) went to HBM, and the
    gather that reads it, 49 152 rows, took 2.4 ms where it takes 0.4
    from VMEM (``PERF.md`` section 6, PR 35)."""
    return needed * 5 // 4 + (2 << 20)


def form(lhs, rhs) -> str:
    """Which form of ``lhs [rows, K]`` times ``rhs [held, K, N]`` and of
    its two gradients runs, from platform, shape and dtype (arrays or
    their ``ShapeDtypeStruct``s): ``"kernels"`` on a TPU (or under the
    interpreter) where both are of one floating dtype, the rows are whole
    tiles and a group's matrix with a visit's blocks fits VMEM; ``"xla"``
    (``jax.lax.ragged_dot``) everywhere else."""
    (rows, k), n, size = lhs.shape, rhs.shape[2], lhs.dtype.itemsize
    fits = (lhs.dtype == rhs.dtype
            and jnp.issubdtype(lhs.dtype, jnp.floating)
            and rows % _TILE == 0
            and max(_rows_vmem(_TILE, k, n, size),
                    _weights_vmem(_TILE, k, n, size)) <= _ROOM)
    return "kernels" if fits and pallas_ops._kernels_enabled() else "xla"


def _stored_transposed(k: int, n: int) -> bool:
    """Whether a TPU keeps ``[held, K, N]`` with K along the lanes: its
    compiler lays an array out so, parameters included, where N is no
    whole number of lane groups and K is (``[8, 2688, 1856]``), and a
    Mosaic call, which takes its operands row-major, would be handed a
    copy (83 MB a call at that shape).  The calls below then take the
    array as ``[held, N, K]``, which is the same bytes, and contract over
    the other axis."""
    return n % _LANES != 0 and k % _LANES == 0


def _note_call(kind: str) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "moe_grouped_kernel_calls_total",
        "calls of the grouped products' Mosaic kernels, counted a call "
        "when the call is traced: fwd rows . W, dlhs rows . W^T, drhs "
        "the weights' gradient; 0 where the XLA form ran",
        labels={"kind": kind}).inc()


# --------------------------------------------------------------------------
# which (group, tile) pairs a call visits
# --------------------------------------------------------------------------
def _visits(sizes, rows: int, tm: int, every_group: bool):
    """The scalar-prefetch operands, all int32: the group and the row
    tile of each of the ``tiles + held - 1`` visits, the groups' first
    rows and ends, and ``[visits that hold a pair, tiles of no group,
    the last tile that holds a pair]``.  A group's visits are the tiles
    its rows touch (with ``every_group`` one tile for an empty group
    too), in order; after them come the tiles past the count, then
    visits that stay where the last one was."""
    held, tiles = sizes.shape[0], rows // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    span = jnp.where(sizes > 0, (ends + (tm - 1)) // tm - first,
                     1 if every_group else 0).astype(jnp.int32)
    upto = jnp.cumsum(span, dtype=jnp.int32)
    active = upto[-1]
    covered = (ends[-1] + (tm - 1)) // tm
    i = jnp.arange(tiles + held - 1, dtype=jnp.int32)
    last = jnp.maximum(active - 1, 0)
    # the group of visit i: as many groups end at or before it
    of = jnp.minimum(i, last)[:, None] >= upto[None, :]
    group = jnp.minimum(of.sum(1, dtype=jnp.int32), held - 1)
    mine = group[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
    tile = i + jnp.where(mine, (first - (upto - span))[None, :], 0).sum(
        1, dtype=jnp.int32)
    tile = jnp.where(i < active, tile,
                     jnp.minimum(covered + (i - active), tiles - 1))
    info = jnp.stack([active, tiles - covered, jnp.maximum(covered - 1, 0)])
    return group, tile, starts, ends, info


def _inside(tile, tm: int, lo, hi):
    """``[tm, 1]`` bool: the tile's rows that lie in ``lo .. hi``."""
    row = tile * np.int32(tm) + jax.lax.broadcasted_iota(
        jnp.int32, (tm, 1), 0)
    return (row >= lo) & (row < hi)


# --------------------------------------------------------------------------
# rows . W and rows . W^T
# --------------------------------------------------------------------------
def _rows_kernel(group_ref, tile_ref, start_ref, end_ref, info_ref,
                 lhs_ref, rhs_ref, out_ref, *, contract):
    """A tile's rows of one group times the group's matrix; the other
    rows of the tile keep what an earlier visit gave them, or are 0."""
    from jax.experimental import pallas as pl
    i = pl.program_id(0)
    active, empty = info_ref[0], info_ref[1]
    tm = out_ref.shape[0]

    @pl.when(i < active)
    def _product():
        g, t = group_ref[i], tile_ref[i]
        lo, hi = start_ref[g], end_ref[g]
        acc = _dot(lhs_ref[...], rhs_ref[...], contract)
        whole = (lo <= t * np.int32(tm)) & (hi >= (t + 1) * np.int32(tm))

        @pl.when(whole)
        def _all():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _some():
            fresh = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)
            before = jnp.where(fresh, jnp.float32(0.0),
                               out_ref[...].astype(jnp.float32))
            out_ref[...] = jnp.where(_inside(t, tm, lo, hi), acc,
                                     before).astype(out_ref.dtype)

    @pl.when((i >= active) & (i < active + empty))
    def _no_group():
        out_ref[...] = jnp.zeros_like(out_ref[...])


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _rows_call(lhs, rhs, sizes, *, transposed: bool, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (rows, k), tm, dtype = lhs.shape, _TILE, lhs.dtype
    held, n = rhs.shape[0], rhs.shape[1 if transposed else 2]
    scalars = _visits(sizes, rows, tm, every_group=False)
    if _stored_transposed(*rhs.shape[1:]):
        rhs, transposed = rhs.swapaxes(1, 2), not transposed

    def lhs_at(i, group, tile, starts, ends, info):
        return jnp.minimum(tile[i], info[2]), i * 0

    def rhs_at(i, group, tile, starts, ends, info):
        return group[i], i * 0, i * 0

    def out_at(i, group, tile, starts, ends, info):
        return tile[i], i * 0

    return pl.pallas_call(
        functools.partial(_rows_kernel,
                          contract=_ABT if transposed else _AB),
        out_shape=jax.ShapeDtypeStruct((rows, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(rows // tm + held - 1,),
            in_specs=[pl.BlockSpec((tm, k), lhs_at),
                      pl.BlockSpec((None,) + rhs.shape[1:], rhs_at)],
            out_specs=pl.BlockSpec((tm, n), out_at)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_limit(_rows_vmem(tm, k, n, dtype.itemsize))),
        name="grouped_dot_t" if transposed else "grouped_dot",
        interpret=interpret)(*scalars, lhs, rhs)


def dot(lhs, rhs, sizes, transposed: bool = False):
    """``[rows, N]``: ``lhs [rows, K]`` times each row's group's matrix
    of ``rhs [held, K, N]`` (``[held, N, K]``, contracted over its last
    axis, where ``transposed``); rows past the groups are 0."""
    _note_call("dlhs" if transposed else "fwd")
    return _rows_call(lhs, rhs, sizes, transposed=transposed,
                      interpret=pallas_ops._interpret())


# --------------------------------------------------------------------------
# the weights' gradient
# --------------------------------------------------------------------------
def _weights_kernel(group_ref, tile_ref, start_ref, end_ref, info_ref,
                    lhs_ref, d_out_ref, out_ref, acc_ref):
    """A group's ``lhs^T . d_out`` summed over the tiles its rows touch,
    in float32 scratch, written once when the group's last visit ends."""
    from jax.experimental import pallas as pl
    i, final = pl.program_id(0), pl.num_programs(0) - 1
    g = group_ref[i]
    lo, hi = start_ref[g], end_ref[g]
    tm = lhs_ref.shape[0]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    @pl.when((i < info_ref[0]) & (hi > lo))
    def _product():
        inside = _inside(tile_ref[i], tm, lo, hi)
        lhs, d_out = lhs_ref[...], d_out_ref[...]
        # the rows of other groups leave through the narrower operand
        if lhs.shape[1] <= d_out.shape[1]:
            lhs = jnp.where(inside, lhs, jnp.zeros_like(lhs))
        else:
            d_out = jnp.where(inside, d_out, jnp.zeros_like(d_out))
        acc_ref[...] += _dot(lhs, d_out, _ATB)

    @pl.when((i == final) | (group_ref[jnp.minimum(i + 1, final)] != g))
    def _end():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _weights_call(lhs, d_out, sizes, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if _stored_transposed(lhs.shape[1], d_out.shape[1]):
        return _weights_call(d_out, lhs, sizes,
                             interpret=interpret).swapaxes(1, 2)
    (rows, k), n, held = lhs.shape, d_out.shape[1], sizes.shape[0]
    tm, dtype = _TILE, lhs.dtype
    scalars = _visits(sizes, rows, tm, every_group=True)

    def rows_at(i, group, tile, starts, ends, info):
        return jnp.minimum(tile[i], info[2]), i * 0

    def out_at(i, group, tile, starts, ends, info):
        return group[i], i * 0, i * 0

    return pl.pallas_call(
        _weights_kernel,
        out_shape=jax.ShapeDtypeStruct((held, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(rows // tm + held - 1,),
            in_specs=[pl.BlockSpec((tm, k), rows_at),
                      pl.BlockSpec((tm, n), rows_at)],
            out_specs=pl.BlockSpec((None, k, n), out_at),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_limit(_weights_vmem(tm, k, n,
                                                  dtype.itemsize))),
        name="grouped_dot_weights",
        interpret=interpret)(*scalars, lhs, d_out)


def dot_weights(lhs, d_out, sizes):
    """``[held, K, N]``: for each group ``lhs_e^T . d_out_e`` over the
    group's rows of ``lhs [rows, K]`` and ``d_out [rows, N]``; the rows
    past the groups count for none."""
    _note_call("drhs")
    return _weights_call(lhs, d_out, sizes,
                         interpret=pallas_ops._interpret())
