"""The rows of a window of (token, choice) pairs added into their tokens,
as a Mosaic kernel.

The routed experts' results come back by the rows of a window of pairs
(``moe/grouped.py``): row r holds pair ``pair[r] = t * k + j``, token t's
j-th choice, weighed by ``gates[t, j]``, and a token may own up to k rows
or none.  One call gives

    out[t] = sum over r with pair[r] // k == t of gates[t, pair[r] % k] * rows[r]

``[tokens, d]``, summed in float32; a row whose pair lies outside ``0 ..
tokens * k - 1`` counts for none, whatever it holds.  Without ``gates``
every row counts once.

How: the rows are sorted by pair before the call (a sort of the window's
pair numbers and one gather of its rows), so the rows of a tile of
``_TOKENS`` tokens lie together.  A visit (one grid step) reads ``_ROWS``
of them, from the row tile at or before where it starts, and adds the
``_STEP`` rows it owns into the tile's float32 sum by one product on the
MXU: ``P^T rows``, ``P[r, t]`` the row's gate where the row is token t's
(read from the tile's block of the gates, transposed) and 0 elsewhere.
A tile with more rows than a visit takes several visits, one after the
other; a tile with none takes one, which writes its zeros.  The grid is
static (``tiles + rows / _STEP`` visits, the most a call can need); what
is left of it after the last tile does nothing.  The gates enter the
product as three bf16 parts, whose sum is the float32 gate, so the
product is the float32 one; without gates P is 0 or 1 and one part does.

Which shapes take the kernel is ``form``'s to say, from the rows and the
tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_ops
from .ssm_kernels import _ATB, _dot

_TOKENS = 128                # tokens a tile
_ROWS = 128                  # rows a visit fetches
# A fetch starts on a whole row tile (16 rows of bf16) at or before the
# first row the visit adds, so a visit adds the _STEP rows after that.
_ALIGN = 16
_STEP = _ROWS - _ALIGN
_ROOM = 32 << 20             # the most a call may hold in VMEM


def vmem_bytes(width: int, itemsize: int, k: int) -> int:
    """What a visit holds: the rows' block and their pairs (a lane group
    wide) twice each, the tile's gates twice, the result's block twice,
    its float32 sum, and P with its parts."""
    return (2 * _ROWS * (width * itemsize + 128 * 4)
            + 2 * max(k, 8) * _TOKENS * 4
            + 2 * _TOKENS * width * 4 + _TOKENS * width * 4
            + 4 * _ROWS * _TOKENS * 4)


def form(rows, tokens: int, k: int) -> str:
    """``"kernel"`` on a TPU (or under the interpreter) where ``tokens``
    is whole tiles, the rows are a floating dtype and a visit fits VMEM;
    ``"xla"`` everywhere else."""
    n, width = rows.shape
    fits = (tokens % _TOKENS == 0 and n > 0
            and jnp.issubdtype(rows.dtype, jnp.floating)
            and vmem_bytes(width, rows.dtype.itemsize, k) <= _ROOM)
    return "kernel" if fits and pallas_ops._kernels_enabled() else "xla"


def _visits(key, tokens: int, k: int, n: int):
    """The scalar-prefetch operands, all int32: the tile of each visit,
    the first sorted row it fetches (a whole number of row tiles) and the
    first it adds, and ``[visits that add rows, rows of a pair]``.
    ``key`` is the sorted pairs of the ``n`` rows with ``_ROWS`` more past
    them, a row of no pair keyed ``tokens * k``."""
    tiles = tokens // _TOKENS
    off = jnp.searchsorted(
        key, jnp.arange(tiles + 1, dtype=jnp.int32) * (_TOKENS * k),
        method="compare_all").astype(jnp.int32)
    chunks = jnp.maximum(1, -(-(off[1:] - off[:-1]) // _STEP))
    upto = jnp.cumsum(chunks, dtype=jnp.int32)
    v = jnp.arange(tiles + -(-n // _STEP), dtype=jnp.int32)
    active = upto[-1]
    tile = jnp.minimum((v[:, None] >= upto[None, :]).sum(
        1, dtype=jnp.int32), tiles - 1)
    at = jnp.minimum(v, active - 1)          # past the last: stay there
    lo = off[tile] + (at - (upto[tile] - chunks[tile])) * _STEP
    return tile, lo // _ALIGN * _ALIGN, lo, jnp.stack([active, off[-1]])


def _kernel(tile_ref, start_ref, lo_ref, info_ref, key_ref, *refs, k,
            parts):
    from jax.experimental import pallas as pl
    *gates_ref, rows_ref, out_ref, acc_ref = refs
    v, final = pl.program_id(0), pl.num_programs(0) - 1
    t, start, lo = tile_ref[v], start_ref[v], lo_ref[v]
    active, in_use = info_ref[0], info_ref[1]
    row = start + jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    @pl.when(v < active)
    def _add():
        key = key_ref[...]                                   # [rows, 1]
        first = (t * _TOKENS + jax.lax.broadcasted_iota(
            jnp.int32, (1, _TOKENS), 1)) * k                 # [1, tokens]
        live = (row >= lo) & (row < lo + _STEP)
        if gates_ref:
            p = jnp.zeros((_ROWS, _TOKENS), jnp.float32)
            for j in range(k):
                p = jnp.where(key == first + j, gates_ref[0][j:j + 1, :], p)
            p = jnp.where(live, p, 0.0)
        else:
            p = (live & (key >= first) & (key < first + k)).astype(
                jnp.float32)
        # past the rows in use the window may hold anything: zeros there,
        # so that they meet P's zeros as zeros
        rows = jax.lax.cond(
            start + _ROWS > in_use,
            lambda: jnp.where(row < in_use, rows_ref[...], 0),
            lambda: rows_ref[...])
        for _ in range(parts):
            part = p.astype(rows.dtype)
            acc_ref[...] += _dot(part, rows, _ATB)
            p = p - part.astype(jnp.float32)

    @pl.when((v == final) | (tile_ref[jnp.minimum(v + 1, final)] != t))
    def _end():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "tokens", "dtype",
                                             "interpret"))
def _call(rows, pair, gates, *, k: int, tokens: int, dtype,
          interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, width = rows.shape
    none = tokens * k
    key = jnp.where((pair >= 0) & (pair < none), pair, none).astype(
        jnp.int32)
    key, order = jax.lax.sort(
        (key, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    pad = functools.partial(jnp.pad, pad_width=(0, _ROWS))
    key, order = pad(key, constant_values=none), pad(order)
    scalars = _visits(key, tokens, k, n)

    def rows_at(v, tile, start, lo, info):
        return pl.multiple_of(start[v], _ALIGN), v * 0

    def tile_at(v, tile, start, lo, info):
        return tile[v], v * 0

    def block(cols):
        return pl.BlockSpec((pl.Element(_ROWS), pl.Element(cols)), rows_at)

    operands, specs = [key[:, None]], [block(1)]
    if gates is not None:
        operands.append(gates.astype(jnp.float32).T)        # [k, tokens]
        specs.append(pl.BlockSpec(
            (k, _TOKENS), lambda v, tile, start, lo, info: (v * 0, tile[v])))
    parts = 3 if gates is not None and rows.dtype != jnp.float32 else 1
    return pl.pallas_call(
        functools.partial(_kernel, k=k, parts=parts),
        out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(scalars[0].shape[0],),
            in_specs=specs + [block(width)],
            out_specs=pl.BlockSpec((_TOKENS, width), tile_at),
            scratch_shapes=[pltpu.VMEM((_TOKENS, width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(width, rows.dtype.itemsize, k)
            * 5 // 4 + (2 << 20)),
        name="token_rows_add",
        interpret=interpret)(*scalars, *operands, rows[order])


def add(rows, pair, k: int, tokens: int, gates=None, dtype=jnp.float32):
    """``[tokens, d]`` of ``dtype``: each of ``rows [n, d]``, times the
    gate of its pair in ``gates [tokens, k]`` (1 without), added into its
    token ``pair[r] // k``, in float32; rows of a pair outside ``0 ..
    tokens * k - 1`` count for none."""
    return _call(rows, pair, gates, k=k, tokens=tokens,
                 dtype=jnp.dtype(dtype), interpret=pallas_ops._interpret())
