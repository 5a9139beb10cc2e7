"""The chunked gated delta rule of ``ops/delta_rule.py`` as Mosaic kernels:
a head's state stays in VMEM from the first chunk to the last, and a
chunk's pairs, its triangular solve and its products never leave the
visit.

The arrays keep the layer's own layout: q, k and log alpha ``[S, H * K]``
float32, v and o ``[S, H * V]`` in v's dtype, beta ``[S, H]`` float32;
head h is the h-th column block.  Nothing is re-laid by chunk.

A visit (one grid step) is a chunk of C positions for all heads; the
chunks are the grid's one, sequential axis, and inside a visit a loop
walks the heads in turns of ``group_of`` heads stacked along the rows (two
at chunk 64: every pair matrix a block-diagonal ``[128, 128]``).  For a
head, with ``g`` the cumulative sum of log alpha inside the chunk (a
product with the lower triangle of ones):

    A_ts = beta_t sum_i k_ti k_si e^{g_ti - g_si}   (s < t)
    M_ts = sum_i q_ti k_si e^{g_ti - g_si}           (s <= t)
    T = (I + A)^-1,  [W | U~] = T [beta K e^g | beta V]
    U = U~ - W S0,  O = (Q e^g) S0 + M U
    S_C = Diag(e^{g_C}) S0 + (K e^{g_C - g})^T U

The pairs are taken as in the XLA form: between sub-chunks of 16 through
the later sub-chunk's first position, two products; inside a sub-chunk
pair by pair, the 16 columns of every sub-chunk at once (``_rows_of``),
every exponent masked to at most 0 before it is taken.
T is made by doubling blocks, ``T_2b = T_b - T_b E_b T_b`` with ``E_b``
the lower left quarters of the blocks of 2b: each product is a block of
the inverse itself, so nothing grows and cancels as powers of A would
(beta near 2 on like keys makes those powers huge).  The state is held
transposed, ``[V, K]`` a head in a scratch ``[V, H * K]`` float32, so that
every product takes it as it lies.

Differentiated, the forward call also writes the state each chunk starts
from (``[chunks * V, H * K]`` float32, 67 MB at the cell's shape), and
the backward pass is one call: a walk back, last chunk first, that makes
a chunk's pairs, T and U again from the inputs and that state, and
carries the state's gradient in scratch.  dlog alpha is the sum of dg
over the chunk's later positions (a product with the upper triangle),
the state's decay to the chunk's end added at its last position.

Every product, sum, pair and state is float32 with its precision stated
(``HIGHEST``), as the XLA form's are.  Which shapes take these kernels is
``delta_rule.rule_form``'s to say.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import pallas_ops

_LANES = 128
_ROOM = 32 << 20             # VMEM a call may use
_HIGHEST = lax.Precision.HIGHEST
_AB, _ABT, _ATB = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# the kernels' bodies are written in ``jax.lax``'s own operations: every
# ``jax.numpy`` function and every ``*`` or ``+`` of two traced values is
# a jitted call of its own, traced and lowered on its own (2 053 of them
# in the two calls of one differentiated rule, most of its 7.6 s of
# tracing under the interpreter: PERF.md section 6).  Operands of
# two shapes are laid against each other with ``_to``.
# --------------------------------------------------------------------------
_F32 = jnp.float32


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _to(x, shape):
    """``x`` laid against ``shape``: its dimensions of 1 repeated."""
    if x.shape == tuple(shape):
        return x
    return lax.broadcast_in_dim(x, tuple(shape), tuple(range(len(shape))))


def _both(fn, a, b):
    shape = tuple(max(x, y) for x, y in zip(a.shape, b.shape))
    return fn(_to(a, shape), _to(b, shape))


def _mul(a, b):
    return _both(lax.mul, a, b)


def _sub(a, b):
    return _both(lax.sub, a, b)


def _full(shape, value):
    return lax.full(tuple(shape), np.float32(value), _F32)


def _where(cond, x, other: float = 0.0):
    """``x`` (an array or a number) where ``cond``, ``other`` elsewhere, at
    ``cond``'s shape."""
    x = _full(cond.shape, x) if isinstance(x, float) else _to(x, cond.shape)
    return lax.select(cond, x, _full(cond.shape, other))


def _sum(x, axis: int):
    """The sums along ``axis``, kept as a dimension of 1."""
    return lax.expand_dims(lax.reduce(x, np.float32(0.0), lax.add, (axis,)),
                           (axis,))


def _rows(x, start: int, stop: int):
    return lax.slice_in_dim(x, start, stop, axis=0)


def _lanes(x, start: int, stop: int):
    return lax.slice_in_dim(x, start, stop, axis=1)


def _cat(parts, axis: int):
    return parts[0] if len(parts) == 1 else lax.concatenate(parts, axis)


def _and(a, b):
    return lax.bitwise_and(a, b)


def _shr(x, n: int):
    return lax.shift_right_logical(x, np.int32(n))


def fits_vmem(heads: int, dim: int, values: int, chunk: int) -> bool:
    """Whether the walk back's blocks, twice each, its scratch and a turn's
    own values fit three quarters of ``_ROOM``: q, k, log alpha and their
    gradients ``[C, H * K]``, v, do and dv ``[C, H * V]`` at 4 bytes (the
    most), beta's and its gradient's ``[C, 128]``, the state's block and
    its gradient ``[V, H * K]``; a turn's R stacked rows ``[R, 3K]`` of
    scratch, some 40 ``[R, K + V]`` and 16 ``[R, R]``.  The cell's shape
    holds 12.9 MB."""
    rows = group_of(heads, chunk) * chunk
    blocks = 4 * chunk * (6 * heads * dim + 3 * heads * values + 2 * _LANES)
    blocks += 4 * values * heads * dim
    own = 4 * values * heads * dim + 4 * rows * 3 * dim \
        + 4 * (40 * rows * (dim + values) + 16 * rows * rows)
    return 2 * blocks + own <= _ROOM * 3 // 4


def _note_visits(kind: str, visits: int) -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "delta_rule_kernel_visits_total",
        "visits of the gated delta rule's Mosaic kernels, counted a call "
        "when the call is traced: a chunk of one head; 0 where the XLA "
        "form ran", labels={"kind": kind}).inc(visits)


def _each(n: int, fn, carry):
    """``carry = fn(i, carry)`` for ``i`` in ``range(n)`` as a loop inside
    the kernel, its counter an int32 (``fori_loop`` counts in int64 under
    the package's x64)."""
    def step(state, _):
        i, inner = state
        return (i + np.int32(1), fn(i, inner)), None

    return lax.scan(step, (np.int32(0), carry), None, length=n)[0][1]


def _unrolled(n: int, fn, carry):
    """``carry = fn(i, carry)`` for ``i`` in ``range(n)``, written out: the
    rows a turn reads are then static, and a load of one row repeats it
    down the sublanes with no vector operation spent.  As a loop inside
    the kernel, whose rows are dynamic, the forward call took 5.11 ms at
    the cell's shape, written out 3.16 on a v5e (PERF.md section 6)."""
    for i in range(n):
        carry = fn(i, carry)
    return carry


# --------------------------------------------------------------------------
# what both kernels make of a turn's chunk
# --------------------------------------------------------------------------
def group_of(heads: int, chunk: int) -> int:
    """Heads a loop turn stacks along the rows: as many as make 128 rows,
    so that a pair matrix fills its registers' lanes and a product of two
    is one full pass of the MXU, where a head's own ``[64, 64]`` leaves
    half of each idle (a ``[128, 128]`` inverse cost 2.05 ms a call where
    a ``[64, 64]`` one cost 1.5 on a v5e: PERF.md section 6); a divisor of
    the heads."""
    return math.gcd(heads, max(1, _LANES // chunk))


def _rows_of(rows_scr, j: int, sub: int):
    """``[R, W]`` whose row t holds ``rows_scr``'s row ``sub * (t // sub)
    + j``: row j of every sub-chunk, laid down that sub-chunk's rows."""
    rows, width = rows_scr.shape
    return _cat([lax.broadcast_in_dim(rows_scr[m * sub + j:m * sub + j + 1,
                                               :], (sub, width), (0, 1))
                 for m in range(rows // sub)], 0)


class _Chunk:
    """A turn's chunk: the heads of a turn stacked along the rows, ``R =
    heads * C``, their inputs in float32, ``g``, the decays and the index
    planes both kernels use.  Every ``[R, R]`` matrix is block-diagonal, a
    block a head.  ``rows_scr [R, 3K]`` gets ``g | k | q``, for
    ``_rows_of``."""

    def __init__(self, q, k, la, beta, rows_scr, chunk: int, sub: int):
        rows, dim = k.shape
        self.q, self.k, self.beta = q, k, beta
        self.chunk, self.sub = chunk, sub
        c = np.int32(chunk)
        self.t, self.s = t, s = _iota((rows, rows), 0), _iota((rows, rows), 1)
        self.same = lax.eq(lax.div(t, c), lax.div(s, c))
        self.lower = _where(_and(lax.le(s, t), self.same), 1.0)
        self.g = g = _dot(self.lower, la, _AB)
        rows_scr[:, 0:dim] = g
        rows_scr[:, dim:2 * dim] = k
        rows_scr[:, 2 * dim:3 * dim] = q
        self.rows_scr = rows_scr
        self.row = _iota((rows, dim), 0)
        self.rem = lax.rem(self.row, np.int32(sub))
        # the first column of each row's sub-chunk
        self.base = lax.sub(t, lax.rem(t, np.int32(sub)))
        self.eg = lax.exp(g)
        ends = [rows_scr[e - 1:e, 0:dim]
                for e in range(chunk, rows + 1, chunk)]
        self.e_end = [lax.exp(e) for e in ends]       # [1, K] a head
        self.to_end = lax.exp(lax.sub(_cat(          # e^{g_C - g} <= 1
            [_to(e, (chunk, dim)) for e in ends], 0), g))

    def head(self, x, p: int):
        """Head p's rows of ``x [R, ...]``."""
        return _rows(x, p * self.chunk, (p + 1) * self.chunk)

    def by_head(self, fn, *xs):
        """``fn`` of each head's rows of ``xs`` (and its index), stacked
        back along the rows."""
        heads = self.k.shape[0] // self.chunk
        return _cat([fn(p, *(self.head(x, p) for x in xs))
                     for p in range(heads)], 0)

    def across(self, m: int):
        """Sub-chunk m's reference row ``r``: its rows' decays ``e^{g_t -
        g_r}`` ``[sub, K]`` and the columns' ``e^{g_r - g_s}`` ``[R, K]``,
        0 but at the earlier positions of r's head; None at a head's first
        sub-chunk."""
        r, sub, g = m * self.sub, self.sub, self.g
        if r % self.chunk == 0:
            return None
        ref = _rows(g, r, r + 1)
        decay = lax.exp(_sub(_rows(g, r, r + sub), ref))
        earlier = _and(lax.lt(self.row, np.int32(r)),
                       lax.ge(self.row, np.int32(r - r % self.chunk)))
        cols = lax.exp(_where(earlier, _sub(ref, g), -np.inf))
        return r, decay, cols

    def within(self, j: int, later: bool):
        """``g``, k and q of position ``sub * (t // sub) + j`` down row t,
        and the decays between that position and row t's, where row t is
        the later of the two (``later``: from j to t; else from t to j),
        0 elsewhere."""
        dim = self.k.shape[1]
        rows = _rows_of(self.rows_scr, j, self.sub)
        g_j = _lanes(rows, 0, dim)
        at = np.int32(j)
        if later:
            gap = _where(lax.ge(self.rem, at), lax.sub(self.g, g_j), -np.inf)
        else:
            gap = _where(lax.le(self.rem, at), lax.sub(g_j, self.g), -np.inf)
        return (lax.exp(gap), _lanes(rows, dim, 2 * dim),
                _lanes(rows, 2 * dim, 3 * dim))

    def at_column(self, j: int):
        """Where column ``sub * (t // sub) + j`` of row t lies."""
        return lax.eq(self.s, lax.add(self.base, np.int32(j)))

    def column(self, pairs, j: int):
        """Of ``pairs [R, R]`` the entry of row t at column ``sub * (t //
        sub) + j``, ``[R, 1]``."""
        return _sum(_where(self.at_column(j), pairs), 1)

    def pairs(self):
        """(M, A without beta): ``[R, R]`` each."""
        rows = self.k.shape[0]
        q, k, sub = self.q, self.k, self.sub
        zeros = _full((sub, rows), 0.0)
        m_rows, a_rows = [], []
        for m in range(rows // sub):
            at = self.across(m)
            if at is None:
                m_rows.append(zeros)
                a_rows.append(zeros)
                continue
            r, decay, cols = at
            both = _dot(_cat([lax.mul(_rows(q, r, r + sub), decay),
                              lax.mul(_rows(k, r, r + sub), decay)], 0),
                        lax.mul(k, cols), _ABT)
            m_rows.append(_rows(both, 0, sub))
            a_rows.append(_rows(both, sub, 2 * sub))

        def column(j, acc):
            m_in, a_in = acc
            decay, k_j, _ = self.within(j, later=True)
            kd = lax.mul(k_j, decay)
            here = self.at_column(j)
            return (lax.add(m_in, _where(here, _sum(lax.mul(q, kd), 1))),
                    lax.add(a_in, _where(here, _sum(lax.mul(k, kd), 1))))

        square = _full((rows, rows), 0.0)
        m_in, a_in = _unrolled(sub, column, (square, square))
        a = lax.add(_cat(a_rows, 0), a_in)
        return (lax.add(_cat(m_rows, 0), m_in),
                _where(lax.lt(self.s, self.t), a))

    def inverse(self, a):
        """``(I + a)^-1`` of a strictly lower, block-diagonal ``a [R, R]``,
        by doubling blocks up to a head's."""
        t, s = self.t, self.s
        inv = lax.sub(_where(lax.eq(t, s), 1.0),
                      _where(lax.eq(_shr(t, 1), _shr(s, 1)), a))
        b, shift = 2, 1
        while b < self.chunk:
            quarter = _and(lax.eq(_shr(t, shift + 1), _shr(s, shift + 1)),
                           lax.ne(_shr(t, shift), _shr(s, shift)))
            inv = lax.sub(inv, _dot(_dot(inv, _where(quarter, a), _AB), inv,
                                    _AB))
            b, shift = 2 * b, shift + 1
        return inv

    def solved(self, v):
        """``(M, A without beta, T, [W | U~], K e^g)``."""
        m_pairs, a_raw = self.pairs()
        inv = self.inverse(_mul(self.beta, a_raw))
        k_grown = lax.mul(self.k, self.eg)
        x = _dot(inv, _cat([_mul(self.beta, k_grown), _mul(self.beta, v)],
                           1), _AB)
        return m_pairs, a_raw, inv, x, k_grown


def _beta_column(b_ref, h):
    tile = b_ref[...]
    return _sum(_where(lax.eq(_iota(tile.shape, 1), h), tile), 1)


def _head_lanes(h, width: int):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(h * np.int32(width), width), width)


def _turn_heads(i, group: int, dim: int, values: int):
    """Turn i's heads, and their lanes of ``[C, H * K]`` and of ``[C, H *
    V]``."""
    heads = [i * np.int32(group) + np.int32(p) for p in range(group)]
    return (heads, [_head_lanes(h, dim) for h in heads],
            [_head_lanes(h, values) for h in heads])


def _stacked(ref, lanes):
    return _cat([lax.convert_element_type(ref[:, at], _F32) for at in lanes],
                0)


def _turn_chunk(q_ref, k_ref, la_ref, b_ref, rows_scr, heads, keys,
                sub: int):
    return _Chunk(_stacked(q_ref, keys), _stacked(k_ref, keys),
                  _stacked(la_ref, keys),
                  _cat([_beta_column(b_ref, h) for h in heads], 0),
                  rows_scr, q_ref.shape[0], sub)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, la_ref, b_ref, o_ref, *rest, sub: int,
                states: bool):
    """A chunk of o for every head, a turn of heads at a time, and the
    state the next chunk starts from; with ``states``, the state this
    chunk started from goes out too."""
    from jax.experimental import pallas as pl
    st_ref = rest[0] if states else None
    s_scr, rows_scr = rest[-2:]
    heads, chunk = b_ref.shape[1], q_ref.shape[0]
    dim, values = k_ref.shape[1] // heads, v_ref.shape[1] // heads
    group = group_of(heads, chunk)

    @pl.when(pl.program_id(0) == 0)
    def _start():
        s_scr[...] = _full(s_scr.shape, 0.0)

    if states:
        st_ref[...] = s_scr[...]

    def turn(i, _):
        hs, keys, vals = _turn_heads(i, group, dim, values)
        c = _turn_chunk(q_ref, k_ref, la_ref, b_ref, rows_scr, hs, keys, sub)
        s0 = [s_scr[:, at] for at in keys]                    # [V, K]
        m_pairs, _, _, x, _ = c.solved(_stacked(v_ref, vals))
        q_grown = lax.mul(c.q, c.eg)
        # W S0 and (Q e^g) S0, a head's in one product
        w_q = [_dot(_cat([c.head(_lanes(x, 0, dim), p), c.head(q_grown, p)],
                         0), s0[p], _ABT) for p in range(group)]
        u = lax.sub(_lanes(x, dim, dim + values),
                    _cat([_rows(a, 0, chunk) for a in w_q], 0))
        o = lax.add(_cat([_rows(a, chunk, 2 * chunk) for a in w_q], 0),
                    _dot(m_pairs, u, _AB))
        k_to_end = lax.mul(c.k, c.to_end)
        for p in range(group):
            o_ref[:, vals[p]] = lax.convert_element_type(c.head(o, p),
                                                         o_ref.dtype)
            s_scr[:, keys[p]] = lax.add(
                _mul(s0[p], c.e_end[p]),
                _dot(c.head(u, p), c.head(k_to_end, p), _ATB))
        return None

    _each(heads // group, turn, None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _pairs_grad(c: _Chunk, dm, da, dm_t, da_t):
    """From the gradients of M and of A without beta (and both
    transposed): (through M's rows, q's; through A's rows, k's; through
    both's columns, k's), each ``[R, K]``."""
    rows, dim = c.k.shape
    q, k, sub = c.q, c.k, c.sub
    zeros = _full((sub, dim), 0.0)
    dq_rows, dk_rows = [], []
    d_cols = _full((rows, dim), 0.0)
    for m in range(rows // sub):
        at = c.across(m)
        if at is None:
            dq_rows.append(zeros)
            dk_rows.append(zeros)
            continue
        r, decay, cols = at
        d_pairs = _cat([_rows(dm, r, r + sub), _rows(da, r, r + sub)], 0)
        got = _dot(d_pairs, lax.mul(k, cols), _AB)           # [2 sub, K]
        dq_rows.append(lax.mul(_rows(got, 0, sub), decay))
        dk_rows.append(lax.mul(_rows(got, sub, 2 * sub), decay))
        d_cols = lax.add(d_cols, lax.mul(cols, _dot(
            d_pairs, _cat([lax.mul(_rows(q, r, r + sub), decay),
                           lax.mul(_rows(k, r, r + sub), decay)], 0),
            _ATB)))

    def column(j, acc):
        dq, dk = acc
        decay, k_j, _ = c.within(j, later=True)
        kd = lax.mul(k_j, decay)
        return (lax.add(dq, _mul(c.column(dm, j), kd)),
                lax.add(dk, _mul(c.column(da, j), kd)))

    def row(i, d):
        decay, k_i, q_i = c.within(i, later=False)
        return lax.add(d, lax.mul(lax.add(_mul(c.column(dm_t, i), q_i),
                                          _mul(c.column(da_t, i), k_i)),
                                  decay))

    dq, dk = _unrolled(sub, column, (_cat(dq_rows, 0), _cat(dk_rows, 0)))
    return dq, dk, _unrolled(sub, row, d_cols)


def _bwd_kernel(q_ref, k_ref, v_ref, la_ref, b_ref, st_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dla_ref, db_ref, ds_scr, rows_scr, *,
                sub: int):
    """A chunk's gradients, last chunk first, a turn of heads at a time,
    and the gradient of the state the chunk started from, carried to the
    chunk before."""
    from jax.experimental import pallas as pl
    heads, chunk = b_ref.shape[1], q_ref.shape[0]
    dim, values = k_ref.shape[1] // heads, v_ref.shape[1] // heads
    group = group_of(heads, chunk)

    @pl.when(pl.program_id(0) == 0)
    def _start():
        ds_scr[...] = _full(ds_scr.shape, 0.0)

    def turn(i, _):
        hs, keys, vals = _turn_heads(i, group, dim, values)
        c = _turn_chunk(q_ref, k_ref, la_ref, b_ref, rows_scr, hs, keys, sub)
        beta, v = c.beta, _stacked(v_ref, vals)
        s0 = [st_ref[:, at] for at in keys]                   # [V, K]
        ds = [ds_scr[:, at] for at in keys]
        do = _stacked(do_ref, vals)
        m_pairs, a_raw, inv, x, k_grown = c.solved(v)
        w, u_tilde = _lanes(x, 0, dim), _lanes(x, dim, dim + values)
        u = lax.sub(u_tilde, c.by_head(
            lambda p, w_: _dot(w_, s0[p], _ABT), w))
        q_grown = lax.mul(c.q, c.eg)
        k_to_end = lax.mul(c.k, c.to_end)
        t, s, same = c.t, c.s, c.same

        du = lax.add(_dot(m_pairs, do, _ATB), c.by_head(
            lambda p, ke: _dot(ke, ds[p], _ABT), k_to_end))
        dm = _where(_and(lax.le(s, t), same), _dot(do, u, _ABT))
        dk_to_end = c.by_head(lambda p, u_: _dot(u_, ds[p], _AB), u)
        # dO S0 and dU S0, a head's in one product
        by_s0 = [_dot(_cat([c.head(do, p), c.head(du, p)], 0), s0[p], _AB)
                 for p in range(group)]
        dq_grown = _cat([_rows(a, 0, chunk) for a in by_s0], 0)
        dx = _cat([lax.neg(_cat([_rows(a, chunk, 2 * chunk) for a in by_s0],
                                0)), du], 1)
        dr = _dot(inv, dx, _ATB)                     # [d(beta K e^g) | ...]
        dkb, dvb = _lanes(dr, 0, dim), _lanes(dr, dim, dim + values)
        da = lax.neg(_where(_and(lax.lt(s, t), same), _dot(dr, x, _ABT)))
        da_raw = _mul(beta, da)
        dq_p, dk_p, dk_cols = _pairs_grad(c, dm, da_raw,
                                          lax.transpose(dm, (1, 0)),
                                          lax.transpose(da_raw, (1, 0)))
        beta_dkb = _mul(beta, dkb)
        dg = lax.add(lax.add(lax.mul(q_grown, dq_grown), lax.mul(c.q, dq_p)),
                     lax.sub(lax.add(lax.mul(c.k, lax.sub(dk_p, dk_cols)),
                                     lax.mul(k_grown, beta_dkb)),
                             lax.mul(k_to_end, dk_to_end)))
        dbeta = lax.add(lax.add(_sum(lax.mul(da, a_raw), 1),
                                _sum(lax.mul(dkb, k_grown), 1)),
                        _sum(lax.mul(dvb, v), 1))
        dq = lax.add(lax.mul(c.eg, dq_grown), dq_p)
        dk = lax.add(lax.add(lax.add(dk_p, dk_cols), lax.mul(c.eg, beta_dkb)),
                     lax.mul(c.to_end, dk_to_end))
        dv = _mul(beta, dvb)
        ends = []
        for p in range(group):
            # dO^T (Q e^g) - dU^T W, one product
            ds_scr[:, keys[p]] = lax.add(_mul(ds[p], c.e_end[p]), _dot(
                _cat([c.head(do, p), lax.neg(c.head(du, p))], 0),
                _cat([c.head(q_grown, p), c.head(w, p)], 0), _ATB))
            kept = _sum(lax.mul(s0[p], ds[p]), 0)             # [1, K]
            ends.append(lax.add(
                _sum(c.head(lax.mul(k_to_end, dk_to_end), p), 0),
                lax.mul(c.e_end[p], kept)))
        last = lax.eq(lax.rem(c.row, np.int32(chunk)), np.int32(chunk - 1))
        dg = lax.add(dg, _where(last, _cat(
            [_to(e, (chunk, dim)) for e in ends], 0)))
        dla = _dot(c.lower, dg, _ATB)
        lane = _iota(db_ref.shape, 1)
        for p in range(group):
            for ref, value, at in ((dq_ref, dq, keys[p]), (dk_ref, dk, keys[p]),
                                   (dv_ref, dv, vals[p]),
                                   (dla_ref, dla, keys[p])):
                ref[:, at] = lax.convert_element_type(c.head(value, p),
                                                      ref.dtype)
            db_ref[...] = lax.select(lax.eq(lane, hs[p]),
                                     _to(c.head(dbeta, p), db_ref.shape),
                                     db_ref[...])
        return None

    _each(heads // group, turn, None)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------
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


def _blocks(chunks: int, back: bool):
    """A chunk's block of ``height`` rows of an array ``[chunks * height,
    width]``, the last chunk first where ``back``."""
    from jax.experimental import pallas as pl
    n = np.int32(chunks)
    at = (lambda c: n - 1 - c) if back else (lambda c: c)
    return lambda height, width: pl.BlockSpec((height, width),
                                              lambda c: (at(c), c * 0))


def _flat(q, k, v, log_alpha):
    seq, heads = k.shape[:2]
    return (q.reshape(seq, -1), k.reshape(seq, -1), v.reshape(seq, -1),
            log_alpha.reshape(seq, -1))


# The two calls are jitted on their own: the layers, the forward pass run
# again and the reference check then share one trace of a kernel and one
# lowering of it a program (as ``ssm_kernels``' calls).  Their names
# are the kernels' names in the compiled step.
@functools.partial(jax.jit,
                   static_argnames=("chunk", "sub", "states", "interpret"))
def _delta_fwd(q, k, v, log_alpha, beta, *, chunk: int, sub: int,
               states: bool, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    (seq, heads, dim), values = k.shape, v.shape[-1]
    chunks, f32 = seq // chunk, jnp.float32
    rows = _blocks(chunks, back=False)
    flat = _flat(q, k, v, log_alpha)
    out = _call(
        functools.partial(_fwd_kernel, sub=sub, states=states), chunks,
        interpret,
        [rows(chunk, heads * dim)] * 2 + [rows(chunk, heads * values),
                                          rows(chunk, heads * dim),
                                          rows(chunk, heads)],
        [rows(chunk, heads * values)] + [rows(values, heads * dim)] * states,
        [jax.ShapeDtypeStruct((seq, heads * values), v.dtype)]
        + [jax.ShapeDtypeStruct((chunks * values, heads * dim), f32)]
        * states,
        [pltpu.VMEM((values, heads * dim), f32),
         pltpu.VMEM((group_of(heads, chunk) * chunk, 3 * dim), f32)],
        *flat, beta)
    return (out[0].reshape(v.shape),) + tuple(out[1:])


def _forward(q, k, v, log_alpha, beta, chunk: int, sub: int, states: bool):
    """o ``[S, H, V]`` in v's dtype and, with ``states``, the states the
    chunks start from, ``[chunks * V, H * K]`` float32 (transposed, a
    head's ``[V, K]`` in its column block)."""
    seq, heads = k.shape[:2]
    _note_visits("fwd", seq // chunk * heads)
    return _delta_fwd(q, k, v, log_alpha, beta, chunk=chunk, sub=sub,
                      states=states, interpret=pallas_ops._interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def rule(q, k, v, log_alpha, beta, chunk: int, sub: int):
    """``delta_rule.gated_delta_rule`` through the kernels, for the shapes
    ``delta_rule.rule_form`` gives them."""
    return _forward(q, k, v, log_alpha, beta, chunk, sub, False)[0]


def _rule_fwd(q, k, v, log_alpha, beta, chunk, sub):
    # the backward pass is given the inputs and the states the chunks
    # start from (a chunk's pairs and solve it makes again)
    o, starts = _forward(q, k, v, log_alpha, beta, chunk, sub, True)
    return o, (q, k, v, log_alpha, beta, starts)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def _delta_bwd(q, k, v, log_alpha, beta, starts, do, *, chunk: int,
               sub: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    (seq, heads, dim), values = k.shape, v.shape[-1]
    chunks, f32 = seq // chunk, jnp.float32
    rows = _blocks(chunks, back=True)
    flat = _flat(q, k, v, log_alpha)
    by_key, by_value = rows(chunk, heads * dim), rows(chunk, heads * values)
    dq, dk, dv, dla, dbeta = _call(
        functools.partial(_bwd_kernel, sub=sub), chunks, interpret,
        [by_key, by_key, by_value, by_key, rows(chunk, heads),
         rows(values, heads * dim), by_value],
        [by_key, by_key, by_value, by_key, rows(chunk, heads)],
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]
        + [jax.ShapeDtypeStruct(beta.shape, f32)],
        [pltpu.VMEM((values, heads * dim), f32),
         pltpu.VMEM((group_of(heads, chunk) * chunk, 3 * dim), f32)],
        *flat, beta, starts, do.reshape(seq, -1).astype(v.dtype))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dla.reshape(log_alpha.shape), dbeta.astype(beta.dtype))


def _rule_bwd(chunk, sub, kept, do):
    seq, heads = kept[1].shape[:2]
    _note_visits("bwd", seq // chunk * heads)
    return _delta_bwd(*kept, do, chunk=chunk, sub=sub,
                      interpret=pallas_ops._interpret())


rule.defvjp(_rule_fwd, _rule_bwd)
