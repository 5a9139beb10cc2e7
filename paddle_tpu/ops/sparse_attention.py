"""Learned sparse attention of the DeepSeek-Sparse-Attention kind: a small
indexer scores every causal key for every query, the ``topk`` best are
kept, and attention runs over the kept keys only.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) / sqrt(DI * J)
    S_t     = the min(t + 1, topk) causal s with the largest I[t, s];
              a tie at the border goes to the later position
    o[t, i] = softmax over S_t of (q[t, i] . k[s, i // R] / sqrt(D)) v

Everything here is a pure function of one sequence's arrays (the model
loops over the batch), laid out as the projections leave them: q ``[S,
H, D]``, k and v ``[S, G, D]``, the indexer's qI ``[S, J, DI]``, kI ``[S,
DI]`` and w ``[S, J]``; the kernels read a head as a lane block of ``[S,
H * D]``, so nothing is transposed.  Nothing ``[S, S]`` of more than one
head's width is ever alive, nor the indexer's products by head at all:
the index scores are one float32 ``[S, S]`` (or, in the plain form,
``[chunk, E]`` of a query chunk against its causal extent), the
selection one ``[S, S]`` int8 mask.

The selection is exact.  Only the ``topk``-th largest score of a row is
needed, so no row is sorted: the scores are mapped to integers of the
same order and the threshold is found bit by bit (32 counts a row); a
tie at the threshold is broken by a second search over positions, which
runs only where a row has one.  It runs in query chunks of ``chunk``
rows against the chunk's causal extent.

The core is dense attention under that mask.  On a TPU it is four Mosaic
kernels (forward, dq, dkv, and the head-averaged probabilities the
indexer learns from) whose visit is a key head's group: one key, value
and selection tile, fetched and decoded once, against the query heads
that read that key head.  The index scores are two more (``I`` itself,
for the selection and again for the indexer's loss, and its three
gradients in one walk): a head's ``[512, 512]`` products live in VMEM
only, are passed through ReLU, weighted and added to the tile's sum over
the heads there.  No kernel computes a tile above the diagonal.
Elsewhere, and for shapes that fill no lane group or block
(:func:`kernels_eligible`, :func:`scores_eligible`), plain
``jax.numpy``, which is also what the kernels are checked against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_ops
from .pallas_ops import _LSE_FLOOR, _across, _lane_sums

_LANES = 128
_NEG = -1e30
BLOCK = 512                  # the kernels' query and key block
GROUP = 8                    # the most query heads one visit holds
_ROOM = 16 << 20             # VMEM for a visit's [BLOCK, BLOCK] values
# Heads of a visit that one iteration of a kernel's loop holds.  All
# eight unrolled is 8 % faster a kernel (nothing of one head waits for
# the last) and 4.95 MB of executable for five layers where two a turn
# is 0.65: each of a run's two executables then loads 6.3 s slower, 17 %
# of the Keye cell's `setup_s` (PERF.md section 6, PR 29).
_TURN = 2


# --------------------------------------------------------------------------
# indexer scores and the exact selection
# --------------------------------------------------------------------------
def chunk_scores(q_idx, k_idx, w_idx):
    """``I`` of a chunk: q_idx ``[C, J, DI]``, k_idx ``[E, DI]``, w_idx
    ``[C, J]`` -> float32 ``[C, E]``."""
    j, di = q_idx.shape[1], q_idx.shape[2]
    pre = jnp.einsum("cjd,ed->cje", q_idx, k_idx,
                     preferred_element_type=jnp.float32)
    w = w_idx.astype(jnp.float32)[:, :, None]
    return (jax.nn.relu(pre) * w).sum(1) * (1.0 / math.sqrt(di * j))


def _chunks(seq: int, chunk: int):
    """(first row, rows, causal extent) of every query chunk."""
    chunk = min(chunk, seq)
    if seq % chunk:
        raise ValueError(f"sequence {seq} is no multiple of the query "
                         f"chunk {chunk}")
    return [(r0, chunk, r0 + chunk) for r0 in range(0, seq, chunk)]


def _ordered(scores):
    """float32 -> uint32 of the same order (-0.0 counted as 0.0)."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def _kth_largest(values, k, bits: int):
    """Per row of uint32 ``values`` ``[C, E]``: the largest ``tau`` with at
    least ``k`` (an int32 a row) entries ``>= tau``, found from the top
    bit down: ``bits`` counts a row."""
    def step(i, tau):
        cand = tau | (jnp.uint32(1) << (jnp.uint32(bits - 1)
                                        - i.astype(jnp.uint32)))
        count = (values >= cand[:, None]).sum(-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, tau)

    return jax.lax.fori_loop(
        0, bits, step, jnp.zeros(values.shape[0], jnp.uint32))


def select_chunk(scores, r0: int, topk: int):
    """The kept keys of the query rows ``r0 .. r0 + C`` as a bool mask
    ``[C, E]`` over the keys ``0 .. E`` (``E = r0 + C``)."""
    c, e = scores.shape
    rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (c, e), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, e), 1)
    causal = cols <= rows
    if e <= topk:                      # every causal key is kept
        return causal
    # a causal score maps to 1 or more, so 0 stands for "not causal"
    u = jnp.where(causal, _ordered(scores), jnp.uint32(0))
    k = jnp.minimum(rows[:, 0] + 1, topk)
    tau = _kth_largest(u, k, 32)[:, None]
    above, at = u > tau, u == tau
    need = k - above.sum(-1, dtype=jnp.int32)               # >= 1
    tied = at.sum(-1, dtype=jnp.int32) != need

    def later_positions(_):
        # of the keys at the threshold, the `need` latest: the largest
        # position p with at least `need` of them at or after it
        pos = jnp.where(at, cols.astype(jnp.uint32) + 1, jnp.uint32(0))
        p = _kth_largest(pos, need, int(e).bit_length())
        return above | (pos >= p[:, None])

    return jax.lax.cond(tied.any(), later_positions,
                        lambda _: above | at, None)


def _in_turn(later, earlier):
    """Ties what a later chunk reads to an earlier chunk's result, so
    that the chunks run one after the other and one chunk's
    intermediates are all that is alive."""
    return jax.lax.optimization_barrier((later, earlier))


def select(q_idx, k_idx, w_idx, topk: int, chunk: int, with_scores=False):
    """The selection of one sequence as an int8 mask ``[S, S]`` (1 where
    key s is in S_t) and, where asked, the index scores ``[S, S]``
    float32 (0 above the diagonal)."""
    seq = q_idx.shape[0]
    masks, all_scores = [], []
    whole = None
    if scores_eligible(*q_idx.shape):
        with jax.named_scope("indexer"):
            whole = _scores_kernels(q_idx, k_idx, w_idx)
    for r0, c, e in _chunks(seq, chunk):
        with jax.named_scope("indexer"):
            scores = whole[r0:r0 + c, :e] if whole is not None else \
                chunk_scores(q_idx[r0:r0 + c], k_idx[:e], w_idx[r0:r0 + c])
        with jax.named_scope("select"):
            keep = select_chunk(scores, r0, topk)
            masks.append(jnp.pad(keep.astype(jnp.int8),
                                 ((0, 0), (0, seq - e))))
            # one chunk after the other: left to itself the compiler
            # computes every chunk's plain [C, J, E] scores first and
            # holds them
            q_idx, masks[-1] = _in_turn(q_idx, masks[-1])
        if with_scores:
            causal = jnp.arange(e)[None, :] <= r0 + jnp.arange(c)[:, None]
            all_scores.append(jnp.pad(jnp.where(causal, scores, 0.0),
                                      ((0, 0), (0, seq - e))))
    mask = jnp.concatenate(masks, 0)
    return (mask, jnp.concatenate(all_scores, 0)) if with_scores else mask


# --------------------------------------------------------------------------
# the core: plain form
# --------------------------------------------------------------------------
def _plain_scores(q, k, mask):
    """``[G, R, S, S]`` float32 masked scores of q ``[S, H, D]`` against
    k ``[S, G, D]``."""
    seq, heads, d = q.shape
    groups = k.shape[1]
    s = jnp.einsum("sgrd,tgd->grst",
                   q.reshape(seq, groups, heads // groups, d), k,
                   preferred_element_type=jnp.float32)
    return jnp.where(mask != 0, s / math.sqrt(d), _NEG)


def core_plain(q, k, v, mask):
    """(out ``[S, H, D]``, lse ``[H, S]``) by explicit scores; any
    dtype, differentiable by jax."""
    s = _plain_scores(q, k, mask)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("grst,tgd->sgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out.reshape(q.shape).astype(q.dtype),
            lse.reshape(q.shape[1], q.shape[0]))


def mean_head_probs_plain(q, k, lse, mask):
    s = _plain_scores(q, k, mask)
    p = jnp.exp(s - lse.reshape(s.shape[:3])[..., None])
    return jnp.where(mask != 0, p.mean((0, 1)), 0.0)


# --------------------------------------------------------------------------
# the core: Mosaic kernels
# --------------------------------------------------------------------------
def kernels_eligible(seq: int, head_dim: int) -> bool:
    """On a TPU (or under the interpreter), for lane-aligned shapes."""
    return (pallas_ops._kernels_enabled() and head_dim % _LANES == 0
            and seq % _LANES == 0)


def scores_eligible(seq: int, heads: int, width: int) -> bool:
    """On a TPU (or under the interpreter), where the indexer's heads
    fill whole lane groups and the sequence whole blocks."""
    return (pallas_ops._kernels_enabled() and _LANES % width == 0
            and heads * width % _LANES == 0 and seq % BLOCK == 0)


def _block(seq: int) -> int:
    return pallas_ops._fit_block(seq, BLOCK)


def _heads_a_visit(rep: int) -> int:
    """Query heads one visit runs against its key head's tile: the whole
    group where it has at most ``GROUP`` heads, else its largest part
    that divides it, so that the visit's blocks stay inside VMEM."""
    return max(n for n in range(1, GROUP + 1) if rep % n == 0)


def _lower_triangle(n: int, parts: int = 0):
    """The visits of the causal triangle ``j <= i`` of ``n`` blocks a
    side, and nothing above it, in the order the flat grid axis walks
    them: int32 tables that the index maps and the kernels read.  By
    query block (query block i, key block j), a row's key blocks one
    after the other; with ``parts``, by key block (i, j, part c of the
    key head's group): a key block's parts and, inside a part, its query
    blocks.  A square grid's steps above the diagonal fetch and compute
    nothing and still cost 0.10 us each (PERF.md section 6, PR 29)."""
    if parts:
        steps = [(i, j, c) for j in range(n) for c in range(parts)
                 for i in range(j, n)]
    else:
        steps = [(i, j) for i in range(n) for j in range(i + 1)]
    return tuple(np.asarray(t, np.int32) for t in zip(*steps))


def _note_visits(visits: int, n: int, heads: int) -> None:
    """Counts, when a kernel call is traced, the grid steps a square grid
    would take and the group visits that compute:
    ``dsa_core_visits_total{kind=square|visited}``, and the query heads
    of a visit, ``dsa_core_heads_per_visit``."""
    from ..observability import metrics as _obs_metrics
    reg = _obs_metrics.registry()
    for kind, steps in (("square", n * n), ("visited", n * (n + 1) // 2)):
        reg.counter("dsa_core_visits_total",
                    "visits of the sparse core's kernels, counted a call "
                    "when the call is traced: a key head's part of its "
                    "query heads against one key block, over the whole "
                    "square and over the causal triangle that is computed",
                    labels={"kind": kind}).inc(visits * steps)
    reg.gauge("dsa_core_heads_per_visit",
              "query heads that share one fetch and one decoding of a "
              "key, value and selection tile in the sparse core's "
              "kernels").set(heads)


def _addend(mask_ref):
    """The selection tile as what the scores take: 0 on a kept key, -inf
    off it.  Made once a visit, for all its heads."""
    return jnp.where(mask_ref[...].astype(jnp.int32) != 0,
                     jnp.float32(0.0), jnp.float32(-jnp.inf))


def _visit(q_ref, k_ref):
    """(rows of a block, head width, query heads) of a visit, read off
    its query and key blocks."""
    blk, d = k_ref.shape
    return blk, d, q_ref.shape[1] // d


def _each_head(heads: int, d: int, fn):
    """``fn(h, cols)`` for every query head of a visit, ``cols`` its lane
    block of the query block, as a loop inside the kernel that holds
    ``_TURN`` heads an iteration (an int32 of its own: ``fori_loop``
    counts in int64 under the package's x64, and Pallas takes ``scan``'s
    ``unroll`` only at 1 or all)."""
    from jax.experimental import pallas as pl
    if heads == 1:
        return fn(0, slice(0, d))
    turn = math.gcd(_TURN, heads)

    def step(i, _):
        for r in range(turn):
            h = i * np.int32(turn) + np.int32(r)
            fn(h, pl.ds(pl.multiple_of(h * np.int32(d), d), d))
        return i + np.int32(1), None
    jax.lax.scan(step, np.int32(0), None, length=heads // turn)


def _delta(do, o):
    """``sum_d do * o`` of a row, held in every lane of it."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    return jnp.broadcast_to(delta, (delta.shape[0], _LANES))


def _scores(q, k, scale, addend):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ) * scale + addend


def _fwd_kernel(i_tab, j_tab, q_ref, k_ref, v_ref, mask_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale):
    """A visit: one key, value and selection tile against ``heads`` query
    heads, each a lane block of the query block.  ``m`` is held in every
    lane of its row and ``l`` as 128 partial sums a row, added up in
    ``_finish`` (PERF.md section 6, PR 27)."""
    from jax.experimental import pallas as pl
    t = pl.program_id(1)
    i, j = i_tab[t], j_tab[t]
    blk, d, heads = _visit(q_ref, k_ref)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    addend = _addend(mask_ref)
    k, v = k_ref[...], v_ref[...]

    def head(h, cols):
        s = _scores(q_ref[:, cols], k, scale, addend)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, _LSE_FLOOR)
        p = jnp.exp(s - _across(m_safe, blk))
        alpha = jnp.exp(jnp.maximum(m_prev, _LSE_FLOOR) - m_safe)
        l_scr[h] = alpha * l_scr[h] + _lane_sums(p)
        acc_scr[:, cols] = acc_scr[:, cols] * _across(alpha, d) + \
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_scr[h] = m_new
    _each_head(heads, d, head)

    @pl.when(j == i)                    # a row ends on the diagonal
    def _finish():
        def head(h, cols):
            l_fin = jnp.maximum(
                jnp.sum(l_scr[h], axis=-1, keepdims=True), 1e-30)
            o_ref[:, cols] = (acc_scr[:, cols] / l_fin).astype(o_ref.dtype)
            lse_ref[h] = jnp.maximum(m_scr[h], _LSE_FLOOR) + jnp.log(l_fin)
        _each_head(heads, d, head)


def _dq_kernel(i_tab, j_tab, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
               mask_ref, dq_ref, dq_scr, delta_scr, *, scale):
    """``ds`` is rounded without the scale, which the sum takes once in
    ``_finish``: one pass over the tile less a head."""
    from jax.experimental import pallas as pl
    t = pl.program_id(1)
    i, j = i_tab[t], j_tab[t]
    blk, d, heads = _visit(q_ref, k_ref)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

        def head(h, cols):
            delta_scr[h] = _delta(do_ref[:, cols], o_ref[:, cols])
        _each_head(heads, d, head)

    addend = _addend(mask_ref)
    k, v = k_ref[...], v_ref[...]

    def head(h, cols):
        p = jnp.exp(_scores(q_ref[:, cols], k, scale, addend)
                    - _across(lse_ref[h], blk))
        dp = jax.lax.dot_general(do_ref[:, cols], v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - _across(delta_scr[h], blk))).astype(k.dtype)
        dq_scr[:, cols] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    _each_head(heads, d, head)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(i_tab, j_tab, c_tab, q_ref, k_ref, v_ref, do_ref, o_ref,
                lse_ref, mask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, parts, blocks):
    """A key block's gradient adds up over the query heads that read it
    (the visit's, then the group's further parts) and over their query
    blocks, in scratch."""
    from jax.experimental import pallas as pl
    t = pl.program_id(1)
    i, j, c = i_tab[t], j_tab[t], c_tab[t]
    blk, d, heads = _visit(q_ref, k_ref)

    @pl.when((c == 0) & (i == j))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    addend = _addend(mask_ref)
    k, v = k_ref[...], v_ref[...]

    def head(h, cols):
        q, do = q_ref[:, cols], do_ref[:, cols]
        p = jnp.exp(_scores(q, k, scale, addend) - _across(lse_ref[h], blk))
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - _across(_delta(do, o_ref[:, cols]), blk))
              ).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    _each_head(heads, d, head)

    @pl.when((c == parts - 1) & (i == blocks - 1))
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, *, scale, of):
    """A visit's heads are added up before the output block is touched,
    which stays resident over the visits of a tile."""
    from jax.experimental import pallas as pl
    i, j, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    blk, d, heads = _visit(q_ref, k_ref)

    @pl.when(b == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref[...])

    @pl.when(j <= i)
    def _run():
        addend = _addend(mask_ref)
        k = k_ref[...]

        def head(h, cols):      # the mean's 1 / of goes with the lse
            p_ref[...] += jnp.exp(
                _scores(q_ref[:, cols], k, scale, addend)
                - _across(lse_ref[h] + math.log(of), blk))
        _each_head(heads, d, head)


def _lanes(lse):
    return jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))


def _bytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, *args,
          tables=(), room=_ROOM):
    """One kernel call; the specs and shapes of its results come as
    lists and so do the results.  ``tables`` are scalar-prefetched: the index maps
    and the kernel get them after the grid indices and before the
    operands.  The VMEM limit is reckoned from what the call holds: every
    operand block twice (it is fetched while the last one is in use), the
    scratch, and ``room`` for the score-sized values of a visit (1 MiB
    each at 512 rows); the compiler's default of 16 MiB is less than a
    group of eight heads' blocks alone.  The room is not only a ceiling:
    the compiler lays a kernel out by it.  With 64 MiB in place of 16 a
    call at [8192, 32 over 4, 128] takes 5895 for 6007 us (dq) and 7265
    for 7449 (dkv), and 4162 for 4047 (forward) and 3255 for 2584
    (probabilities): PERF.md section 6, PR 29; so the two backward
    kernels ask for ``4 * _ROOM``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    held = sum(2 * _bytes(spec.block_shape, x.dtype) for spec, x in zip(
        in_specs + out_specs, list(args) + out_shape))
    held += sum(_bytes(s.shape, s.dtype) for s in scratch)
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=held + room),
        interpret=pallas_ops._interpret())(*tables, *args)


def _specs(blk: int, d: int, heads: int, where):
    """Block specs of the operands: q-like ``[S, H * D]``, of which a
    visit takes ``heads`` adjacent lane blocks, and key-like ``[S, G *
    D]``, of which it takes one; the lane-broadcast lse and the mask.
    ``where(*grid indices, *tables)`` gives (block of query heads, key
    head, query block, key block) of a grid step.  ``b * 0`` and
    ``lax.div``: under jax_enable_x64 a literal 0 or a ``//`` traces as
    int64, and Mosaic refuses the index map."""
    from jax.experimental import pallas as pl

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *ids: index(*where(*ids)))

    rows = spec((blk, heads * d), lambda b, g, i, j: (i, b))
    keys = spec((blk, d), lambda b, g, i, j: (j, g))
    lse = spec((heads, blk, _LANES), lambda b, g, i, j: (b, i, b * 0))
    mask = spec((blk, blk), lambda b, g, i, j: (i, j))
    return rows, keys, lse, mask


def _key_head(b, parts: int):
    return jax.lax.div(b, jnp.int32(parts))


def _by_query_block(parts: int):
    """Grid (block of query heads, visit of the triangle)."""
    return lambda b, t, i_tab, j_tab: (
        b, _key_head(b, parts), i_tab[t], j_tab[t])


def _by_key_block(parts: int):
    """Grid (key head, visit of the triangle and part of the group)."""
    return lambda g, t, i_tab, j_tab, c_tab: (
        g * parts + c_tab[t], g, i_tab[t], j_tab[t])


def _flat(x):
    return x.reshape(x.shape[0], -1)


def _geometry(q, k):
    """(block, blocks a side, query heads a visit, visits' parts of a
    key head's group) of a call."""
    s, h, _ = q.shape
    blk, rep = _block(s), h // k.shape[1]
    heads = _heads_a_visit(rep)
    return blk, s // blk, heads, rep // heads


def _core_fwd_kernels(q, k, v, mask):
    from jax.experimental.pallas import tpu as pltpu
    s, h, d = q.shape
    blk, n, heads, parts = _geometry(q, k)
    _note_visits(h // heads, n, heads)
    tables = _lower_triangle(n)
    rows, keys, lse_spec, mask_spec = _specs(blk, d, heads,
                                             _by_query_block(parts))
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d)),
        (h // heads, len(tables[0])), [rows, keys, keys, mask_spec],
        [rows, lse_spec],
        [jax.ShapeDtypeStruct((s, h * d), q.dtype),
         jax.ShapeDtypeStruct((h, s, _LANES), jnp.float32)],
        [pltpu.VMEM((heads, blk, _LANES), jnp.float32),
         pltpu.VMEM((heads, blk, _LANES), jnp.float32),
         pltpu.VMEM((blk, heads * d), jnp.float32)],
        _flat(q), _flat(k), _flat(v), mask, tables=tables)
    return out.reshape(q.shape), lse[..., 0]


def _core_bwd_kernels(q, k, v, mask, out, lse, do):
    from jax.experimental.pallas import tpu as pltpu
    s, h, d = q.shape
    g = k.shape[1]
    blk, n, heads, parts = _geometry(q, k)
    _note_visits(2 * (h // heads), n, heads)
    scale = 1.0 / math.sqrt(d)
    operands = tuple(_flat(x) for x in (q, k, v, do, out)) + (
        _lanes(lse), mask)
    tables = _lower_triangle(n)
    rows, keys, lse_spec, mask_spec = _specs(blk, d, heads,
                                             _by_query_block(parts))
    dq, = _call(
        functools.partial(_dq_kernel, scale=scale),
        (h // heads, len(tables[0])),
        [rows, keys, keys, rows, rows, lse_spec, mask_spec], [rows],
        [jax.ShapeDtypeStruct((s, h * d), q.dtype)],
        [pltpu.VMEM((blk, heads * d), jnp.float32),
         pltpu.VMEM((heads, blk, _LANES), jnp.float32)],
        *operands, tables=tables, room=4 * _ROOM)
    tables = _lower_triangle(n, parts)
    rows, keys, lse_spec, mask_spec = _specs(blk, d, heads,
                                             _by_key_block(parts))
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, parts=parts, blocks=n),
        (g, len(tables[0])),
        [rows, keys, keys, rows, rows, lse_spec, mask_spec], [keys, keys],
        [jax.ShapeDtypeStruct((s, g * d), k.dtype)] * 2,
        [pltpu.VMEM((blk, d), jnp.float32),
         pltpu.VMEM((blk, d), jnp.float32)],
        *operands, tables=tables, room=4 * _ROOM)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _mean_head_probs_kernels(q, k, lse, mask):
    """Grid (query block, key block, block of query heads): the output
    tile stays resident over its visits.  Above the diagonal a step
    writes its zeros and fetches nothing: every block index is held where
    the last step left it."""
    from jax.experimental import pallas as pl
    s, h, d = q.shape
    blk, n, heads, parts = _geometry(q, k)
    _note_visits(h // heads, n, heads)

    def where(i, j, b):
        b = jnp.where(j <= i, b, b * 0)
        return b, _key_head(b, parts), i, jnp.minimum(i, j)

    rows, keys, lse_spec, mask_spec = _specs(blk, d, heads, where)
    return _call(
        functools.partial(_probs_kernel, scale=1.0 / math.sqrt(d), of=h),
        (n, n, h // heads), [rows, keys, lse_spec, mask_spec],
        [pl.BlockSpec((blk, blk), lambda i, j, b: (i, j))],
        [jax.ShapeDtypeStruct((s, s), jnp.float32)], (),
        _flat(q), _flat(k), _lanes(lse), mask)[0]


@jax.custom_vjp
def _core_kernels(q, k, v, mask):
    return _core_fwd_kernels(q, k, v, mask)


def _core_kernels_fwd(q, k, v, mask):
    out, lse = _core_fwd_kernels(q, k, v, mask)
    return (out, lse), (q, k, v, mask, out, lse)


def _core_kernels_bwd(res, cts):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _core_bwd_kernels(q, k, v, mask, out, lse, cts[0])
    return dq, dk, dv, None


_core_kernels.defvjp(_core_kernels_fwd, _core_kernels_bwd)


def core(q, k, v, mask):
    """Attention of q ``[S, H, D]`` over the keys ``mask`` keeps of k, v
    ``[S, G, D]`` (query head i reads key head ``i // (H / G)``):
    (out ``[S, H, D]``, lse ``[H, S]`` float32).  The log-sum-exp is for
    :func:`mean_head_probs` and carries no gradient."""
    if kernels_eligible(q.shape[0], q.shape[2]):
        out, lse = _core_kernels(q, k, v, mask)
    else:
        out, lse = core_plain(q, k, v, mask)
    return out, jax.lax.stop_gradient(lse)


def mean_head_probs(q, k, lse, mask):
    """The attention probabilities summed over the heads and divided by
    their number: float32 ``[S, S]``, 0 off the selection.  It is the
    indexer's target and carries no gradient."""
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    if kernels_eligible(q.shape[0], q.shape[2]):
        return _mean_head_probs_kernels(q, k, lse, mask)
    return mean_head_probs_plain(q, k, lse, mask)


# --------------------------------------------------------------------------
# the index scores: Mosaic kernels
# --------------------------------------------------------------------------
def _note_score_visits(n: int, heads: int) -> None:
    """Counts, when a score-kernel call is traced, the head-tiles of the
    whole square and those of the causal triangle that are computed:
    ``dsa_indexer_visits_total{kind=square|visited}`` (a name of its own
    beside :func:`_note_visits`: instrument names are literals)."""
    from ..observability import metrics as _obs_metrics
    reg = _obs_metrics.registry()
    for kind, tiles in (("square", n * n), ("visited", n * (n + 1) // 2)):
        reg.counter("dsa_indexer_visits_total",
                    "head-tiles of the index-score kernels, counted a call "
                    "when the call is traced: one indexer head's products "
                    "of a query block and a key block, over the whole "
                    "square and over the causal triangle that is computed",
                    labels={"kind": kind}).inc(tiles * heads)


def _spread_weights(w_ref, w_scr, scale):
    """A query block's head weights, times the scores' scale, each held
    in every lane of its row: once a query block, for all its tiles."""
    w = w_ref[...].astype(jnp.float32) * scale
    for h in range(w_scr.shape[0]):
        w_scr[h] = jnp.broadcast_to(w[:, h:h + 1], w_scr.shape[1:])


def _on_the_diagonal(k_idx, blk: int, share: int):
    """The key tiles ``[blk, width]`` each as ``[share * blk, 128]``:
    ``share`` copies down the diagonal of lane blocks ``width`` wide,
    zeros beside them.  A lane group of the query block holds ``share``
    heads, and its one full-depth product against this is their ``[blk,
    blk]`` products side by side, nothing sliced inside a lane group; a
    head of 64 fills half of the MXU's depth either way."""
    seq, width = k_idx.shape
    tiles = k_idx.reshape(seq // blk, blk, width)
    return jnp.concatenate(
        [jnp.pad(tiles, ((0, 0), (0, 0),
                         (x * width, _LANES - (x + 1) * width)))
         for x in range(share)], 1).reshape(share * seq, _LANES)


def _off_the_diagonal(dk, blk: int, share: int):
    """The gradient of what :func:`_on_the_diagonal` made."""
    width = _LANES // share
    dk = dk.reshape(-1, share, blk, share, width)
    return sum(dk[:, x, :, x] for x in range(share)).reshape(-1, width)


def _group_products(q_ref, group, cols, k, blk: int):
    """(head, its ``[blk, blk]`` products) for the heads of a lane group
    of the query block, ``cols``: one product against the key tile as
    :func:`_on_the_diagonal` laid it out."""
    share = k.shape[0] // blk
    pre = jax.lax.dot_general(q_ref[:, cols], k, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return [(group * np.int32(share) + np.int32(x),
             pre[:, x * blk:(x + 1) * blk]) for x in range(share)]


def _score_fwd_kernel(i_tab, j_tab, q_ref, k_ref, w_ref, o_ref, w_scr, *,
                      scale):
    """A tile of ``I``: a head's ``[blk, blk]`` products are passed
    through ReLU, weighted and added to the tile where they are made."""
    from jax.experimental import pallas as pl
    blk, k = o_ref.shape[0], k_ref[...]

    @pl.when(j_tab[pl.program_id(0)] == 0)
    def _init():
        _spread_weights(w_ref, w_scr, scale)

    o_ref[...] = jnp.zeros_like(o_ref[...])

    def group(g, cols):
        o_ref[...] += functools.reduce(jnp.add, (
            jnp.maximum(pre, 0.0) * _across(w_scr[h], blk)
            for h, pre in _group_products(q_ref, g, cols, k, blk)))
    _each_head(q_ref.shape[1] // _LANES, _LANES, group)


def _score_bwd_kernel(i_tab, j_tab, q_ref, k_ref, w_ref, di_ref, dq_ref,
                      dk_ref, dw_ref, dq_scr, w_scr, *, scale):
    """The three gradients in one walk by query block.  ``dq`` adds up
    over a row's key tiles in scratch and ``dw`` as 128 partial sums a
    row in its output block.  ``dk`` adds up across query blocks in
    float32 in an output block that is the whole array and stays in VMEM
    for the call: the indexer has one key head, so that is 8 MB at
    ``[8192, 64]`` in the key tiles' layout, where a second walk by key
    block would make every product and every ReLU again."""
    from jax.experimental import pallas as pl
    t = pl.program_id(0)
    i, j = i_tab[t], j_tab[t]
    blk, k, di = di_ref.shape[0], k_ref[...], di_ref[...]
    keys = pl.ds(pl.multiple_of(j * np.int32(k.shape[0]), k.shape[0]),
                 k.shape[0])

    @pl.when(t == 0)
    def _first():
        dk_ref[...] = jnp.zeros_like(dk_ref[...])

    @pl.when(j == 0)
    def _init():
        _spread_weights(w_ref, w_scr, scale)
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        dw_ref[...] = jnp.zeros_like(dw_ref[...])

    def group(g, cols):
        dpre = []
        for h, pre in _group_products(q_ref, g, cols, k, blk):
            dw_ref[h] += _lane_sums(jnp.maximum(pre, 0.0) * di)
            dpre.append(jnp.where(pre > 0.0, di * _across(w_scr[h], blk),
                                  0.0).astype(k.dtype))
        dpre = jnp.concatenate(dpre, 1)
        dq_scr[:, cols] += jax.lax.dot_general(
            dpre, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[keys, :] += jax.lax.dot_general(
            dpre, q_ref[:, cols], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    _each_head(q_ref.shape[1] // _LANES, _LANES, group)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _score_geometry(q_idx, k_idx, w_idx):
    """What both score kernels' calls share: (block, heads a lane group,
    the scores' scale, the triangle's tables, the three operands, and the
    block specs of the query block's rows ``[blk, J * width]``, the key
    tile, the weights ``[blk, J]`` and the ``[blk, blk]`` tile over the
    flat grid)."""
    from jax.experimental import pallas as pl
    seq, heads, width = q_idx.shape
    blk, share = _block(seq), _LANES // width
    _note_score_visits(seq // blk, heads)

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda t, i_tab, j_tab: index(
            i_tab[t], j_tab[t]))

    return (blk, share, 1.0 / math.sqrt(width * heads),
            _lower_triangle(seq // blk),
            (_flat(q_idx), _on_the_diagonal(k_idx, blk, share), w_idx),
            [spec((blk, heads * width), lambda i, j: (i, i * 0)),
             spec((share * blk, _LANES), lambda i, j: (j, j * 0)),
             spec((blk, heads), lambda i, j: (i, i * 0)),
             spec((blk, blk), lambda i, j: (i, j))])


def _scores_fwd_kernels(q_idx, k_idx, w_idx):
    from jax.experimental.pallas import tpu as pltpu
    seq, heads, _ = q_idx.shape
    blk, _, scale, tables, operands, specs = _score_geometry(q_idx, k_idx,
                                                             w_idx)
    return _call(
        functools.partial(_score_fwd_kernel, scale=scale),
        (len(tables[0]),), specs[:3], specs[3:],
        [jax.ShapeDtypeStruct((seq, seq), jnp.float32)],
        [pltpu.VMEM((heads, blk, _LANES), jnp.float32)],
        *operands, tables=tables)[0]


def _scores_bwd_kernels(q_idx, k_idx, w_idx, d_scores):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    seq, heads, width = q_idx.shape
    blk, share, scale, tables, operands, specs = _score_geometry(
        q_idx, k_idx, w_idx)
    dq, dk, dw = _call(
        functools.partial(_score_bwd_kernel, scale=scale),
        (len(tables[0]),), specs,
        [specs[0],
         pl.BlockSpec((share * seq, _LANES),
                      lambda t, i_tab, j_tab: (t * 0, t * 0)),
         pl.BlockSpec((heads, blk, _LANES),
                      lambda t, i_tab, j_tab: (t * 0, i_tab[t], t * 0))],
        [jax.ShapeDtypeStruct((seq, heads * width), q_idx.dtype),
         jax.ShapeDtypeStruct((share * seq, _LANES), jnp.float32),
         jax.ShapeDtypeStruct((heads, seq, _LANES), jnp.float32)],
        [pltpu.VMEM((blk, heads * width), jnp.float32),
         pltpu.VMEM((heads, blk, _LANES), jnp.float32)],
        *operands, d_scores, tables=tables, room=4 * _ROOM)
    return (dq.reshape(q_idx.shape),
            _off_the_diagonal(dk, blk, share).astype(k_idx.dtype),
            (dw.sum(-1).T * scale).astype(w_idx.dtype))


@jax.custom_vjp
def _scores_kernels(q_idx, k_idx, w_idx):
    """``I`` of a sequence, float32 ``[S, S]``: the tiles of the causal
    triangle; the tiles above it are never written and carry no
    gradient."""
    return _scores_fwd_kernels(q_idx, k_idx, w_idx)


def _scores_kernels_fwd(q_idx, k_idx, w_idx):
    return _scores_fwd_kernels(q_idx, k_idx, w_idx), (q_idx, k_idx, w_idx)


_scores_kernels.defvjp(
    _scores_kernels_fwd, lambda res, d: _scores_bwd_kernels(*res, d))


# --------------------------------------------------------------------------
# what the indexer learns from
# --------------------------------------------------------------------------
def _kl_chunk(scores, probs, mask, seq: int):
    """Of a chunk's rows: sum_t KL(P_t || softmax over S_t of I_t), and
    the gradient of the sequence's mean of it in I, (Q - P) / S on the
    selection and 0 off it."""
    keep = mask != 0
    logits = jnp.where(keep, scores, _NEG)
    log_q = logits - jax.nn.logsumexp(logits, -1, keepdims=True)
    p = jnp.where(keep, probs, 0.0)
    kl = jnp.where(p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-37)) - log_q),
                   0.0).sum()
    return kl, jnp.where(keep, jnp.exp(log_q) - p, 0.0) / seq


def _kl_and_grads(q_idx, k_idx, w_idx, probs, mask, chunk):
    """mean_t KL(P_t || softmax over S_t of I_t) of one sequence, and its
    gradients for (q_idx, k_idx, w_idx) in float32.  The scores are
    computed again and their gradient is pushed back at once, so nothing
    ``[S, S]`` waits for a backward pass."""
    seq = q_idx.shape[0]
    total = jnp.zeros((), jnp.float32)
    if scores_eligible(*q_idx.shape):
        # one kernel call each way over the sequence; and not while the
        # selection's scores are alive: left to itself the forward call
        # runs as soon as the indexer's three outputs are there
        q_idx, probs = _in_turn(q_idx, probs)
        scores, back = jax.vjp(_scores_kernels, q_idx, k_idx, w_idx)
        d_scores = []
        for r0, c, e in _chunks(seq, chunk):
            kl, d_chunk = _kl_chunk(scores[r0:r0 + c, :e],
                                    probs[r0:r0 + c, :e],
                                    mask[r0:r0 + c, :e], seq)
            total += kl
            d_scores.append(jnp.pad(d_chunk, ((0, 0), (0, seq - e))))
        grads = back(jnp.concatenate(d_scores, 0))
        # the loss is there when its gradients are, so that who waits
        # for the one (models/keye_lm.py) has waited for the other
        return _in_turn(total / seq, tuple(
            g.astype(jnp.float32) for g in grads))
    gq, gw = [], []
    gk = jnp.zeros(k_idx.shape, jnp.float32)
    for r0, c, e in _chunks(seq, chunk):
        scores, back = jax.vjp(chunk_scores, q_idx[r0:r0 + c], k_idx[:e],
                               w_idx[r0:r0 + c])
        kl, d_chunk = _kl_chunk(scores, probs[r0:r0 + c, :e],
                                mask[r0:r0 + c, :e], seq)
        total += kl
        dq_c, dk_c, dw_c = back(d_chunk)
        gq.append(dq_c.astype(jnp.float32))
        gw.append(dw_c.astype(jnp.float32))
        gk = gk.at[:e].add(dk_c.astype(jnp.float32))
        q_idx, gk = _in_turn(q_idx, gk)
    return total / seq, (jnp.concatenate(gq, 0), gk, jnp.concatenate(gw, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def indexer_kl(q_idx, k_idx, w_idx, probs, mask, chunk):
    """The indexer's loss on one sequence; differentiable in the
    indexer's three inputs only."""
    return _kl_and_grads(q_idx, k_idx, w_idx, probs, mask, chunk)[0]


def _indexer_kl_fwd(q_idx, k_idx, w_idx, probs, mask, chunk):
    loss, grads = _kl_and_grads(q_idx, k_idx, w_idx, probs, mask, chunk)
    return loss, tuple(g.astype(x.dtype) for g, x in zip(
        grads, (q_idx, k_idx, w_idx)))


def _indexer_kl_bwd(chunk, grads, ct):
    return tuple((ct * g).astype(g.dtype) for g in grads) + (None, None)


indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)
