"""Learned sparse attention of the DeepSeek-Sparse-Attention kind: a small
indexer scores every causal key for every query, the ``topk`` best are
kept, and attention runs over the kept keys only.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) / sqrt(DI * J)
    S_t     = the min(t + 1, topk) causal s with the largest I[t, s];
              a tie at the border goes to the later position
    o[t, i] = softmax over S_t of (q[t, i] . k[s, i // R] / sqrt(D)) v

Everything here is a pure function of one sequence's arrays (the model
loops over the batch), laid out as the projections leave them: q ``[S,
H, D]``, k and v ``[S, G, D]``; the kernels read head h as the lane
block h of ``[S, H * D]``, so nothing is transposed.  Work is laid out in query chunks of ``chunk``
rows against the causal extent of the chunk, so no ``[S, S]`` array of
more than one head's width is ever alive: index scores ``[chunk, E]``
float32, the selection as one ``[S, S]`` int8 mask.

The selection is exact.  Only the ``topk``-th largest score of a row is
needed, so no row is sorted: the scores are mapped to integers of the
same order and the threshold is found bit by bit (32 counts a row); a
tie at the threshold is broken by a second search over positions, which
runs only where a row has one.

The core is dense attention under that mask.  On a TPU it is four Mosaic
kernels after ``pallas_ops``' unpacked flash family with the mask as one
more operand (forward, dq, dkv, and the head-averaged probabilities the
indexer learns from); elsewhere plain ``jax.numpy``, which is also what
the kernels are checked against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import pallas_ops

_LANES = 128
_NEG = -1e30
BLOCK = 512                  # the kernels' query and key block


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
    for r0, c, e in _chunks(seq, chunk):
        with jax.named_scope("indexer"):
            scores = chunk_scores(q_idx[r0:r0 + c], k_idx[:e],
                                  w_idx[r0:r0 + c])
        with jax.named_scope("select"):
            keep = select_chunk(scores, r0, topk)
            masks.append(jnp.pad(keep.astype(jnp.int8),
                                 ((0, 0), (0, seq - e))))
            # one chunk after the other: left to itself the compiler
            # computes every chunk's [C, J, E] scores first and holds them
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


def _block(seq: int) -> int:
    return pallas_ops._fit_block(seq, BLOCK)


def _masked_scores(q_ref, k_ref, mask_ref, scale):
    s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, -jnp.inf)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, blocks):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(1), pl.program_id(2)
    floor = pallas_ops._LSE_FLOOR

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(j <= i)
    def _run():
        s = _masked_scores(q_ref, k_ref, mask_ref, scale)
        m_prev, l_prev = m_scr[...][:, :1], l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, floor)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.maximum(m_prev, floor) - m_safe)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == blocks - 1)
    def _finish():
        l_fin = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[...] = (acc_scr[...] / l_fin).astype(o_ref.dtype)
        lse = jnp.maximum(m_scr[...][:, :1], floor) + jnp.log(l_fin)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, mask_ref,
               dq_ref, dq_scr, delta_scr, *, scale, blocks):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        delta = jnp.sum(do_ref[...].astype(jnp.float32)
                        * o_ref[...].astype(jnp.float32), -1, keepdims=True)
        delta_scr[...] = jnp.broadcast_to(delta, delta_scr.shape)

    @pl.when(j <= i)
    def _run():
        k = k_ref[...]
        p = jnp.exp(_masked_scores(q_ref, k_ref, mask_ref, scale)
                    - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_scr[:, :1]) * scale).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == blocks - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, mask_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, blocks, rep):
    """Grid (key head, key block, query head of the group, query block):
    a key block's gradient adds up over the query heads that read it and
    over their query blocks, in scratch."""
    from jax.experimental import pallas as pl
    j, r, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    @pl.when(i >= j)
    def _run():
        q, do = q_ref[...], do_ref[...]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[...].astype(jnp.float32), -1, keepdims=True)
        p = jnp.exp(_masked_scores(q_ref, k_ref, mask_ref, scale)
                    - lse_ref[0][:, :1])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((r == rep - 1) & (i == blocks - 1))
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, *, scale, heads):
    from jax.experimental import pallas as pl
    i, j, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(h == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref[...])

    @pl.when(j <= i)
    def _run():
        p_ref[...] += jnp.exp(_masked_scores(q_ref, k_ref, mask_ref, scale)
                              - lse_ref[0][:, :1]) * (1.0 / heads)


def _lanes(lse):
    return jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, *args):
    from jax.experimental import pallas as pl
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        interpret=pallas_ops._interpret())(*args)


def _key_head(h, rep: int):
    return jax.lax.div(h, jnp.int32(rep))


def _specs(blk: int, d: int, where):
    """Block specs of the operands: q-like ``[S, H * D]`` and key-like
    ``[S, G * D]`` (a head is a lane block), the lane-broadcast lse and
    the mask.  ``where(*grid indices)`` gives (query head, key head,
    query block, key block) of a grid step; a step above the diagonal
    computes nothing, and the block it would fetch is held at the
    diagonal's so that it fetches nothing either.  ``h * 0`` and
    ``lax.div``: under jax_enable_x64 a literal 0 or a ``//`` traces as
    int64, and Mosaic refuses the index map."""
    from jax.experimental import pallas as pl

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *ids: index(*where(*ids)))

    rows = spec((blk, d), lambda h, g, i, j: (i, h))
    keys = spec((blk, d), lambda h, g, i, j: (j, g))
    lse = spec((1, blk, _LANES), lambda h, g, i, j: (h, i, h * 0))
    mask = spec((blk, blk), lambda h, g, i, j: (i, j))
    return rows, keys, lse, mask


def _by_query_block(rep: int):
    """Grid (query head, query block, key block)."""
    return lambda h, i, j: (h, _key_head(h, rep), i, jnp.minimum(i, j))


def _by_key_block(rep: int):
    """Grid (key head, key block, query head of the group, query block)."""
    return lambda g, j, r, i: (g * rep + r, g, jnp.maximum(i, j), j)


def _flat(x):
    return x.reshape(x.shape[0], -1)


def _core_fwd_kernels(q, k, v, mask):
    from jax.experimental.pallas import tpu as pltpu
    s, h, d = q.shape
    blk, rep = _block(s), h // k.shape[1]
    n = s // blk
    rows, keys, lse_spec, mask_spec = _specs(blk, d, _by_query_block(rep))
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), blocks=n),
        (h, n, n), [rows, keys, keys, mask_spec], [rows, lse_spec],
        [jax.ShapeDtypeStruct((s, h * d), q.dtype),
         jax.ShapeDtypeStruct((h, s, _LANES), jnp.float32)],
        [pltpu.VMEM((blk, _LANES), jnp.float32),
         pltpu.VMEM((blk, _LANES), jnp.float32),
         pltpu.VMEM((blk, d), jnp.float32)],
        _flat(q), _flat(k), _flat(v), mask)
    return out.reshape(q.shape), lse[..., 0]


def _core_bwd_kernels(q, k, v, mask, out, lse, do):
    from jax.experimental.pallas import tpu as pltpu
    s, h, d = q.shape
    g = k.shape[1]
    blk, rep = _block(s), h // g
    n = s // blk
    scale = 1.0 / math.sqrt(d)
    lse_b = _lanes(lse)
    operands = tuple(_flat(x) for x in (q, k, v, do, out)) + (lse_b, mask)
    rows, keys, lse_spec, mask_spec = _specs(blk, d, _by_query_block(rep))
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, blocks=n), (h, n, n),
        [rows, keys, keys, rows, rows, lse_spec, mask_spec], rows,
        jax.ShapeDtypeStruct((s, h * d), q.dtype),
        [pltpu.VMEM((blk, d), jnp.float32),
         pltpu.VMEM((blk, _LANES), jnp.float32)],
        *operands)
    rows, keys, lse_spec, mask_spec = _specs(blk, d, _by_key_block(rep))
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, blocks=n, rep=rep),
        (g, n, rep, n),
        [rows, keys, keys, rows, rows, lse_spec, mask_spec], [keys, keys],
        [jax.ShapeDtypeStruct((s, g * d), k.dtype)] * 2,
        [pltpu.VMEM((blk, d), jnp.float32),
         pltpu.VMEM((blk, d), jnp.float32)],
        *operands)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _mean_head_probs_kernels(q, k, lse, mask):
    from jax.experimental import pallas as pl
    s, h, d = q.shape
    blk, rep = _block(s), h // k.shape[1]
    n = s // blk

    def head(i, j, hh):          # held at 0 above the diagonal: no fetch
        return jnp.where(j <= i, hh, hh * 0)

    rows = pl.BlockSpec((blk, d), lambda i, j, hh: (i, head(i, j, hh)))
    keys = pl.BlockSpec((blk, d), lambda i, j, hh: (
        jnp.minimum(i, j), _key_head(head(i, j, hh), rep)))
    lse_spec = pl.BlockSpec((1, blk, _LANES), lambda i, j, hh: (
        head(i, j, hh), i, hh * 0))
    tile = pl.BlockSpec((blk, blk), lambda i, j, hh: (i, jnp.minimum(i, j)))
    out = pl.BlockSpec((blk, blk), lambda i, j, hh: (i, j))
    return _call(
        functools.partial(_probs_kernel, scale=1.0 / math.sqrt(d), heads=h),
        (n, n, h), [rows, keys, lse_spec, tile], out,
        jax.ShapeDtypeStruct((s, s), jnp.float32), (),
        _flat(q), _flat(k), _lanes(lse), mask)


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
# what the indexer learns from
# --------------------------------------------------------------------------
def _kl_and_grads(q_idx, k_idx, w_idx, probs, mask, chunk):
    """mean_t KL(P_t || softmax over S_t of I_t) of one sequence, and its
    gradients for (q_idx, k_idx, w_idx), chunk by chunk: the gradient of
    the loss in I is (Q - P) / S on the selection, and it is pushed back
    through the chunk's scores at once, so nothing ``[S, S]`` waits for a
    backward pass."""
    seq = q_idx.shape[0]
    total = jnp.zeros((), jnp.float32)
    gq, gw = [], []
    gk = jnp.zeros(k_idx.shape, jnp.float32)
    for r0, c, e in _chunks(seq, chunk):
        scores, back = jax.vjp(chunk_scores, q_idx[r0:r0 + c], k_idx[:e],
                               w_idx[r0:r0 + c])
        keep = mask[r0:r0 + c, :e] != 0
        logits = jnp.where(keep, scores, _NEG)
        log_q = logits - jax.nn.logsumexp(logits, -1, keepdims=True)
        p = jnp.where(keep, probs[r0:r0 + c, :e], 0.0)
        total += jnp.where(p > 0.0, p * (jnp.log(jnp.maximum(p, 1e-37))
                                         - log_q), 0.0).sum()
        d_scores = jnp.where(keep, jnp.exp(log_q) - p, 0.0) / seq
        dq_c, dk_c, dw_c = back(d_scores)
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
