"""Pallas TPU kernels — flash attention (v2 scope).

This is the TPU-native replacement for upstream's flashattn CUDA
integration (paddle/phi/kernels/gpu/flash_attn_kernel.cu +
third_party/flashattn — SURVEY.md §2.1 "FlashAttention integration",
including the varlen kernels).

Strategy per /opt/skills/guides/pallas_guide.md: a blocked online-softmax
kernel over (Bq, Bk) tiles with the K/V loop in the grid's minor-most
dimension (sequential on TPU) carrying running max/denominator in VMEM
scratch.  On non-TPU backends (CPU tests) the op takes the XLA composed
form — same math, same signature — so the op is portable and the
Pallas path is a pure performance substitution.

Feature coverage (upstream flash_attn / flash_attn_varlen parity):

* causal and full attention;
* cross-attention ``Sq != Sk`` (non-causal) on the Pallas path;
* GQA / MQA: ``key``/``value`` may carry fewer heads than ``query``
  (``Hq % Hkv == 0``); KV heads are broadcast per group;
* varlen / packed sequences via ``segment_ids`` masking — the TPU-native
  form of upstream's cu_seqlens varlen kernels (static shapes, SPMD
  friendly); tokens attend only within equal segment ids;
* dropout: computed in the composed XLA form (mask fused by XLA); the
  streaming Pallas kernel is used on the dropout-free path (the common
  LLM-training configuration).  Semantics are never silently dropped.

Which form runs (the packed kernels, the [BH, S, D] kernels or the
composed form) is decided in one place, ``_attention_form``, from the
platform and the shape one device holds; no environment variable picks
a kernel or a block size.  A kernel the compiler refuses is a compile
error in the caller's step: nothing here catches it and hands the call
to the composed form, because a training run that quietly lost its
kernels looks exactly like one that has them.
Under a mesh of several devices the kernels run per device inside a
``shard_map`` (``_per_device``): Mosaic kernels cannot be partitioned
by GSPMD.

Layout: paddle flash_attention takes [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ._primitive import primitive
from ..framework import env_knobs
from ..framework import random as _random

logger = logging.getLogger("paddle_tpu")

_WARNED: set = set()

# -inf clamp for the saved log-sum-exp: keeps fully-masked rows (varlen
# padding) from producing NaN in the recompute backward (exp(-inf - -inf))
_LSE_FLOOR = -1e30


def _warn_once(tag: str, msg: str) -> None:
    if tag not in _WARNED:
        _WARNED.add(tag)
        logger.warning(msg)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """PADDLE_TPU_PALLAS_INTERPRET=1 runs the Pallas kernels in
    interpreter mode — lets CPU tests exercise the ACTUAL kernel code
    (not just the composed fallback)."""
    return bool(env_knobs.get_raw("PADDLE_TPU_PALLAS_INTERPRET"))


def _fit_block(seq: int, requested: int) -> int:
    """Largest block ≤ requested that divides ``seq`` (multiple-of-128
    preferred).  The grid floor-divides by the block, so a non-dividing
    block would silently leave the sequence tail uncomputed."""
    b = min(requested, seq)
    while b > 128 and seq % b:
        b -= 128
    if seq % b:
        b = math.gcd(seq, b)
    return max(b, 1)


# ---------------------------------------------------------------------------
# Pallas forward kernel (TPU)
# ---------------------------------------------------------------------------
# NOTE: index maps use `b * 0` instead of a literal 0 — with the
# global jax_enable_x64 a literal traces as i64 and Mosaic fails to
# legalize the index-map func.return (verified on hardware).
# Mosaic layout constants: trailing lane dim for row-vectors (lse,
# delta, q-side segment ids) and sublane rows for k-side segment ids —
# the TPU vector layout requires the last two block dims to be (8k,
# 128k) or equal to the array dims (same trick as jax's reference
# pallas flash kernel).
_LANES = 128
_SUBLANES = 8

# The resident block, what one grid step holds in VMEM: query rows
# against key rows, each fitted down to divide its sequence.  Of the
# sizes run on a v5e these won (PERF.md section 6, PR 27); a kernel
# entry's ``block_q``/``block_k`` arguments override them.
_BLOCK_Q = 512
_BLOCK_K = 1024


def _flash_kernel(*refs, scale: float, causal: bool, block_q: int,
                  block_k: int, seq_k: int, has_seg: bool):
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, \
            m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        qs_ref = ks_ref = None

    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def body():
        # keep the matmul inputs in their storage dtype (bf16 in training)
        # with f32 accumulation: bf16×bf16→f32 is the native full-rate MXU
        # mode, while f32×f32 runs at 1/4 rate (this one cast was worth
        # ~2.5× on the whole attention step)
        q = q_ref[0]                         # [block_q, d]
        k = k_ref[0]                         # [block_k, d]
        v = v_ref[0]                         # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        if has_seg:
            qs = qs_ref[0][:, :1]            # [block_q, 1] int32
            ks = ks_ref[0][:1, :]            # [1, block_k] int32
            s = jnp.where(qs == ks, s, -jnp.inf)
        m_prev = m_scr[...][:, :1]           # [bq, 1]
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # clamp so fully-masked rows stay finite downstream
        m_safe = jnp.maximum(m_new, _LSE_FLOOR)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.maximum(m_prev, _LSE_FLOOR) - m_safe)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, (block_q, _LANES))
        l_scr[...] = jnp.broadcast_to(l_new, (block_q, _LANES))

    if causal and not has_seg:
        # skip fully-masked kv blocks (upper-triangular): kv_start > q_end
        @pl.when(kv_idx * block_k <= q_idx * block_q + block_q - 1)
        def _run():
            body()
    else:
        body()

    n_kv = seq_k // block_k

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        l_fin = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_fin, 1e-30)).astype(
            o_ref.dtype)
        # log-sum-exp per query row (clamped), saved for the backward;
        # broadcast across the lane dim (Mosaic layout requirement)
        lse = (jnp.maximum(m_scr[...][:, :1], _LSE_FLOOR) +
               jnp.log(jnp.maximum(l_fin, 1e-30)))
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, _LANES))


def _pallas_flash_bh(q, k, v, q_seg=None, k_seg=None, *, causal: bool,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None):
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] → (out [BH, Sq, D],
    lse [BH, Sq, LANES] — per-row log-sum-exp lane-broadcast across the
    last dim; value at [..., 0], kept in this layout for the backward).
    Sq/Sk must divide by the blocks (caller guards).
    q_seg/k_seg: optional [BH, S*] int32 segment ids (varlen packing)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    has_seg = q_seg is not None
    block_q = _fit_block(sq, block_q or _BLOCK_Q)
    block_k = _fit_block(sk, block_k or _BLOCK_K)
    scale = 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_k=sk, has_seg=has_seg)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, b * 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, b * 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, b * 0)),
    ]
    args = [q, k, v]
    if has_seg:
        # lane/sublane-broadcast layouts (Mosaic block constraint)
        qsb = jax.lax.broadcast_in_dim(
            q_seg, (bh, sq, _LANES), (0, 1))
        ksb = jax.lax.broadcast_in_dim(
            k_seg, (bh, _SUBLANES, sk), (0, 2))
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, b * 0)),
            pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b, i, j: (b, b * 0, j)),
        ]
        args += [qsb, ksb]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, b * 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, b * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    # lse stays in its [BH, Sq, LANES] lane-broadcast form: the backward
    # kernels read it directly, avoiding a 50MB-per-layer slice + re-
    # broadcast round-trip through HBM (measured ~3 ms/step on GPT-2)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels — standard flash-attention backward: recompute
# P per block from the saved lse; never materialise [Sq, Sk] in HBM.  A
# dQ pass, then a dK/dV pass, each with one block of scratch.
# ---------------------------------------------------------------------------
def _flash_bwd_dq_kernel(*refs, scale: float, causal: bool,
                         block_q: int, block_k: int, seq_k: int,
                         has_seg: bool):
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, qs_ref, ks_ref, \
            dq_ref, dq_scr, delta_scr = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, \
            dq_scr, delta_scr = refs
        qs_ref = ks_ref = None

    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        # delta_i = rowsum(dO_i * O_i), computed once per q block in
        # VMEM instead of as an XLA pass + [BH, Sq, LANES] broadcast
        d_row = jnp.sum(do_ref[0].astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=-1,
                        keepdims=True)
        delta_scr[...] = jnp.broadcast_to(d_row, delta_scr.shape)

    def body():
        # bf16 matmul inputs + f32 accumulation (full-rate MXU; see fwd)
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]
        do = do_ref[0]                            # [bq, d]
        lse = lse_ref[0][:, :1]                   # [bq, 1]
        delta = delta_scr[:, :1]                  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        if has_seg:
            s = jnp.where(qs_ref[0][:, :1] == ks_ref[0][:1, :], s,
                          -jnp.inf)
        p = jnp.exp(s - lse)                      # normalised probs
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal and not has_seg:
        @pl.when(kv_idx * block_k <= q_idx * block_q + block_q - 1)
        def _run():
            body()
    else:
        body()

    n_kv = seq_k // block_k

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, scale: float, causal: bool,
                          block_q: int, block_k: int, seq_q: int,
                          has_seg: bool):
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, qs_ref, ks_ref, \
            dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref, \
            dk_scr, dv_scr = refs
        qs_ref = ks_ref = None

    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def body():
        # bf16 matmul inputs + f32 accumulation (full-rate MXU; see fwd)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        # delta recomputed per visit (cheap VPU rowsum on the streamed
        # dO/O blocks; replaces the XLA delta pass + lane broadcast)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=-1,
                        keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        if has_seg:
            s = jnp.where(qs_ref[0][:, :1] == ks_ref[0][:1, :], s,
                          -jnp.inf)
        p = jnp.exp(s - lse)                      # [bq, bk]
        p_lo = p.astype(do.dtype)
        dv_scr[...] += jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # [bk, d]

    if causal and not has_seg:
        @pl.when(q_idx * block_q + block_q - 1 >= kv_idx * block_k)
        def _run():
            body()
    else:
        body()

    n_q = seq_q // block_q

    @pl.when(q_idx == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pallas_flash_bwd(q, k, v, out, lse, do, q_seg=None, k_seg=None, *,
                      causal: bool, block_q: Optional[int] = None,
                      block_k: Optional[int] = None):
    """Flash backward; q [BH,Sq,D], k/v [BH,Sk,D] → (dq, dk, dv).

    ``lse`` arrives in the forward's [BH, Sq, LANES] lane-broadcast
    form and is consumed directly; delta is computed inside the kernels
    from the streamed dO/O blocks (no XLA delta pass, no broadcasts)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(sq, block_q or _BLOCK_Q)
    block_k = _fit_block(sk, block_k or _BLOCK_K)
    scale = 1.0 / math.sqrt(d)
    has_seg = q_seg is not None
    lse_b = lse
    if has_seg:
        qs_b = jax.lax.broadcast_in_dim(q_seg, (bh, sq, _LANES), (0, 1))
        ks_b = jax.lax.broadcast_in_dim(
            k_seg, (bh, _SUBLANES, sk), (0, 2))

    # dQ pass: grid (bh, q, kv), kv the minor (sequential) axis
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, b * 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, b * 0))
    rowq = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, b * 0))
    rowk = pl.BlockSpec((1, _SUBLANES, block_k),
                        lambda b, i, j: (b, b * 0, j))
    in_specs = [qspec, kspec, kspec, qspec, qspec, rowq]
    args = [q, k, v, do, out, lse_b]
    if has_seg:
        in_specs += [rowq, rowk]
        args += [qs_b, ks_b]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, seq_k=sk, has_seg=has_seg),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, b * 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=_interpret(),
    )(*args)

    # dkv grid: (bh, kv, q) — q is the minor (sequential) axis
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, b * 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, b * 0))
    rowq2 = pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, b * 0))
    rowk2 = pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b, j, i: (b, b * 0, j))
    in_specs2 = [qspec2, kspec2, kspec2, qspec2, qspec2, rowq2]
    args2 = [q, k, v, do, out, lse_b]
    if has_seg:
        in_specs2 += [rowq2, rowk2]
        args2 += [qs_b, ks_b]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, seq_q=sq, has_seg=has_seg),
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, b * 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, b * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
    )(*args2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Packed-heads kernels — the transpose-free layout.
#
# The [B,S,H,D]→[B*H,S,D] form above needs a physical S↔H transpose of
# q/k/v/out in BOTH directions of every layer (XLA materialises a
# layout-change copy per tensor because pallas_call pins default
# layouts).  Here the kernels instead read the projection output
# directly as [B, S, H*D] (a free reshape): heads are packed into
# 128-lane groups (``hpb`` heads per block when D < 128), the grid
# walks (B*G, ...) with G = H/hpb lane-groups, and each kernel unrolls
# the per-head online softmax over static lane slices of its block.
# lse is stored in the SAME [B, Sq, H*D] layout (per-head value
# broadcast over that head's d lanes), so forward and backward agree
# without any re-broadcasts.
#
# Two levels of blocking.  The *resident block* is what a BlockSpec
# brings into VMEM for one grid step: ``block_q`` query rows against
# ``block_k`` key rows (``_BLOCK_Q`` x ``_BLOCK_K``, 512 x 1024),
# large so that a call takes few grid steps.  Inside a
# step ``_walk_tiles`` runs the block as *compute tiles* of ``tile_q``
# x ``tile_k`` rows (``_compute_tile``), sliced from the refs with
# ``pl.ds``: two ``scf.for`` loops whose bounds follow from the grid
# indices.  Under a causal mask a tile wholly above the diagonal is
# never visited, a tile wholly below it is computed with no
# iota/compare/select, and only a tile the diagonal crosses is masked.
# Without a mask every tile is computed bare; with segment ids every
# tile is visited and masked.  The grid-level ``pl.when`` still skips a
# resident block that lies wholly above the diagonal.
#
# With a ``window`` (causal: a query sees the ``window`` keys up to its
# own, ``0 <= t - s < window``) the band is all there is.  The grid's
# last dimension then walks only the resident blocks the band of its
# block touches (``_band``: as many steps as the widest block needs, the
# index maps held at the last block of the band so that a step beyond it
# fetches nothing), and inside a block the tiles wholly below the band
# are never visited either; a tile the band's lower edge crosses is
# masked as one the diagonal crosses is.
# ---------------------------------------------------------------------------
def _packed_geometry(h: int, d: int):
    """lane-block width, heads per block, and group count — or None
    when the packed layout doesn't apply to this head size."""
    if d >= 128:
        if d % 128:
            return None
        lb, hpb = d, 1
    else:
        if 128 % d:
            return None
        hpb = 128 // d
        lb = 128
        if h % hpb:
            return None
    return lb, hpb, h // hpb


def _compute_tile(block_q: int, block_k: int, causal: bool,
                  has_seg: bool):
    """(tile_q, tile_k): the compute tile of a resident block.  Each
    divides its side of the block.

    Under a causal mask, 512 x 512: of the sizes tried on a v5e it is
    the one that beats the whole 512 x 1024 block in all three kernels
    (PERF.md section 6, PR 27: 519 against 572 us a forward call at
    b8 x s1024 x 16 heads x 64, 558 against 689 dq, 675 against 838
    dkv).  A kernel's time goes with the number of tiles it visits as
    well as with their area, so a narrower tile loses: 256 x 256 skips
    37.5 % of the square where 512 x 512 skips 25 %, and costs 805,
    657 and 825 us.  With nothing to skip (no mask, or segment ids,
    which are not skipped by) a tile is the block's query rows against
    up to 1024 keys, the widest the kernels have run with."""
    if has_seg or not causal:
        return block_q, math.gcd(block_k, 1024)
    return math.gcd(block_q, 512), math.gcd(block_k, 512)


def _tile_counts(sq: int, sk: int, tile_q: int, tile_k: int,
                 causal: bool, has_seg: bool, window: Optional[int] = None):
    """Compute tiles of one head's score square: (all, visited,
    masked), by the rule ``_walk_tiles`` follows."""
    nq, nk = sq // tile_q, sk // tile_k
    square = nq * nk
    if window is not None:
        # off: a tile's first query position less its first key
        off = (np.arange(nq)[:, None] * tile_q
               - np.arange(nk)[None, :] * tile_k)
        seen = (off >= -(tile_q - 1)) & (off <= window + tile_k - 2)
        bare = (off >= tile_k - 1) & (off <= window - tile_q)
        visited = int(seen.sum())
        return square, visited, visited if has_seg else visited - int(
            (seen & bare).sum())
    if has_seg:
        return square, square, square
    if not causal:
        return square, square, 0
    bare = sum(min((i * tile_q + 1) // tile_k, nk) for i in range(nq))
    visited = sum(min(-(-(i + 1) * tile_q // tile_k), nk)
                  for i in range(nq))
    return square, visited, visited - bare


def _note_tiles(heads: int, sq: int, sk: int, tile_q: int, tile_k: int,
                causal: bool, has_seg: bool,
                window: Optional[int] = None) -> None:
    """Counts, when a kernel call is traced, the compute tiles it will
    walk: ``flash_tiles_total{kind=square|visited|masked}``."""
    from ..observability import metrics as _obs_metrics
    reg = _obs_metrics.registry()
    counts = _tile_counts(sq, sk, tile_q, tile_k, causal, has_seg, window)
    for kind, n in zip(("square", "visited", "masked"), counts):
        reg.counter("flash_tiles_total",
                    "compute tiles of the packed flash kernels' score "
                    "squares, counted a head and call when the call is "
                    "traced: all of them, those visited, those masked",
                    labels={"kind": kind}).inc(heads * n)


def _band(by_key: bool, block_q: int, block_k: int, window: int,
          n_q: int = 0):
    """The resident blocks a window's band touches: ``(first, last)``,
    each from a block's index to the first and the last block of the
    other side that holds a pair of its band, for the key blocks of a
    query block, or ``by_key`` the query blocks of a key block (of
    ``n_q``, which nothing else reads).  They take an int32 of a kernel
    or an index map, or a numpy array of indices."""
    i32 = np.int32
    bq, bk = i32(block_q), i32(block_k)

    def div(a, b):      # never negative: truncation is the floor
        return a // b if isinstance(a, np.ndarray) else jax.lax.div(a, b)

    def least(a, b):
        return np.minimum(a, b) if isinstance(a, np.ndarray) \
            else jax.lax.min(a, b)

    if by_key:
        return (lambda kv: div(kv * bk, bq),
                lambda kv: least(div(kv * bk + i32(block_k + window - 2),
                                     bq), i32(n_q - 1)))
    return (lambda q: div(q * bq - least(q * bq, i32(window - 1)), bk),
            lambda q: div(q * bq + i32(block_q - 1), bk))


def _band_steps(by_key: bool, block_q: int, block_k: int, window: int,
                n_q: int, n_k: int) -> int:
    """Grid steps the widest band of a block takes."""
    first, last = _band(by_key, block_q, block_k, window, n_q)
    idx = np.arange(n_k if by_key else n_q, dtype=np.int32)
    return int((last(idx) - first(idx)).max()) + 1


def _band_block(step, outer, by_key: bool, block_q: int, block_k: int,
                window: Optional[int]):
    """The block of the other side a kernel's grid step stands at: the
    step itself with no window, else that many past the first block of
    ``outer``'s band (it may lie past the band's last: ``_walk_tiles``
    then runs nothing)."""
    if window is None:
        return step
    return _band(by_key, block_q, block_k, window)[0](outer) + step


def _walk_tiles(q_idx, kv_idx, q_rows, *, block_q: int, block_k: int,
                tile_q: int, tile_k: int, causal: bool, has_seg: bool,
                window: Optional[int] = None,
                q_blocks: Optional[int] = None):
    """Runs the resident block (``q_idx``, ``kv_idx``) as compute
    tiles.  ``q_rows(qs)`` opens the query rows ``qs .. qs + tile_q``
    of the block and returns ``k_cols(ks, off, masked)``, which
    computes them against the key rows ``ks .. ks + tile_k``; ``off``
    is the tile's first query position less its first key position, so
    a causal mask keeps ``row + off >= col``.  All indices are int32:
    the package-wide jax_enable_x64 makes a bare Python literal an i64
    that Mosaic refuses."""
    from jax.experimental import pallas as pl
    i32 = np.int32

    def loop(lo, hi, fn):
        """``fn(i)`` for i in lo .. hi - 1, as an ``scf.for``."""
        if not isinstance(hi, int):     # lo is an int32 too
            jax.lax.fori_loop(lo, hi,
                              lambda i, carry: (fn(i), carry)[1], i32(0))
        elif hi - lo == 1:      # no loop: the tile's slices stay static
            fn(i32(lo))
        else:                   # fori_loop would count these in i64

            def step(i, _):
                fn(i)
                return i + i32(1), None
            jax.lax.scan(step, i32(lo), None, length=hi - lo)

    nq, nk = block_q // tile_q, block_k // tile_k

    def q_tile(i):
        qs = pl.multiple_of(i * i32(tile_q), tile_q)
        k_cols = q_rows(qs)
        # first query position of the tile less the block's first key
        rel = q_idx * i32(block_q) + qs - kv_idx * i32(block_k)

        def run(lo, hi, masked):
            def k_tile(j):
                ks = pl.multiple_of(j * i32(tile_k), tile_k)
                k_cols(ks, rel - ks, masked)
            loop(lo, hi, k_tile)

        # every row of the tile sees the keys before rel + 1, none
        # sees those from rel + tile_q on
        def tiles_before(pos):
            return jax.lax.div(jax.lax.clamp(
                i32(0), pos, i32(block_k)), i32(tile_k))

        if window is not None:
            # the first row's first key is rel - window + 1; every row
            # sees the keys from rel + tile_q - window on
            first = tiles_before(rel - i32(window - 1))
            n_seen = tiles_before(rel + i32(tile_q + tile_k - 1))
            if has_seg:
                run(first, n_seen, True)
            else:
                n_bare = tiles_before(rel + i32(1))
                bare_from = jax.lax.min(n_bare, tiles_before(
                    rel + i32(tile_q + tile_k - 1 - window)))
                run(first, bare_from, True)
                run(bare_from, n_bare, False)
                run(n_bare, n_seen, True)
        elif has_seg:
            run(0, nk, True)
        elif not causal:
            run(0, nk, False)
        else:
            n_bare = tiles_before(rel + i32(1))
            n_seen = tiles_before(rel + i32(tile_q + tile_k - 1))
            run(i32(0), n_bare, False)
            run(n_bare, n_seen, True)

    if window is not None:
        # a resident block wholly above the diagonal or wholly below
        # the band holds no tile; a step past the last block neither
        q_first = q_idx * i32(block_q)
        k_first = kv_idx * i32(block_k)
        inside = (k_first <= q_first + i32(block_q - 1)) & (
            q_first - k_first < i32(window + block_k - 1))
        if q_blocks is not None:
            inside = inside & (q_idx < i32(q_blocks))

        @pl.when(inside)
        def _run_band():
            loop(0, nq, q_tile)
    elif causal and not has_seg:
        # a resident block wholly above the diagonal holds no tile
        @pl.when(kv_idx * block_k <= q_idx * block_q + block_q - 1)
        def _run():
            loop(0, nq, q_tile)
    else:
        loop(0, nq, q_tile)


def _tile_keep(off, tile_q: int, tile_k: int, causal: bool, q_ids,
               k_ids, by_key: bool = False, window: Optional[int] = None):
    """What a masked compute tile keeps: [tile_q, tile_k] bool, or
    [tile_k, tile_q] ``by_key`` (then ``q_ids`` is a row and ``k_ids``
    a column)."""
    keep = None
    if causal:
        shape = (tile_k, tile_q) if by_key else (tile_q, tile_k)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, shape, int(by_key))
        k_pos = jax.lax.broadcasted_iota(jnp.int32, shape, int(not by_key))
        keep = q_pos + off >= k_pos
        if window is not None:
            keep = keep & (q_pos + off < k_pos + np.int32(window))
    if q_ids is not None:
        same = q_ids == k_ids
        keep = same if keep is None else keep & same
    return keep


def _across(rows, width: int):
    """A row statistic held once in every lane, [n, 128], laid against
    a tile ``width`` columns wide: whole vregs reused, where a [n, 1]
    column would be broadcast along the lanes on every use."""
    if width <= _LANES:
        return rows[:, :width]
    return jnp.tile(rows, (1, width // _LANES))


def _lane_sums(x):
    """[n, 128] whose lanes add up to the row sums of ``x``: the
    128-column pieces added to each other, no lane crossed."""
    return functools.reduce(
        jnp.add, (x[:, j:j + _LANES] for j in range(0, x.shape[1], _LANES)))


def _flash_packed_fwd_kernel(*refs, scale: float, causal: bool,
                             block_q: int, block_k: int, tile_q: int,
                             tile_k: int, seq_k: int, d: int, hpb: int,
                             has_seg: bool, window: Optional[int] = None,
                             steps: Optional[int] = None):
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, \
            m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        qs_ref = ks_ref = None

    q_idx = pl.program_id(1)
    step = pl.program_id(2)
    kv_idx = _band_block(step, q_idx, False, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def q_rows(qs):
        rows = pl.ds(qs, tile_q)
        q_blk = q_ref[0, rows, :]
        q_ids = qs_ref[0, rows, :][:, :1] if has_seg else None

        def k_cols(ks, off, masked):
            cols = pl.ds(ks, tile_k)
            k_blk = k_ref[0, cols, :]
            v_blk = v_ref[0, cols, :]
            keep = _tile_keep(
                off, tile_q, tile_k, causal, q_ids,
                ks_ref[0, :, cols][:1, :] if has_seg else None,
                window=window) if masked else None
            for hh in range(hpb):
                dsl = slice(hh * d, (hh + 1) * d)
                lsl = slice(hh * _LANES, (hh + 1) * _LANES)
                v = v_blk[:, dsl]
                s = jax.lax.dot_general(
                    q_blk[:, dsl], k_blk[:, dsl],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if keep is not None:
                    s = jnp.where(keep, s, -jnp.inf)
                # m in every lane of its row; l as 128 partial sums a
                # row, added up once, in _finish
                m_prev = m_scr[rows, lsl]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                m_safe = jnp.maximum(m_new, _LSE_FLOOR)
                p = jnp.exp(s - _across(m_safe, tile_k))
                alpha = jnp.exp(jnp.maximum(m_prev, _LSE_FLOOR) - m_safe)
                l_scr[rows, lsl] = alpha * l_scr[rows, lsl] + _lane_sums(p)
                acc_scr[rows, dsl] = \
                    acc_scr[rows, dsl] * _across(alpha, d) + \
                    jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_scr[rows, lsl] = m_new
        return k_cols

    _walk_tiles(q_idx, kv_idx, q_rows, block_q=block_q, block_k=block_k,
                tile_q=tile_q, tile_k=tile_k, causal=causal,
                has_seg=has_seg, window=window)

    n_kv = steps or seq_k // block_k

    @pl.when(step == n_kv - 1)
    def _finish():
        # assemble the full lane-block then write each ref ONCE —
        # ref[0][:, sl] = x is a chained setitem on a VALUE, not a ref
        # write, and fails (verified in interpret mode)
        o_cols = []
        lse_cols = []
        for hh in range(hpb):
            dsl = slice(hh * d, (hh + 1) * d)
            lsl = slice(hh * _LANES, (hh + 1) * _LANES)
            l_fin = jnp.maximum(
                jnp.sum(l_scr[:, lsl], axis=-1, keepdims=True), 1e-30)
            o_cols.append((acc_scr[:, dsl] / l_fin).astype(o_ref.dtype))
            lse = jnp.maximum(m_scr[:, lsl], _LSE_FLOOR) + jnp.log(l_fin)
            lse_cols.append(_across(lse, d))
        o_ref[0] = jnp.concatenate(o_cols, axis=-1)
        lse_ref[0] = jnp.concatenate(lse_cols, axis=-1)


def _flash_packed_bwd_dq_kernel(*refs, scale: float, causal: bool,
                                block_q: int, block_k: int, tile_q: int,
                                tile_k: int, seq_k: int, d: int,
                                hpb: int, has_seg: bool,
                                window: Optional[int] = None,
                                steps: Optional[int] = None):
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, qs_ref, ks_ref, \
            dq_ref, dq_scr, delta_scr = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, \
            dq_scr, delta_scr = refs
        qs_ref = ks_ref = None

    q_idx = pl.program_id(1)
    step = pl.program_id(2)
    kv_idx = _band_block(step, q_idx, False, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        for hh in range(hpb):
            dsl = slice(hh * d, (hh + 1) * d)
            lsl = slice(hh * _LANES, (hh + 1) * _LANES)
            d_row = jnp.sum(do_ref[0][:, dsl].astype(jnp.float32)
                            * o_ref[0][:, dsl].astype(jnp.float32),
                            axis=-1, keepdims=True)
            delta_scr[:, lsl] = jnp.broadcast_to(d_row,
                                                 (block_q, _LANES))

    def q_rows(qs):
        rows = pl.ds(qs, tile_q)
        q_blk = q_ref[0, rows, :]
        do_blk = do_ref[0, rows, :]
        lse_blk = lse_ref[0, rows, :]
        q_ids = qs_ref[0, rows, :][:, :1] if has_seg else None

        def k_cols(ks, off, masked):
            cols = pl.ds(ks, tile_k)
            k_blk = k_ref[0, cols, :]
            v_blk = v_ref[0, cols, :]
            keep = _tile_keep(
                off, tile_q, tile_k, causal, q_ids,
                ks_ref[0, :, cols][:1, :] if has_seg else None,
                window=window) if masked else None
            for hh in range(hpb):
                dsl = slice(hh * d, (hh + 1) * d)
                lsl = slice(hh * _LANES, (hh + 1) * _LANES)
                k = k_blk[:, dsl]
                lse = lse_blk[:, hh * d:hh * d + 1]
                delta = delta_scr[rows, lsl][:, :1]
                s = jax.lax.dot_general(
                    q_blk[:, dsl], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if keep is not None:
                    s = jnp.where(keep, s, -jnp.inf)
                p = jnp.exp(s - lse)
                dp = jax.lax.dot_general(
                    do_blk[:, dsl], v_blk[:, dsl],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = (p * (dp - delta) * scale).astype(k.dtype)
                dq_scr[rows, dsl] += jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return k_cols

    _walk_tiles(q_idx, kv_idx, q_rows, block_q=block_q, block_k=block_k,
                tile_q=tile_q, tile_k=tile_k, causal=causal,
                has_seg=has_seg, window=window)

    n_kv = steps or seq_k // block_k

    @pl.when(step == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_packed_bwd_dkv_kernel(*refs, scale: float, causal: bool,
                                 block_q: int, block_k: int,
                                 tile_q: int, tile_k: int, seq_q: int,
                                 d: int, hpb: int, has_seg: bool,
                                 window: Optional[int] = None,
                                 steps: Optional[int] = None):
    """Scores are computed by key, [tile_k, tile_q]: p and ds then stand
    as the left operands of the dv and dk products with nothing to
    transpose, which took a call at [8, 1024, 16 x 64] from 738 to 675
    us (PERF.md section 6, PR 27).  The query rows' statistics (lse,
    delta) are turned into rows once a query tile; the segment ids come
    in the layouts that fit, keys down the sublanes and queries along
    the lanes."""
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, ks_ref, qs_ref, \
            dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref, \
            dk_scr, dv_scr = refs
        qs_ref = ks_ref = None

    kv_idx = pl.program_id(1)
    step = pl.program_id(2)
    q_idx = _band_block(step, kv_idx, True, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def q_rows(qs):
        rows = pl.ds(qs, tile_q)
        q_blk = q_ref[0, rows, :]
        do_blk = do_ref[0, rows, :]
        # [lanes, tile_q]: a head's lse in each of its d rows, and the
        # addends of its delta down them
        lse_t = lse_ref[0, rows, :].T
        delta_t = (do_blk.astype(jnp.float32)
                   * o_ref[0, rows, :].astype(jnp.float32)).T
        stats = [(lse_t[hh * d:hh * d + 1, :],
                  jnp.sum(delta_t[hh * d:(hh + 1) * d, :], axis=0,
                          keepdims=True)) for hh in range(hpb)]
        q_ids = qs_ref[0, :, rows][:1, :] if has_seg else None

        def k_cols(ks, off, masked):
            cols = pl.ds(ks, tile_k)
            k_blk = k_ref[0, cols, :]
            v_blk = v_ref[0, cols, :]
            keep = _tile_keep(
                off, tile_q, tile_k, causal, q_ids,
                ks_ref[0, cols, :][:, :1] if has_seg else None,
                by_key=True, window=window) if masked else None
            for hh in range(hpb):
                dsl = slice(hh * d, (hh + 1) * d)
                q = q_blk[:, dsl]
                do = do_blk[:, dsl]
                lse, delta = stats[hh]
                s = jax.lax.dot_general(
                    k_blk[:, dsl], q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if keep is not None:
                    s = jnp.where(keep, s, -jnp.inf)
                p = jnp.exp(s - lse)
                dv_scr[cols, dsl] += jax.lax.dot_general(
                    p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(
                    v_blk[:, dsl], do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = (p * (dp - delta) * scale).astype(q.dtype)
                dk_scr[cols, dsl] += jax.lax.dot_general(
                    ds, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return k_cols

    _walk_tiles(q_idx, kv_idx, q_rows, block_q=block_q, block_k=block_k,
                tile_q=tile_q, tile_k=tile_k, causal=causal,
                has_seg=has_seg, window=window,
                q_blocks=seq_q // block_q)

    n_q = steps or seq_q // block_q

    @pl.when(step == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _packed_index_maps(g: int):
    """Index maps of the packed grids, whose leading index fuses
    (batch, lane-group) as ``bg = b * g + group``.  The split is
    spelled with lax.div/rem on an int32 constant: ``bg // g`` makes
    the Python int an i64 operand under the package-wide
    jax_enable_x64, which Mosaic cannot lower (grid indices are never
    negative, so truncating division is floor division here).

    ``at`` is the grid position (1 or 2) whose index walks the sequence
    dim.  ``block(at)`` is the index map of a [B, S, H*D] tensor;
    ``block(at, band)`` that of the side a window's band grid walks:
    ``band = (outer_at, first, last)`` (``_band``), the block at
    position ``at`` counted from the first of the outer block's band and
    held at its last.
    ``seg_down(ids, n, at)`` and ``seg_along(ids, n, at)`` lay [B, S]
    segment ids down the sublanes, [B, S, LANES], or along the lanes,
    [B, SUBLANES, S], ``n`` of them a grid step: the array and its block
    spec."""
    from jax.experimental import pallas as pl
    g32 = np.int32(g)

    def along(at, band):
        """The sequence's block a grid position stands at."""
        if band is None:
            return lambda idx: idx[at]
        outer_at, first, last = band
        return lambda idx: jax.lax.min(first(idx[outer_at]) + idx[at],
                                       last(idx[outer_at]))

    def block(at, band=None):
        at_ = along(at, band)
        return lambda *idx: (jax.lax.div(idx[0], g32), at_(idx),
                             jax.lax.rem(idx[0], g32))

    def seg_down(ids, n, at, band=None):
        at_ = along(at, band)
        return (jax.lax.broadcast_in_dim(ids, ids.shape + (_LANES,),
                                         (0, 1)),
                pl.BlockSpec((1, n, _LANES), lambda *idx: (
                    jax.lax.div(idx[0], g32), at_(idx), idx[0] * 0)))

    def seg_along(ids, n, at, band=None):
        at_ = along(at, band)
        return (jax.lax.broadcast_in_dim(
            ids, (ids.shape[0], _SUBLANES, ids.shape[1]), (0, 2)),
            pl.BlockSpec((1, _SUBLANES, n), lambda *idx: (
                jax.lax.div(idx[0], g32), idx[0] * 0, at_(idx))))

    return block, seg_down, seg_along


class _PackedPlan(NamedTuple):
    """What one packed kernel call is built from, beside its arrays:
    hashable, so that it can be a static argument."""
    heads: int
    d: int
    causal: bool
    has_seg: bool
    block_q: int
    block_k: int
    tile_q: int
    tile_k: int
    window: Optional[int] = None

    def kernel_args(self) -> dict:
        return dict(scale=1.0 / math.sqrt(self.d), causal=self.causal,
                    block_q=self.block_q, block_k=self.block_k,
                    tile_q=self.tile_q, tile_k=self.tile_k, d=self.d,
                    hpb=_packed_geometry(self.heads, self.d)[1],
                    has_seg=self.has_seg)

    def band(self, by_key: bool, n_q: int, n_k: int) -> dict:
        """With a window, what a kernel of the band grid is given beside
        ``kernel_args``: the window and the steps of its last grid
        dimension."""
        if self.window is None:
            return {}
        return dict(window=self.window, steps=_band_steps(
            by_key, self.block_q, self.block_k, self.window, n_q, n_k))


def _packed_plan(b, sq, sk, h, d, causal, has_seg, block_q, block_k, tile,
                 default_block_k: int = _BLOCK_K,
                 window: Optional[int] = None) -> _PackedPlan:
    """Resident block and compute tile of one kernel call at this
    shape.  ``block_q``/``block_k``/``tile`` override the defaults
    (tests, sweeps).  Counts the call's tiles.  A block is at least 128
    rows, which divides every sequence the packed path takes
    (``_attention_form``): the dkv kernel lays the queries along the
    lanes, the other two the keys."""
    block_q = _fit_block(sq, max(_LANES, block_q or _BLOCK_Q))
    block_k = _fit_block(sk, max(_LANES, block_k or default_block_k))
    tile_q, tile_k = tile or _compute_tile(
        block_q, block_k, causal, has_seg and window is None)
    if block_q % tile_q or block_k % tile_k or tile_q % _LANES \
            or tile_k % _LANES:
        raise ValueError(
            f"compute tile {tile_q} x {tile_k} must divide the resident "
            f"block {block_q} x {block_k} (block_q x block_k, fitted to "
            f"the sequence) in whole groups of {_LANES} rows")
    _note_tiles(b * h, sq, sk, tile_q, tile_k, causal, has_seg, window)
    return _PackedPlan(h, d, causal, has_seg, block_q, block_k, tile_q,
                       tile_k, window)


def _pallas_flash_packed(q, k, v, h, d, q_seg=None, k_seg=None, *,
                         causal: bool, block_q: Optional[int] = None,
                         block_k: Optional[int] = None, tile=None,
                         window: Optional[int] = None):
    """q [B, Sq, H*D]; k/v [B, Sk, H*D] → (out [B, Sq, H*D],
    lse [B, Sq, H*D] f32, per-head value broadcast over its d lanes).
    Segment ids are [B, S*] (NOT per-head — the packed grid reuses one
    mask per lane-group)."""
    plan = _packed_plan(q.shape[0], q.shape[1], k.shape[1], h, d, causal,
                        q_seg is not None, block_q, block_k, tile,
                        window=window)
    return _flash_packed_fwd_call(q, k, v, q_seg, k_seg, plan=plan,
                                  interpret=_interpret())


# The calls themselves are jitted with everything the environment
# decides passed in as static arguments: the layers of a model share one
# trace and one Mosaic lowering of each kernel, where every call site
# would otherwise build its own (a step of 24 layers holds 72; the
# first step of gpt2-medium over a warm compile cache took 12.5 s
# against 17.3, PERF.md section 6, PR 27).
@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _flash_packed_fwd_call(q, k, v, q_seg, k_seg, *, plan: _PackedPlan,
                           interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, hd = q.shape
    sk = k.shape[1]
    lb, hpb, g = _packed_geometry(plan.heads, plan.d)
    block_q, block_k = plan.block_q, plan.block_k

    block, seg_down, seg_along = _packed_index_maps(g)
    # grid (b*g, q, kv) — kv minor; with a window the kv blocks of the
    # query block's band
    band = plan.band(False, sq // block_q, sk // block_k)
    keys = (1,) + _band(False, block_q, block_k, plan.window) \
        if band else None
    qspec = pl.BlockSpec((1, block_q, lb), block(1))
    kspec = pl.BlockSpec((1, block_k, lb), block(2, keys))
    segs = [seg_down(q_seg, block_q, 1),
            seg_along(k_seg, block_k, 2, keys)] if plan.has_seg else []
    out, lse = pl.pallas_call(
        functools.partial(_flash_packed_fwd_kernel, seq_k=sk,
                          **plan.kernel_args(), **band),
        grid=(b * g, sq // block_q, band.get("steps", sk // block_k)),
        in_specs=[qspec, kspec, kspec] + [spec for _, spec in segs],
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, sq, hd), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, hpb * _LANES), jnp.float32),
            pltpu.VMEM((block_q, hpb * _LANES), jnp.float32),
            pltpu.VMEM((block_q, lb), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *[ids for ids, _ in segs])
    return out, lse


def _pallas_flash_packed_bwd(q, k, v, out, lse, do, h, d, q_seg=None,
                             k_seg=None, *, causal: bool,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None, tile=None,
                             window: Optional[int] = None):
    shape = (q.shape[0], q.shape[1], k.shape[1], h, d, causal,
             q_seg is not None, block_q, block_k, tile)
    # dq keeps up to 2048 keys resident: with one key block the K/V of
    # a lane-group are fetched once and not once a query block, which
    # took a dq call at [2, 2048, 8 x 128] from 334 to 234 us and did
    # nothing for the other two kernels (PERF.md section 6, PR 27); the
    # compute tile, not the resident block, bounds the temporaries.
    return _flash_packed_bwd_call(
        q, k, v, out, lse, do, q_seg, k_seg,
        dq_plan=_packed_plan(*shape, default_block_k=2048, window=window),
        dkv_plan=_packed_plan(*shape, window=window),
        interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("dq_plan", "dkv_plan", "interpret"))
def _flash_packed_bwd_call(q, k, v, out, lse, do, q_seg, k_seg, *,
                           dq_plan: _PackedPlan, dkv_plan: _PackedPlan,
                           interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, hd = q.shape
    sk = k.shape[1]
    lb, hpb, g = _packed_geometry(dq_plan.heads, dq_plan.d)
    args = [q, k, v, do, out, lse]
    block, seg_down, seg_along = _packed_index_maps(g)

    def specs(plan, q_at, k_at):
        """Block specs of the six tensors for a grid whose position
        ``q_at`` walks the queries and ``k_at`` the keys, the band grid's
        steps and what its last position walks (None: no window)."""
        by_key = k_at == 1
        band = plan.band(by_key, sq // plan.block_q, sk // plan.block_k)
        walked = (1,) + _band(by_key, plan.block_q, plan.block_k,
                              plan.window, sq // plan.block_q) \
            if band else None
        qspec = pl.BlockSpec((1, plan.block_q, lb),
                             block(q_at, walked if by_key else None))
        kspec = pl.BlockSpec((1, plan.block_k, lb),
                             block(k_at, None if by_key else walked))
        return (qspec, kspec, [qspec, kspec, kspec, qspec, qspec, qspec],
                band, walked)

    # dq pass: grid (b*g, q, kv) — kv minor
    qspec, _, in_specs, band, walked = specs(dq_plan, 1, 2)
    segs = [seg_down(q_seg, dq_plan.block_q, 1),
            seg_along(k_seg, dq_plan.block_k, 2, walked)] \
        if dq_plan.has_seg else []
    dq = pl.pallas_call(
        functools.partial(_flash_packed_bwd_dq_kernel, seq_k=sk,
                          **dq_plan.kernel_args(), **band),
        grid=(b * g, sq // dq_plan.block_q,
              band.get("steps", sk // dq_plan.block_k)),
        in_specs=in_specs + [spec for _, spec in segs],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dq_plan.block_q, lb), jnp.float32),
            pltpu.VMEM((dq_plan.block_q, hpb * _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*args, *[ids for ids, _ in segs])

    # dkv pass: grid (b*g, kv, q) — q minor; scores by key, so the
    # keys' ids go down the sublanes and the queries' along the lanes
    _, kspec, in_specs, band, walked = specs(dkv_plan, 2, 1)
    segs = [seg_down(k_seg, dkv_plan.block_k, 1),
            seg_along(q_seg, dkv_plan.block_q, 2, walked)] \
        if dkv_plan.has_seg else []
    dk, dv = pl.pallas_call(
        functools.partial(_flash_packed_bwd_dkv_kernel, seq_q=sq,
                          **dkv_plan.kernel_args(), **band),
        grid=(b * g, sk // dkv_plan.block_k,
              band.get("steps", sq // dkv_plan.block_q)),
        in_specs=in_specs + [spec for _, spec in segs],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b, sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((dkv_plan.block_k, lb), jnp.float32),
                        pltpu.VMEM((dkv_plan.block_k, lb), jnp.float32)],
        interpret=interpret,
    )(*args, *[ids for ids, _ in segs])
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core_packed(q, k, v, q_seg, k_seg, causal, h, d, window=None):
    out, _ = _flash_packed_fwd(q, k, v, q_seg, k_seg, causal, h, d, window)
    return out


def _flash_packed_fwd(q, k, v, q_seg, k_seg, causal, h, d, window):
    out, lse = _pallas_flash_packed(
        q, k, v, h, d, _seg_or_none(q_seg), _seg_or_none(k_seg),
        causal=causal, window=window)
    return out, (q, k, v, out, lse, q_seg, k_seg)


def _flash_packed_bwd(causal, h, d, window, res, g):
    q, k, v, out, lse, q_seg, k_seg = res
    dq, dk, dv = _pallas_flash_packed_bwd(
        q, k, v, out, lse, g, h, d, _seg_or_none(q_seg),
        _seg_or_none(k_seg), causal=causal, window=window)
    return dq, dk, dv, _int_zero_ct(q_seg), _int_zero_ct(k_seg)


_flash_core_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


# ---------------------------------------------------------------------------
# Which form runs — decided in ``_attention_form`` and nowhere else, from
# platform and shape.  A kernel the compiler refuses is a compile error
# in the user's step, never a quiet hand-over to the composed form.
# ---------------------------------------------------------------------------
def _kernels_enabled() -> bool:
    """True where the Pallas kernels run at all: on a TPU (or under the
    interpreter, for tests), unless the user opted out."""
    if env_knobs.get_raw("PADDLE_TPU_DISABLE_PALLAS"):
        return False
    return _on_tpu() or _interpret()


def _attention_form(h: int, d: int, sq: int, sk: int) -> Optional[str]:
    """The form of dropout-free attention on the [b, sq | sk, h, d]
    arrays one device holds: ``"packed"`` (the transpose-free kernels),
    ``"bh"`` (the [BH, S, D] kernels) or None, the composed form.

    A kernel takes sequences of whole 128-row groups with at least 256
    queries (128 under the interpreter, which lets the tests stay
    small); the benchmark's cell gpt2m-short-s128 is the composed form
    at work.  The packed kernels take the heads that fill 128-lane
    groups (``_packed_geometry``), the [BH, S, D] kernels the rest: a
    head width that neither divides 128 nor is a multiple of it, or a
    head count that leaves a group part empty."""
    if not _kernels_enabled():
        return None
    min_s = 128 if _interpret() else 256
    if sq < min_s or sq % 128 or sk % 128:
        return None
    return "packed" if _packed_geometry(h, d) is not None else "bh"


# ---------------------------------------------------------------------------
# Composed XLA form — numerics oracle, dropout path, and the form used
# where no kernel is eligible (non-TPU backends, unaligned shapes)
# ---------------------------------------------------------------------------
def _flash_reference(q, k, v, causal, q_seg=None, k_seg=None,
                     dropout_key=None, dropout_p=0.0, window=None):
    """Composed attention on [BH,Sq,D]/[BH,Sk,D]; with a ``window``
    (causal) a query sees the ``window`` keys up to its own."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                                    -int(window))
        s = jnp.where(mask, s, -jnp.inf)
    if q_seg is not None:
        s = jnp.where(q_seg[:, :, None] == k_seg[:, None, :], s, -jnp.inf)
    # fully-masked rows (varlen padding) produce a 0 output, not NaN
    lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(lse, _LSE_FLOOR))
    if dropout_key is not None and dropout_p > 0.0:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), jnp.zeros_like(p))
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _seg_or_none(seg):
    """The sentinel for 'no segment ids' is a 0-sized int array (its
    size is static under tracing, so this is a trace-time dispatch)."""
    return seg if seg is not None and seg.size else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_core(q, k, v, q_seg, k_seg, causal, kernel, window=None):
    """Attention on [BH, S, D] by the kernels (``kernel``, the caller's
    ``_attention_form``) or in the composed form, whose backward
    recomputes the probabilities rather than keep them.  A ``window`` is
    the composed form's: the [BH, S, D] kernels have none."""
    out, _ = _flash_fwd(q, k, v, q_seg, k_seg, causal, kernel, window)
    return out


def _flash_fwd(q, k, v, q_seg, k_seg, causal, kernel, window):
    qs, ks = _seg_or_none(q_seg), _seg_or_none(k_seg)
    if kernel:
        out, lse = _pallas_flash_bh(q, k, v, qs, ks, causal=causal)
    else:
        out = _flash_reference(q, k, v, causal, qs, ks, window=window)
        # the composed backward recomputes: it is handed no lse
        lse = jnp.zeros((0,), jnp.float32)
    return out, (q, k, v, out, lse, q_seg, k_seg)


def _int_zero_ct(x):
    """Symbolic-zero cotangent for integer primals (jax float0)."""
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


def _flash_bwd(causal, kernel, window, res, g):
    q, k, v, out, lse, q_seg, k_seg = res
    qs, ks = _seg_or_none(q_seg), _seg_or_none(k_seg)
    if kernel:    # block-streaming backward, no [S,S] in HBM
        dq, dk, dv = _pallas_flash_bwd(q, k, v, out, lse, g, qs, ks,
                                       causal=causal)
    else:         # composed forward: differentiate the composed form
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _flash_reference(q_, k_, v_, causal,
                                                qs, ks, window=window),
            q, k, v)
        dq, dk, dv = vjp(g)
    return (dq, dk, dv, _int_zero_ct(q_seg), _int_zero_ct(k_seg))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _heads_to_batch(x):
    """[B, S, H, D] → [B*H, S, D] (a physical transpose)."""
    b, s, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)


def _batch_to_heads(x, b):
    """[B*H, S, D] → [B, S, H, D]."""
    bh, s, d = x.shape
    return jnp.moveaxis(x.reshape(b, bh // b, s, d), 1, 2)


def _flash_local(query, key, value, qseg, kseg, *, causal, window=None):
    """Dropout-free attention on the arrays one device holds:
    [b, S, h, D] in and out, ``qseg``/``kseg`` [b, S*] int32 or the
    0-sized 'none' sentinel.  Picks the kernel layout from the local
    shape; a ``window`` is walked by the packed kernels and masked by
    the composed form, and by nothing else."""
    b, sq, h, d = query.shape
    sk = key.shape[1]
    form = _attention_form(h, d, sq, sk)
    if window is not None and form == "bh":
        form = None
    if form == "packed":
        # transpose-free path: [B,S,H,D] → [B,S,H*D] is a free reshape;
        # segment ids stay [B, S] (one mask per lane-group)
        out = _flash_core_packed(
            query.reshape(b, sq, h * d), key.reshape(b, sk, h * d),
            value.reshape(b, sk, h * d), qseg, kseg, causal, h, d,
            window)
        return out.reshape(b, sq, h, d)
    if qseg.size:
        qseg, kseg = jnp.repeat(qseg, h, axis=0), jnp.repeat(kseg, h, axis=0)
    out = _flash_core(_heads_to_batch(query), _heads_to_batch(key),
                      _heads_to_batch(value), qseg, kseg, causal,
                      form == "bh", window)
    return _batch_to_heads(out, b)


def _per_device(fn, b: int, h: int, has_seg: bool):
    """``fn`` wrapped so that, under a mesh of several devices, each
    device runs it on its own block: batch split over the mesh's data
    axes and heads over 'mp', where those divide.  Mosaic kernels
    cannot be partitioned by GSPMD, so the implicit-SPMD train step
    must hand them per-device shapes itself.  Returns ``fn`` unchanged
    on one device and inside a shard_map (every in-repo shard_map
    binds the whole mesh, so the caller is per-device already)."""
    from ..distributed import collective as coll
    mesh = coll.get_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return fn
    from jax.sharding import PartitionSpec as P
    from ..distributed.shard_map_compat import shard_map
    daxes = coll.data_axes(mesh)
    if daxes and b % int(np.prod([mesh.shape[a] for a in daxes])):
        daxes = ()
    mp = int(mesh.shape.get("mp", 1))
    head_ax = "mp" if mp > 1 and h % mp == 0 else None
    # trailing dims are left out of the specs: unnamed means unsharded
    qkv = P(daxes or None, None, head_ax)
    seg = P(daxes or None) if has_seg else P()
    return shard_map(fn, mesh=mesh, in_specs=(qkv, qkv, qkv, seg, seg),
                     out_specs=qkv, check_vma=False)


@primitive(name="flash_attention")
def flash_attention(query, key, value, causal=False, dropout=0.0,
                    training=True, segment_ids=None, kv_segment_ids=None,
                    window=None):
    """[B, S, H, D] in/out, paddle flash_attention convention.

    ``window`` (with ``causal``): a query sees the ``window`` keys up to
    and with its own, ``0 <= t - s < window``.  The packed kernels then
    visit only the tiles the band touches; every other form masks.

    ``key``/``value`` may have fewer heads (GQA/MQA).  ``segment_ids``
    [B, Sq] / ``kv_segment_ids`` [B, Sk] mask attention across packed
    sequences (upstream flash_attn_varlen parity); when only
    ``segment_ids`` is given and Sq == Sk it is used for both sides.
    """
    from ._primitive import unwrap
    segment_ids = unwrap(segment_ids)
    kv_segment_ids = unwrap(kv_segment_ids)
    b, sq, hq, d = query.shape
    sk, hkv = key.shape[1], key.shape[2]
    if causal and sq != sk:
        raise ValueError(
            f"causal flash_attention requires Sq == Sk, got {sq} vs {sk}")
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError(
                "flash_attention: a window is a causal band of at least "
                f"one key, got causal={causal}, window={window}")
    if hq != hkv:
        if hq % hkv != 0:
            raise ValueError(
                f"GQA requires query heads ({hq}) divisible by kv heads "
                f"({hkv})")
        # NOTE: correctness-first GQA — K/V are materialised at Hq heads
        # before the kernel.  The bandwidth-optimal form maps the kernel
        # batch-grid index b -> b // rep in the K/V BlockSpecs (and
        # group-sums dK/dV); tracked as a perf follow-up.
        rep = hq // hkv
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)

    qseg = kseg = None
    if segment_ids is not None:
        qseg = jnp.asarray(segment_ids, jnp.int32)
        kseg = (jnp.asarray(kv_segment_ids, jnp.int32)
                if kv_segment_ids is not None else qseg)
        if kseg.shape[1] != sk:
            raise ValueError(
                f"kv_segment_ids length {kseg.shape[1]} != Sk {sk}")

    if dropout > 0.0 and training:
        # dropout path: composed XLA form (correct semantics; the
        # streaming kernel covers the dropout-free configuration)
        _warn_once(
            "flash_dropout",
            "flash_attention(dropout>0) runs the composed XLA attention "
            "(dropout is fused by XLA); the streaming Pallas kernel is "
            "used when dropout == 0.")
        qs = None if qseg is None else jnp.repeat(qseg, hq, axis=0)
        ks = None if kseg is None else jnp.repeat(kseg, hq, axis=0)
        out = _flash_reference(
            _heads_to_batch(query), _heads_to_batch(key),
            _heads_to_batch(value), causal, qs, ks,
            dropout_key=_random.next_key(), dropout_p=float(dropout),
            window=window)
        return _batch_to_heads(out, b)

    local = functools.partial(_flash_local, causal=causal, window=window)
    if _kernels_enabled():
        # the form follows from the shape a device holds, so the split
        # comes first (_flash_local asks _attention_form inside it)
        local = _per_device(local, b, hq, qseg is not None)
    empty = jnp.zeros((0,), jnp.int32)
    return local(query, key, value,
                 qseg if qseg is not None else empty,
                 kseg if kseg is not None else empty)
