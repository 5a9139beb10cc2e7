"""The gated delta rule of Kimi Delta Attention (KDA; Kimi Linear,
arXiv:2510.26692): a linear attention whose state a delta rule corrects
and a gate decays, one decay for each key channel.  For one head, from
``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q, k [S, H, K]``, ``v [S, H, V]``, ``log_alpha [S, H, K]`` (at most 0),
``beta [S, H]``.  The transition is not diagonal, so Mamba's scans do not
compute it.

The chunked form (chunk C, the sequence in whole chunks).  Inside a chunk
``g_t`` is the sum of ``log_alpha`` over the chunk's positions up to and
including t, and with ``u_t`` the rows the chunk adds to the state:

    S_t = Diag(e^{g_t}) S_0 + sum_{s <= t} Diag(e^{g_t - g_s}) k_s u_s^T
    (I + A) U = beta V - beta (K e^g) S_0,
        A_ts = beta_t sum_i k_ti k_si e^{g_ti - g_si}       (s < t)
    O = (Q e^g) S_0 + M U,   M_ts = sum_i q_ti k_si e^{g_ti - g_si} (s <= t)
    S_C = Diag(e^{g_C}) S_0 + (K e^{g_C - g})^T U

``T = (I + A)^-1`` is a triangular solve (the WY, or UT, transform): with
``W = T (beta K e^g)`` and ``Ũ = T (beta V)``, ``U = Ũ - W S_0``.  A, M, W
and Ũ depend on the chunk alone and are made for every chunk at once; only
the state walks from chunk to chunk (``lax.scan``, one matrix product of
``[C, K] x [K, V]`` and one of ``[K, C] x [C, V]`` a head), and the
outputs are made from the states the chunks start from, again for every
chunk at once.

Every decay is the exponential of a difference of the sums, never of a
sum alone: ``e^{-g_s}`` overflows float32 once a chunk's decay passes
e^-88, which a steep gate reaches in a few positions.  The pairs of two
positions are taken in sub-chunks of 16: between sub-chunks through the
first position ``r`` of the later one, ``e^{g_t - g_s} = e^{g_t - g_r}
e^{g_r - g_s}`` with both exponents at most 0, so a pair is two matrix
products; inside a sub-chunk pair by pair.  All sums are float32, every
product at ``HIGHEST`` precision.

The rule has two forms, and :func:`rule_form` names the one that runs,
from platform and shape: on a TPU (or under the interpreter), for heads
whose keys and values fill lane groups and a sequence of whole chunks,
the Mosaic kernels of ``ops/delta_rule_kernels.py`` (a head's state in
VMEM from the first chunk to the last, the pairs and the solve inside the
visit, the forward pass one call and the walk back one call); everywhere
else (the CPU, odd shapes) the XLA operations below, whose backward pass
is jax's differentiation of the chunked form, through the triangular
solve and the scan.  The calls count themselves as they are traced, a
forward pass recomputed under ``jax.checkpoint`` once more:
``delta_rule_calls_total``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import delta_rule_kernels, pallas_ops

CHUNK = 64
SUB = 16
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _note_call() -> None:
    from ..observability import metrics
    metrics.registry().counter(
        "delta_rule_calls_total",
        "calls of the gated delta rule, counted when the call is traced").inc()


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def _by_chunk(a, chunk: int):
    """``[S, H, ...]`` -> ``[H, S / chunk, chunk, ...]``."""
    seq, heads = a.shape[:2]
    a = a.reshape((seq // chunk, chunk, heads) + a.shape[2:])
    return jnp.moveaxis(a, 2, 0)


@jax.checkpoint
def _pairs_within(a, b, g):
    """``P[t, s] = sum_i a_ti b_si e^{g_ti - g_si}`` for ``s <= t`` inside
    each sub-chunk, 0 elsewhere: ``a, b, g [..., n, B, K]`` ->
    ``[..., n, B, B]``.  Kept: its inputs; the ``[B, B, K]`` decays are
    made again in the backward pass."""
    size = a.shape[-2]
    below = jnp.tril(jnp.ones((size, size), bool))[:, :, None]
    gap = jnp.where(below, g[..., :, None, :] - g[..., None, :, :], 0.0)
    return jnp.where(below[..., 0], jnp.sum(
        a[..., :, None, :] * b[..., None, :, :] * jnp.exp(gap), -1), 0.0)


def _pairs(a, b, g):
    """``P [..., C, C]``, ``P[t, s] = sum_i a_ti b_si e^{g_ti - g_si}`` for
    ``s <= t`` and 0 above, from ``a, b, g [..., C, K]``."""
    chunk, width = g.shape[-2:]
    sub = min(SUB, chunk)
    n = chunk // sub
    lead = g.shape[:-2]
    split = lambda x: x.reshape(lead + (n, sub, width))          # noqa: E731
    a_, b_, g_ = split(a), split(b), split(g)
    ref = g_[..., :1, :]                          # [..., n, 1, K]: g_r
    # between sub-chunks: rows of sub-chunk m against every earlier column
    rows = a_ * jnp.exp(g_ - ref)                 # e^{g_t - g_r} <= 1
    earlier = (jnp.arange(chunk)[None, :] < sub * jnp.arange(n)[:, None])
    gap = jnp.where(earlier[..., None], ref - g[..., None, :, :], 0.0)
    cols = jnp.where(earlier[..., None], b[..., None, :, :] * jnp.exp(gap),
                     0.0)                         # [..., n, C, K]
    across = _dot("...mtk,...msk->...mts", rows, cols)
    across = across.reshape(lead + (chunk, chunk))
    # inside a sub-chunk: pair by pair, on the block diagonal
    within = _pairs_within(a_, b_, g_)            # [..., n, B, B]
    eye = jnp.eye(n, dtype=within.dtype)
    diagonal = (within[..., :, :, None, :] * eye[:, None, :, None]).reshape(
        lead + (chunk, chunk))
    return across + diagonal


def _chunked(q, k, v, log_alpha, beta, chunk: int):
    f32 = jnp.float32
    seq = q.shape[0]
    pad = -seq % chunk
    if pad:
        # positions after the sequence add nothing and are dropped
        q, k, v, log_alpha, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, log_alpha, beta))
    q, k, v, g, b = (_by_chunk(x.astype(f32), chunk)
                     for x in (q, k, v, log_alpha, beta))
    g = jnp.cumsum(g, axis=-2)                    # [H, N, C, K]
    b = b[..., None]                              # [H, N, C, 1]
    # I + A, A strictly below the diagonal: the solve reads 1 on it
    rhs = jnp.concatenate([b * k * jnp.exp(g), b * v], -1)
    solved = jax.lax.linalg.triangular_solve(
        b * _pairs(k, k, g), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u_tilde = jnp.split(solved, [k.shape[-1]], -1)
    last = g[..., -1:, :]                         # g_C: [H, N, 1, K]
    k_to_end = k * jnp.exp(last - g)

    def step(state, xs):
        w_c, u_c, k_c, decay_c = xs
        u = u_c - _dot("hck,hkv->hcv", w_c, state)
        return (decay_c[..., None] * state
                + _dot("hck,hcv->hkv", k_c, u)), state

    heads, width, values = k.shape[0], k.shape[-1], v.shape[-1]
    first = lambda x: jnp.moveaxis(x, 1, 0)                      # noqa: E731
    _, starts = jax.lax.scan(step, jnp.zeros((heads, width, values), f32), (
        first(w), first(u_tilde), first(k_to_end),
        first(jnp.exp(last[..., 0, :]))))
    starts = jnp.moveaxis(starts, 0, 1)           # [H, N, K, V]
    u = u_tilde - _dot("hnck,hnkv->hncv", w, starts)
    out = _dot("hnck,hnkv->hncv", q * jnp.exp(g), starts) + _dot(
        "hnts,hnsv->hntv", _pairs(q, k, g), u)
    out = jnp.moveaxis(out, 0, 2).reshape((-1,) + out.shape[:1] + (values,))
    return out[:seq]


def rule_form(seq: int, heads: int, dim: int, values: int,
              chunk: int) -> str:
    """Which form of the rule runs, from platform and shape: ``"kernels"``
    (``ops/delta_rule_kernels.py``) on a TPU (or under the interpreter)
    where the keys' and the values' widths are multiples of 128, the
    sequence is a whole number of chunks, the chunk a whole number of
    sub-chunks, and a visit fits VMEM; ``"xla"`` everywhere else."""
    fits = (dim % _LANES == 0 and values % _LANES == 0 and chunk % SUB == 0
            and seq % chunk == 0
            and delta_rule_kernels.fits_vmem(heads, dim, values, chunk))
    return "kernels" if fits and pallas_ops._kernels_enabled() else "xla"


def gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK):
    """``o [S, H, V]`` of one sequence, in v's dtype, by the chunked form:
    ``q, k [S, H, K]``, ``v [S, H, V]``, ``log_alpha [S, H, K]`` (at most
    0: the log of each key channel's decay), ``beta [S, H]``.  The chunk
    is a whole number of sub-chunks of 16, or smaller than one; a
    sequence that is no whole number of chunks is padded with positions
    that add nothing."""
    if chunk > SUB and chunk % SUB:
        raise ValueError(f"gated_delta_rule: chunk {chunk} is no whole "
                         f"number of sub-chunks of {SUB}")
    _note_call()
    if rule_form(*k.shape, v.shape[-1], chunk) == "kernels":
        # the kernels hold nothing wider than 32 bits: a float64 input
        # (jax.random's default under the package's x64) is float32 there,
        # as the XLA form's sums are
        narrow = lambda x: (x.astype(jnp.float32)                # noqa: E731
                            if x.dtype.itemsize > 4 else x)
        return delta_rule_kernels.rule(
            *map(narrow, (q, k, v, log_alpha, beta)), chunk,
            SUB).astype(v.dtype)
    return _chunked(q, k, v, log_alpha, beta, chunk).astype(v.dtype)
