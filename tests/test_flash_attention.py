"""Flash-attention v2 feature tests (upstream flash_attn /
flash_attn_varlen parity — SURVEY.md §2.1 FlashAttention row).

The composed XLA path runs on CPU directly; the ACTUAL Pallas kernels
are exercised in interpreter mode (PADDLE_TPU_PALLAS_INTERPRET) so the
kernel code is tested without TPU hardware.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas_ops


def _rand_qkv(rng, b=2, s=64, h=4, d=16, sk=None, hkv=None):
    sk = sk or s
    hkv = hkv or h
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    k = rng.randn(b, sk, hkv, d).astype(np.float32) * 0.5
    v = rng.randn(b, sk, hkv, d).astype(np.float32) * 0.5
    return q, k, v


def _oracle(q, k, v, causal=False, seg_q=None, seg_k=None):
    """Dense reference in fp32 numpy."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = np.repeat(k, rep, axis=2)
        v = np.repeat(v, rep, axis=2)
    qt = np.moveaxis(q, 2, 1).astype(np.float64)    # [b,h,sq,d]
    kt = np.moveaxis(k, 2, 1).astype(np.float64)
    vt = np.moveaxis(v, 2, 1).astype(np.float64)
    s = qt @ np.swapaxes(kt, -1, -2) / np.sqrt(d)
    mask = np.ones((sq, sk), dtype=bool)
    if causal:
        mask &= np.tril(np.ones((sq, sk), dtype=bool))
    mask = np.broadcast_to(mask, s.shape).copy()
    if seg_q is not None:
        m = (seg_q[:, :, None] == seg_k[:, None, :])   # [b,sq,sk]
        mask &= m[:, None, :, :]
    s = np.where(mask, s, -np.inf)
    s = s - np.max(s, axis=-1, keepdims=True)
    e = np.exp(s)
    den = np.sum(e, axis=-1, keepdims=True)
    p = np.where(den > 0, e / np.maximum(den, 1e-30), 0.0)
    out = p @ vt
    return np.moveaxis(out, 1, 2).astype(np.float32)


def test_flash_causal_matches_oracle():
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng)
    out, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                               causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               _oracle(q, k, v, causal=True),
                               rtol=2e-4, atol=2e-5)


def test_flash_cross_attention_sq_ne_sk():
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, s=32, sk=96)
    out, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                               causal=False)
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               _oracle(q, k, v), rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="Sq == Sk"):
        F.flash_attention(Tensor(q), Tensor(k), Tensor(v), causal=True)


def test_flash_gqa_matches_repeated_kv():
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, h=8, hkv=2)
    out, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                               causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               _oracle(q, k, v, causal=True),
                               rtol=2e-4, atol=2e-5)
    bad_k = k[:, :, :1]
    q3 = q[:, :, :3]
    with pytest.raises(ValueError, match="divisible"):
        F.flash_attention(Tensor(q3[:, :, :3]), Tensor(k[:, :, :2][:, :, :2]),
                          Tensor(v[:, :, :2]), causal=False)


def test_flash_segment_ids_varlen_masking():
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, b=2, s=32)
    # two packed sequences of 16 + padding-free
    seg = np.concatenate([np.zeros((2, 16), np.int32),
                          np.ones((2, 16), np.int32)], axis=1)
    out, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                               causal=True, segment_ids=Tensor(seg))
    np.testing.assert_allclose(
        np.asarray(out.numpy()),
        _oracle(q, k, v, causal=True, seg_q=seg, seg_k=seg),
        rtol=2e-4, atol=2e-5)
    # cross-segment attention is actually blocked: second half of the
    # packed batch must equal attention over the second half alone
    out2, _ = F.flash_attention(Tensor(q[:, 16:]), Tensor(k[:, 16:]),
                                Tensor(v[:, 16:]), causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy())[:, 16:],
                               np.asarray(out2.numpy()),
                               rtol=2e-4, atol=2e-5)


def test_flash_fully_masked_rows_zero_not_nan():
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, b=1, s=16)
    seg_q = np.zeros((1, 16), np.int32)
    seg_k = np.full((1, 16), 7, np.int32)       # nothing matches
    out, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                               segment_ids=Tensor(seg_q),
                               kv_segment_ids=Tensor(seg_k))
    o = np.asarray(out.numpy())
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, np.zeros_like(o), atol=1e-6)


def test_flash_dropout_semantics():
    """dropout>0 must actually drop (not silently ignore — r2 weak #5)."""
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng)
    paddle.seed(0)
    out_d, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                                 causal=True, dropout=0.5, training=True)
    out_ref = _oracle(q, k, v, causal=True)
    # with p=0.5 the dropped-mask output must differ measurably
    diff = np.abs(np.asarray(out_d.numpy()) - out_ref).mean()
    assert diff > 1e-3, "dropout was silently ignored"
    # eval mode: dropout off, exact match
    out_e, _ = F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                                 causal=True, dropout=0.5, training=False)
    np.testing.assert_allclose(np.asarray(out_e.numpy()), out_ref,
                               rtol=2e-4, atol=2e-5)


def test_flash_gradients_flow():
    rng = np.random.RandomState(6)
    q, k, v = _rand_qkv(rng)
    qt, kt, vt = Tensor(q), Tensor(k), Tensor(v)
    for t in (qt, kt, vt):
        t.stop_gradient = False
    out, _ = F.flash_attention(qt, kt, vt, causal=True)
    loss = out.sum()
    loss.backward()
    for t in (qt, kt, vt):
        g = np.asarray(t.grad.numpy())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0


@pytest.fixture()
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    yield
    # env restored by monkeypatch


def test_pallas_kernel_fwd_matches_composed(_interpret_mode):
    """Runs the ACTUAL Pallas kernel (interpret mode) vs the oracle."""
    rng = np.random.RandomState(7)
    b, s, h, d = 1, 256, 2, 32
    q, k, v = _rand_qkv(rng, b=b, s=s, h=h, d=d)
    qf = jnp.asarray(q.reshape(b, s, h, d))
    qbh = jnp.moveaxis(qf, 2, 1).reshape(b * h, s, d)
    kbh = jnp.moveaxis(jnp.asarray(k), 2, 1).reshape(b * h, s, d)
    vbh = jnp.moveaxis(jnp.asarray(v), 2, 1).reshape(b * h, s, d)
    for causal in (False, True):
        out, lse = pallas_ops._pallas_flash_bh(
            qbh, kbh, vbh, causal=causal, block_q=128, block_k=128)
        ref = pallas_ops._flash_reference(qbh, kbh, vbh, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        assert np.isfinite(np.asarray(lse)).all()


def test_pallas_kernel_bwd_matches_composed(_interpret_mode):
    rng = np.random.RandomState(8)
    b, s, h, d = 1, 128, 2, 16
    q, k, v = _rand_qkv(rng, b=b, s=s, h=h, d=d)
    qbh = jnp.moveaxis(jnp.asarray(q), 2, 1).reshape(b * h, s, d)
    kbh = jnp.moveaxis(jnp.asarray(k), 2, 1).reshape(b * h, s, d)
    vbh = jnp.moveaxis(jnp.asarray(v), 2, 1).reshape(b * h, s, d)
    empty = jnp.zeros((0,), jnp.int32)

    def f_kernel(q_, k_, v_):
        return pallas_ops._flash_core(q_, k_, v_, empty, empty,
                                      True, True).sum()

    def f_ref(q_, k_, v_):
        return pallas_ops._flash_reference(q_, k_, v_, True).sum()

    g_kernel = jax.grad(f_kernel, argnums=(0, 1, 2))(qbh, kbh, vbh)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(qbh, kbh, vbh)
    for gk, gr in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=5e-4, atol=5e-5)


def test_pallas_kernel_segment_ids(_interpret_mode):
    rng = np.random.RandomState(9)
    b, s, h, d = 1, 128, 1, 16
    q, k, v = _rand_qkv(rng, b=b, s=s, h=h, d=d)
    qbh = jnp.moveaxis(jnp.asarray(q), 2, 1).reshape(b * h, s, d)
    kbh = jnp.moveaxis(jnp.asarray(k), 2, 1).reshape(b * h, s, d)
    vbh = jnp.moveaxis(jnp.asarray(v), 2, 1).reshape(b * h, s, d)
    seg = jnp.asarray(
        np.repeat(np.arange(2, dtype=np.int32), 64)[None, :])
    out, _ = pallas_ops._pallas_flash_bh(
        qbh, kbh, vbh, seg, seg, causal=False, block_q=128, block_k=128)
    ref = pallas_ops._flash_reference(qbh, kbh, vbh, False, seg, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_fit_block_always_divides():
    from paddle_tpu.ops.pallas_ops import _fit_block
    for seq in (128, 256, 384, 640, 768, 1024, 4096, 200):
        for req in (128, 256, 512, 1024, 300):
            b = _fit_block(seq, req)
            assert seq % b == 0 and b <= max(req, 1), (seq, req, b)


def test_pallas_kernel_non_block_multiple_seq(_interpret_mode):
    """seq=384 divides 128 but not the 512 default block — the fitted
    block must cover the whole sequence (review finding: tail rows were
    silently left uncomputed)."""
    rng = np.random.RandomState(11)
    b, s, h, d = 1, 384, 1, 16
    q, k, v = _rand_qkv(rng, b=b, s=s, h=h, d=d)
    qbh = jnp.moveaxis(jnp.asarray(q), 2, 1).reshape(b * h, s, d)
    kbh = jnp.moveaxis(jnp.asarray(k), 2, 1).reshape(b * h, s, d)
    vbh = jnp.moveaxis(jnp.asarray(v), 2, 1).reshape(b * h, s, d)
    out, _ = pallas_ops._pallas_flash_bh(qbh, kbh, vbh, causal=True)
    ref = pallas_ops._flash_reference(qbh, kbh, vbh, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def _rand_bh(rng, s, h, d):
    """q, k, v as the [BH, S, D] kernels take them, one batch row."""
    return tuple(jnp.moveaxis(jnp.asarray(x), 2, 1).reshape(h, s, d)
                 for x in _rand_qkv(rng, b=1, s=s, h=h, d=d))


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bh_bwd_several_blocks_matches_composed(_interpret_mode,
                                                       causal):
    """The split dq and dkv kernels over two query and two key blocks
    (block_q = block_k = 128 at s256): dq is carried across the key
    blocks, dk and dv across the query blocks, and under a causal mask
    one block pair is skipped."""
    qbh, kbh, vbh = _rand_bh(np.random.RandomState(12), 256, 2, 16)
    do = jnp.ones_like(qbh)
    out, lse = pallas_ops._pallas_flash_bh(
        qbh, kbh, vbh, causal=causal, block_q=128, block_k=128)
    g_kernel = pallas_ops._pallas_flash_bwd(
        qbh, kbh, vbh, out, lse, do, causal=causal, block_q=128,
        block_k=128)
    g_ref = jax.grad(
        lambda q_, k_, v_: pallas_ops._flash_reference(
            q_, k_, v_, causal).sum(), argnums=(0, 1, 2))(qbh, kbh, vbh)
    for gk, gr in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=5e-4, atol=5e-5)


def _composed_oracle_bh(q, k, v, causal, q_seg=None, k_seg=None):
    """Standalone composed attention (same math as
    pallas_ops._flash_reference) usable while the module's fallback is
    monkeypatched to raise."""
    import math as _math
    scale = 1.0 / _math.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool))
        s = jnp.where(mask, s, -jnp.inf)
    if q_seg is not None:
        s = jnp.where(q_seg[:, :, None] == k_seg[:, None, :], s,
                      -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(lse, -1e30))
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.fixture()
def _no_fallback(monkeypatch):
    """Fail the test if the packed kernel silently degrades to the
    composed form (the original packed tests passed vacuously through
    the fallback — a real ref-write bug was hidden)."""
    def boom(*a, **k):
        raise AssertionError(
            "packed kernel fell back to _flash_reference")
    monkeypatch.setattr(pallas_ops, "_flash_reference", boom)
    yield


def test_pallas_packed_kernels_match_composed(_interpret_mode,
                                              _no_fallback):
    """The transpose-free packed-heads layout ([B,S,H*D], heads packed
    into 128-lane groups) — fwd and bwd vs the composed oracle, with
    multiple q/kv blocks, causal and full."""
    from paddle_tpu.ops.pallas_ops import (
        _flash_core_packed, _packed_geometry)
    _flash_reference = _composed_oracle_bh
    assert _packed_geometry(4, 64) == (128, 2, 2)
    assert _packed_geometry(2, 128) == (128, 1, 2)
    assert _packed_geometry(3, 64) is None          # h % hpb != 0
    rng = np.random.RandomState(13)
    b, s, h, d = 2, 256, 4, 32                      # hpb=4, g=1
    x = rng.randn(b, s, h * d).astype(np.float32)
    qp = jnp.asarray(x)
    kp = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32))
    vp = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32))
    empty = jnp.zeros((0,), jnp.int32)

    def to_bh(t):
        return jnp.moveaxis(t.reshape(b, s, h, d), 2, 1).reshape(
            b * h, s, d)

    for causal in (False, True):
        def f_packed(q_, k_, v_):
            return _flash_core_packed(q_, k_, v_, empty, empty,
                                      causal, h, d).sum()

        def f_ref(q_, k_, v_):
            return _flash_reference(to_bh(q_), to_bh(k_), to_bh(v_),
                                    causal).sum()

        out_p = _flash_core_packed(qp, kp, vp, empty, empty, causal,
                                   h, d)
        out_r = _flash_reference(to_bh(qp), to_bh(kp), to_bh(vp),
                                 causal)
        np.testing.assert_allclose(
            np.asarray(to_bh(out_p)), np.asarray(out_r),
            rtol=2e-4, atol=2e-5)
        g_p = jax.grad(f_packed, argnums=(0, 1, 2))(qp, kp, vp)
        g_r = jax.grad(f_ref, argnums=(0, 1, 2))(qp, kp, vp)
        for gp_, gr_ in zip(g_p, g_r):
            np.testing.assert_allclose(np.asarray(gp_),
                                       np.asarray(gr_),
                                       rtol=5e-4, atol=5e-5)


def test_pallas_packed_segment_ids(_interpret_mode, _no_fallback):
    from paddle_tpu.ops.pallas_ops import _flash_core_packed
    _flash_reference = _composed_oracle_bh
    rng = np.random.RandomState(14)
    b, s, h, d = 1, 128, 2, 64
    qp = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32))
    seg = jnp.asarray(
        np.repeat(np.arange(2, dtype=np.int32), 64)[None, :])

    def to_bh(t):
        return jnp.moveaxis(t.reshape(b, s, h, d), 2, 1).reshape(
            b * h, s, d)

    out_p = _flash_core_packed(qp, qp, qp, seg, seg, False, h, d)
    seg_bh = jnp.repeat(seg, h, axis=0)
    out_r = _flash_reference(to_bh(qp), to_bh(qp), to_bh(qp), False,
                             seg_bh, seg_bh)
    np.testing.assert_allclose(np.asarray(to_bh(out_p)),
                               np.asarray(out_r), rtol=2e-4, atol=2e-5)


def test_flash_attention_public_uses_packed(_interpret_mode,
                                            _no_fallback):
    """End-to-end through the public op at GPT-like head geometry."""
    rng = np.random.RandomState(15)
    b, s, h, d = 1, 256, 4, 64
    q = rng.randn(b, s, h, d).astype(np.float32)
    out, _ = F.flash_attention(Tensor(q), Tensor(q), Tensor(q),
                               causal=True)
    ref = _oracle(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bh_fwd_several_blocks_matches_composed(_interpret_mode,
                                                       causal):
    """The [BH, S, D] forward kernel over two query and two key blocks
    at 4 heads x 64: the output against the oracle, and the saved
    log-sum-exp, the same in every lane, against the scores' own."""
    s, h, d = 256, 4, 64
    qbh, kbh, vbh = _rand_bh(np.random.RandomState(11), s, h, d)
    out, lse = pallas_ops._pallas_flash_bh(
        qbh, kbh, vbh, causal=causal, block_q=128, block_k=128)
    ref = pallas_ops._flash_reference(qbh, kbh, vbh, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    scores = jnp.einsum("bqd,bkd->bqk", qbh, kbh) / np.sqrt(d)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
    want = jax.scipy.special.logsumexp(scores, axis=-1)
    assert lse.shape == (h, s, pallas_ops._LANES)
    np.testing.assert_allclose(
        np.asarray(lse), np.broadcast_to(np.asarray(want)[..., None],
                                         lse.shape), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h, d", [(2, 128), (3, 64)])
def test_pallas_bh_kernel_takes_any_head_geometry(_interpret_mode, h, d):
    """The [BH, S, D] kernel sees one head a program, so it takes a
    head as wide as the lanes and an odd count of narrow ones alike."""
    qbh, kbh, vbh = _rand_bh(np.random.RandomState(12), 256, h, d)
    out, _ = pallas_ops._pallas_flash_bh(
        qbh, kbh, vbh, causal=True, block_q=128, block_k=128)
    ref = pallas_ops._flash_reference(qbh, kbh, vbh, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_public_reaches_bh_kernels(_interpret_mode,
                                                   _no_fallback):
    """Three heads of 64 leave half a lane group empty, so the public op
    takes the [BH, S, D] kernels (``_attention_form``), forward and
    backward: values and the three gradients against the oracle, with
    the composed form forbidden."""
    rng = np.random.RandomState(18)
    b, s, h, d = 1, 256, 3, 64
    assert pallas_ops._attention_form(h, d, s, s) == "bh"
    q, k, v = (jnp.asarray(x) for x in _rand_qkv(rng, b=b, s=s, h=h, d=d))
    do = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))

    out, vjp = jax.vjp(
        lambda q_, k_, v_: pallas_ops.flash_attention.raw(
            q_, k_, v_, causal=True), q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(*(np.asarray(x) for x in (q, k, v)),
                                 causal=True), rtol=2e-4, atol=2e-5)

    to_bh = pallas_ops._heads_to_batch
    _, vjp_ref = jax.vjp(
        lambda q_, k_, v_: pallas_ops._batch_to_heads(_composed_oracle_bh(
            to_bh(q_), to_bh(k_), to_bh(v_), True), b), q, k, v)
    for got, want in zip(vjp(do), vjp_ref(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-5)


# The eight PADDLE_TPU_* variables that once chose a kernel or a block
# (PR 30 took them out): the names are put together here so that a
# search for any of them finds no reader.
_DEAD_KNOBS = ["PADDLE_TPU_" + "_".join(parts) for parts in (
    ("FLASH", "HEADPACK"), ("FLASH", "BQ"), ("FLASH", "BK"),
    ("FLASH", "FUSED", "BWD"), ("FLASH", "NO", "PACKED"),
    ("FUSED", "LMCE"), ("LMCE", "BN"), ("LMCE", "BV"))]

_ON_TPU_CASES = [
    # the shapes users bring: (heads, head width, sq, sk) -> form
    pytest.param(16, 64, 1024, 1024, "packed", id="gpt2-medium-16x64"),
    pytest.param(16, 128, 1024, 1024, "packed", id="gpt3-xl-16x128"),
    pytest.param(8, 128, 2048, 2048, "packed", id="gpt3-xl-mp2-8x128"),
    pytest.param(25, 64, 1024, 1024, "bh", id="gpt2-xl-25x64"),
    pytest.param(32, 80, 2048, 2048, "bh", id="gpt3-2p7b-32x80"),
    pytest.param(16, 96, 2048, 2048, "bh", id="gpt3-large-16x96"),
    pytest.param(16, 64, 128, 128, None, id="s128-composed"),
    pytest.param(16, 64, 1024, 1000, None, id="sk-not-of-128-composed"),
    pytest.param(16, 64, 320, 320, None, id="sq-not-of-128-composed"),
]


@pytest.fixture()
def _as_on_a_tpu(monkeypatch):
    """A TPU backend with nothing set, as far as ``_attention_form`` can
    tell."""
    for name in _DEAD_KNOBS + ["PADDLE_TPU_PALLAS_INTERPRET",
                               "PADDLE_TPU_DISABLE_PALLAS"]:
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)


@pytest.mark.parametrize("h, d, sq, sk, want", _ON_TPU_CASES)
def test_attention_form_follows_the_shape(_as_on_a_tpu, h, d, sq, sk,
                                          want):
    """``_attention_form`` is the one place that says which attention
    runs: on a TPU the shape one device holds decides."""
    assert pallas_ops._attention_form(h, d, sq, sk) == want


def test_attention_form_ignores_the_dead_knobs(_as_on_a_tpu, monkeypatch):
    """All eight set, to the values that once chose another kernel or
    block: every shape gets the answer it gets without them."""
    for name, value in zip(_DEAD_KNOBS, ("2", "256", "256", "1", "1", "1",
                                         "128", "256")):
        monkeypatch.setenv(name, value)
    for case in _ON_TPU_CASES:
        *shape, want = case.values
        assert pallas_ops._attention_form(*shape) == want, case.id


@pytest.mark.parametrize("on_tpu, switch, h, d, s, want", [
    pytest.param(True, "PADDLE_TPU_DISABLE_PALLAS", 16, 64, 1024, None,
                 id="tpu-kernels-disabled"),
    pytest.param(False, None, 16, 64, 1024, None, id="cpu"),
    pytest.param(False, "PADDLE_TPU_PALLAS_INTERPRET", 16, 64, 128,
                 "packed", id="cpu-interpreter-takes-s128"),
    pytest.param(False, "PADDLE_TPU_PALLAS_INTERPRET", 3, 64, 256, "bh",
                 id="cpu-interpreter-3x64"),
])
def test_attention_form_follows_the_platform(_as_on_a_tpu, monkeypatch,
                                             on_tpu, switch, h, d, s, want):
    """Beside the shape it reads the backend and the two switches the
    tests and the harness use, and nothing else."""
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: on_tpu)
    if switch:
        monkeypatch.setenv(switch, "1")
    assert pallas_ops._attention_form(h, d, s, s) == want


def test_dead_knobs_are_not_registered():
    from paddle_tpu.framework import env_knobs
    assert not set(_DEAD_KNOBS) & set(env_knobs.KNOBS)


def test_flash_attention_runs_per_device_under_a_mesh(_interpret_mode,
                                                      _no_fallback):
    """Mosaic kernels cannot be partitioned by GSPMD, so under a mesh
    of several devices the public op runs the kernel per device inside
    a shard_map (batch on the data axes, heads on 'mp').  Values and
    gradients on a dp2 x mp2 mesh match the one-device kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import collective
    rng = np.random.RandomState(16)
    b, s, h, d = 2, 128, 4, 64      # per device: [1, 128, 2, 64], packed
    q, k, v = (jnp.asarray(x) for x in _rand_qkv(rng, b=b, s=s, h=h, d=d))

    def loss(q_, k_, v_):
        out = pallas_ops.flash_attention.raw(q_, k_, v_, causal=True)
        return (out * out).sum(), out

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    g_one, out_one = grad(q, k, v)

    mesh = collective.build_mesh({"dp": 2, "mp": 2})
    collective.set_mesh(mesh)
    sh = NamedSharding(mesh, P("dp", None, "mp", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2),
                               has_aux=True)).lower(qs, ks, vs)
    assert "sdy.manual_computation" in lowered.as_text()  # the shard_map
    g_mesh, out_mesh = lowered.compile()(qs, ks, vs)
    assert out_mesh.sharding.spec == sh.spec
    np.testing.assert_allclose(np.asarray(out_mesh), np.asarray(out_one),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out_mesh), _oracle(*(np.asarray(x) for x in (q, k, v)),
                                      causal=True), rtol=2e-4, atol=2e-5)
    for gm, go in zip(g_mesh, g_one):
        np.testing.assert_allclose(np.asarray(gm), np.asarray(go),
                                   rtol=1e-5, atol=1e-6)


def _tile_case(case_id, **kw):
    case = dict(b=1, s=512, sk=None, h=2, d=64, causal=True, seg=False,
                orphans=False, block_q=None, block_k=None, tile=None)
    case.update(kw)
    return pytest.param(case, id=case_id)


# Each case names the tile classes it reaches: "bare" (below the
# diagonal, or no mask at all), "masked" (the diagonal crosses it, or
# segment ids), "skipped" (above the diagonal: by the loop's bounds
# inside a resident block, by the grid's pl.when for a whole block).
_TILE_CASES = [
    # one resident block, several compute tiles
    _tile_case("s512-t128-one-block", tile=(128, 128)),
    _tile_case("s1024-default-rule-hpb2", s=1024),
    _tile_case("s1024-t256x128", s=1024, tile=(256, 128)),
    _tile_case("s512-t128x256", tile=(128, 256)),
    # two resident key blocks: grid skip and loop skip together
    _tile_case("s512-two-key-blocks", block_q=128, block_k=256,
               tile=(128, 128)),
    _tile_case("s1024-bq256-bk512-default-tile", s=1024, block_q=256,
               block_k=512),
    # no mask: every tile bare, also where Sq != Sk
    _tile_case("full-t128", causal=False, tile=(128, 128)),
    _tile_case("cross-sq256-sk512", s=256, sk=512, causal=False,
               block_k=256, tile=(128, 128)),
    _tile_case("cross-default-rule", s=256, sk=512, causal=False),
    # segment ids: every tile visited and masked
    _tile_case("seg-default-rule", seg=True),
    _tile_case("seg-causal-t128", seg=True, tile=(128, 128)),
    _tile_case("seg-full-t128x256", seg=True, causal=False,
               tile=(128, 256)),
    # query rows whose segment holds no key: all of the row masked
    _tile_case("seg-rows-without-keys", seg=True, orphans=True,
               causal=False, tile=(128, 128)),
    _tile_case("seg-rows-without-keys-default-rule", seg=True,
               orphans=True, d=128),
    # hpb 1: one head fills the lane block
    _tile_case("d128-hpb1-t128", d=128, tile=(128, 128)),
    _tile_case("d128-hpb1-two-key-blocks", d=128, s=1024, block_k=512),
]


@pytest.mark.parametrize("case", _TILE_CASES)
def test_pallas_packed_tiles_match_composed(_interpret_mode, _no_fallback,
                                            case):
    """The packed kernels walk their resident block as compute tiles
    (``_walk_tiles``): forward and all three gradients against the
    composed oracle, at shapes and tiles that reach every tile class."""
    b, s, h, d = case["b"], case["s"], case["h"], case["d"]
    sk, causal = case["sk"] or s, case["causal"]
    rng = np.random.RandomState(17)
    q = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32) * 0.5)
    k, v = (jnp.asarray(rng.randn(b, sk, h * d).astype(np.float32) * 0.5)
            for _ in range(2))
    do = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32))
    qseg = kseg = None
    if case["seg"]:     # documents of uneven lengths, cut off the tiles
        cuts = np.sort(rng.choice(np.arange(1, sk), 3, replace=False))
        kseg = jnp.asarray(np.searchsorted(cuts, np.arange(sk),
                                           side="right")[None, :]
                           .repeat(b, 0).astype(np.int32))
        qseg = kseg[:, :s]
        if case["orphans"]:
            qseg = qseg.at[:, 100:230].set(99)
    over = dict(causal=causal, block_q=case["block_q"],
                block_k=case["block_k"], tile=case["tile"])

    def to_bh(t):
        return jnp.moveaxis(t.reshape(b, t.shape[1], h, d), 2,
                            1).reshape(b * h, t.shape[1], d)

    def from_bh(t):
        return jnp.moveaxis(t.reshape(b, h, t.shape[1], d), 1,
                            2).reshape(b, t.shape[1], h * d)

    def rep(seg):
        return None if seg is None else jnp.repeat(seg, h, axis=0)

    out, lse = pallas_ops._pallas_flash_packed(q, k, v, h, d, qseg, kseg,
                                               **over)
    grads = pallas_ops._pallas_flash_packed_bwd(
        q, k, v, out, lse, do, h, d, qseg, kseg, **over)
    ref, vjp = jax.vjp(
        lambda q_, k_, v_: from_bh(_composed_oracle_bh(
            to_bh(q_), to_bh(k_), to_bh(v_), causal, rep(qseg),
            rep(kseg))), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    if case["orphans"]:
        assert not np.asarray(out)[:, 100:230].any()
    for got, want in zip(grads, vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-5)


def _tiles(kind):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(
        "flash_tiles_total", labels={"kind": kind}).collect()


@pytest.mark.parametrize("s, tile, causal", [
    (1024, 256, True), (1024, 512, True), (1024, 128, True),
    (2048, 256, True), (512, 128, False),
    (1024, None, True), (2048, None, True)])    # the shape's own: 512
def test_flash_tile_counter_reads_the_closed_form(_interpret_mode, s, tile,
                                                  causal):
    """``flash_tiles_total`` counts, as a call is traced, the compute
    tiles of the score square, those visited and those masked, a head:
    n², n (n + 1) / 2 and n for n = s / tile under a causal mask."""
    b, h, d = 2, 2, 64
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.float32)
    kinds = ("square", "visited", "masked")
    before = [_tiles(kind) for kind in kinds]
    jax.eval_shape(
        lambda q, k, v: pallas_ops._pallas_flash_packed(
            q, k, v, h, d, causal=causal, tile=tile and (tile, tile)),
        x, x, x)
    n = s // (tile or 512)
    want = (n * n, n * (n + 1) // 2, n) if causal else (n * n, n * n, 0)
    assert [_tiles(kind) - was for kind, was in zip(kinds, before)] == \
        [b * h * w for w in want]
    # the backward pass makes two calls over the same tiles
    before = [_tiles(kind) for kind in kinds]
    lse = jax.ShapeDtypeStruct((b, s, h * d), jnp.float32)
    jax.eval_shape(
        lambda q, k, v, o, l, do: pallas_ops._pallas_flash_packed_bwd(
            q, k, v, o, l, do, h, d, causal=causal,
            tile=tile and (tile, tile)), x, x, x, x, lse, x)
    assert [_tiles(kind) - was for kind, was in zip(kinds, before)] == \
        [2 * b * h * w for w in want]


def test_flash_tile_counts_by_brute_force():
    """``_tile_counts`` against a walk over every tile's corners, for
    tiles that are not square and squares that are not."""
    for sq, sk, tq, tk in [(1024, 1024, 256, 256), (1024, 1024, 512, 256),
                           (1024, 1024, 128, 512), (512, 1024, 128, 256),
                           (2048, 2048, 256, 128)]:
        visited = masked = 0
        for q0 in range(0, sq, tq):
            for k0 in range(0, sk, tk):
                if k0 > q0 + tq - 1:
                    continue        # no row sees any of these keys
                visited += 1
                masked += k0 + tk - 1 > q0  # the first row misses some
        assert pallas_ops._tile_counts(sq, sk, tq, tk, True, False) == \
            ((sq // tq) * (sk // tk), visited, masked)
    assert pallas_ops._tile_counts(512, 1024, 128, 256, False, False) == \
        (16, 16, 0)
    assert pallas_ops._tile_counts(512, 512, 128, 128, True, True) == \
        (16, 16, 16)


# ---------------------------------------------------------------------------
# the window: a causal band the tile walk keeps to
# ---------------------------------------------------------------------------
def _banded_oracle(q, k, v, window, seg=None):
    """Plain float32 attention [B, S, H, D] under ``0 <= t - s <
    window`` (and inside a segment)."""
    s = q.shape[1]
    t, u = np.arange(s)[:, None], np.arange(s)[None, :]
    keep = jnp.asarray((u <= t) & (t - u < window))[None, None]
    if seg is not None:
        keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * np.float32(
        1 / np.sqrt(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _window_case(case_id, **kw):
    case = dict(s=512, h=2, d=64, window=128, seg=False, block_q=None,
                block_k=None, tile=None)
    case.update(kw)
    return pytest.param(case, id=case_id)


# Each case names what of the band it reaches: tiles the lower edge
# crosses, tiles wholly inside (bare), resident blocks wholly below the
# band (never stood at: the band grid is shorter than the sequence's),
# steps past a band's last block (nothing runs).
_WINDOW_CASES = [
    _window_case("w128-t128-one-block", tile=(128, 128)),
    _window_case("w200-t128-edge-inside-a-tile", window=200,
                 tile=(128, 128)),
    _window_case("w512-s2048-default-rule", s=2048, window=512),
    _window_case("w300-bare-tiles-between-the-edges", s=1024, window=300,
                 tile=(128, 128)),
    _window_case("w128-four-key-blocks-band-grid", s=1024, block_q=128,
                 block_k=256, tile=(128, 128)),
    _window_case("w130-key-blocks-narrower-than-query-blocks", s=1024,
                 window=130, block_q=512, block_k=128, tile=(128, 128)),
    _window_case("w1-the-diagonal-alone", window=1, tile=(128, 128)),
    _window_case("w-wider-than-the-sequence", window=4096),
    _window_case("w256-d128-hpb1", d=128, window=256, s=1024,
                 block_q=256, block_k=256),
    _window_case("w128-segments", seg=True, s=1024, block_q=256,
                 block_k=256, tile=(128, 128)),
]


@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_pallas_packed_window_matches_banded_attention(
        _interpret_mode, _no_fallback, case):
    """Forward and all three gradients of the packed kernels with a
    window against plain banded attention, and the band grid's length."""
    s, h, d, window = case["s"], case["h"], case["d"], case["window"]
    rng = np.random.RandomState(23)
    q, k, v, do = (jnp.asarray(rng.randn(1, s, h * d).astype(np.float32)
                               * 0.5) for _ in range(4))
    seg = None
    if case["seg"]:
        cuts = np.sort(rng.choice(np.arange(1, s), 3, replace=False))
        seg = jnp.asarray(np.searchsorted(cuts, np.arange(s),
                                          side="right")[None].astype(
                                              np.int32))
    over = dict(causal=True, block_q=case["block_q"],
                block_k=case["block_k"], tile=case["tile"], window=window)
    out, lse = pallas_ops._pallas_flash_packed(q, k, v, h, d, seg, seg,
                                               **over)
    grads = pallas_ops._pallas_flash_packed_bwd(
        q, k, v, out, lse, do, h, d, seg, seg, **over)
    heads = lambda a: a.reshape(1, s, h, d)                 # noqa: E731
    ref, vjp = jax.vjp(
        lambda q_, k_, v_: _banded_oracle(
            heads(q_), heads(k_), heads(v_), window, seg).reshape(
                1, s, h * d), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for got, want in zip(grads, vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("sq, bq, bk, window, by_key, want", [
    (8192, 512, 1024, 512, False, 2),    # the cell's forward: 2 of 8
    (8192, 512, 2048, 512, False, 2),    # its dq: 2 of 4
    (8192, 512, 1024, 512, True, 3),     # its dkv: 3 of 16 query blocks
    (1024, 128, 256, 128, False, 2), (1024, 128, 256, 128, True, 3),
    (1024, 512, 128, 130, False, 6), (1024, 128, 128, 1, False, 1),
    (512, 128, 128, 4096, False, 4), (512, 128, 128, 4096, True, 4)])
def test_the_band_grid_walks_the_band_and_no_more(sq, bq, bk, window,
                                                  by_key, want):
    """``_band_steps`` against a walk over every pair of blocks, and the
    first and last block of every band."""
    n_q, n_k = sq // bq, sq // bk
    assert pallas_ops._band_steps(by_key, bq, bk, window, n_q, n_k) == want
    first, last = pallas_ops._band(by_key, bq, bk, window, n_q)
    touch = np.zeros((n_q, n_k), bool)
    for i in range(n_q):
        for j in range(n_k):
            t = np.arange(i * bq, (i + 1) * bq)[:, None]
            u = np.arange(j * bk, (j + 1) * bk)[None, :]
            touch[i, j] = ((u <= t) & (t - u < window)).any()
    for o in range(n_k if by_key else n_q):
        line = np.flatnonzero(touch[:, o] if by_key else touch[o])
        assert (int(first(np.arange(o, o + 1, dtype=np.int32))[0]),
                int(last(np.arange(o, o + 1, dtype=np.int32))[0])) == (
                    line[0], line[-1])


@pytest.mark.parametrize("s, tile, window", [
    (8192, None, 512), (2048, 128, 512), (1024, 128, 200), (1024, 256, 1),
    (512, 128, 4096)])
def test_flash_tile_counter_counts_the_band(_interpret_mode, s, tile,
                                            window):
    """With a window ``flash_tiles_total`` counts the band's tiles: by a
    walk over every tile's corners here.  At the cell's shape (8192, the
    shape's own 512 x 512 tiles, window 512) a head's forward call
    visits 31 of the triangle's 136, every one masked."""
    b, h, d = 1, 2, 64
    t = tile or 512
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.float32)
    kinds = ("square", "visited", "masked")
    before = [_tiles(kind) for kind in kinds]
    jax.eval_shape(
        lambda q, k, v: pallas_ops._pallas_flash_packed(
            q, k, v, h, d, causal=True, tile=tile and (tile, tile),
            window=window), x, x, x)
    visited = masked = 0
    for q0 in range(0, s, t):
        for k0 in range(0, s, t):
            rows = np.arange(q0, q0 + t)[:, None]
            cols = np.arange(k0, k0 + t)[None, :]
            keep = (cols <= rows) & (rows - cols < window)
            visited += keep.any()
            masked += keep.any() and not keep.all()
    n = s // t
    assert [_tiles(kind) - was for kind, was in zip(kinds, before)] == \
        [b * h * w for w in (n * n, visited, masked)]
    if (s, tile, window) == (8192, None, 512):
        assert (visited, masked, n * (n + 1) // 2) == (31, 31, 136)


def test_flash_attention_window_in_every_form(_interpret_mode, monkeypatch):
    """The public op with a window: the packed kernels (GQA 4 on 2, as
    one of the differential calls), the composed form where no kernel
    takes the shape (24 heads of 48 go to the [BH, S, D] kernels without
    a window and to the composed form with one; the CPU without the
    interpreter), and with dropout; a window needs the causal mask."""
    rng = np.random.RandomState(29)

    def draw(s, h, hkv, d):
        return tuple(jnp.asarray(rng.randn(1, s, n, d).astype(np.float32)
                                 * 0.5) for n in (h, hkv, hkv))

    def check(q, k, v, window):
        rep = q.shape[2] // k.shape[2]
        f = lambda *a: pallas_ops.flash_attention.raw(      # noqa: E731
            *a, causal=True, window=window)
        g = lambda q_, k_, v_: _banded_oracle(              # noqa: E731
            q_, jnp.repeat(k_, rep, 2), jnp.repeat(v_, rep, 2), window)
        (out, vjp), (ref, ref_vjp) = jax.vjp(f, q, k, v), jax.vjp(g, q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        w = jnp.asarray(rng.randn(*out.shape).astype(np.float32))
        for got, want in zip(vjp(w), ref_vjp(w)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=5e-4, atol=5e-5)

    visited = _tiles("visited")
    check(*draw(512, 4, 2, 64), 100)
    assert _tiles("visited") > visited           # the packed kernels ran
    assert pallas_ops._attention_form(3, 48, 256, 256) == "bh"
    visited = _tiles("visited")
    check(*draw(256, 3, 3, 48), 100)
    assert _tiles("visited") == visited
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    check(*draw(192, 4, 2, 16), 50)              # the composed form
    q, k, v = draw(128, 2, 2, 16)
    out = pallas_ops.flash_attention.raw(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(pallas_ops.flash_attention.raw(
            q, k, v, causal=True)), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="causal band"):
        pallas_ops.flash_attention.raw(q, k, v, window=16)
    with pytest.raises(ValueError, match="causal band"):
        pallas_ops.flash_attention.raw(q, k, v, causal=True, window=0)


# sha256 of the lowered text (``jax.jit(...).lower(...).as_text()``, the
# kernels interpreted, which spells every kernel out as HLO) of the
# public op's forward and backward pass without a window, at the two
# rehearsal shapes of the cells that share the packed kernels, as the
# commit before the window (PR 37) lowered them: ``window=None`` is that
# program byte for byte.  A PR that changes the kernels themselves pins
# its own.
_PARENTS_TEXT = {
    (4, 128, 4, 64):
        "1cf1afb983465a4e4d84f292f20983de464d142eda26693bbadbc3e032b6b787",
    (2, 128, 4, 16, 2):
        "b8062db8d47745568a401a06a1645cc2eef8518ba1ca9e3e5e860a2491299de1",
}


@pytest.mark.parametrize("shape", sorted(_PARENTS_TEXT), ids=str)
def test_no_window_lowers_to_the_parents_text(_interpret_mode, shape):
    import hashlib
    b, s, h, d = shape[:4]
    hkv = shape[4] if len(shape) > 4 else h
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)

    def grads(window):
        kw = {} if window == "absent" else {"window": window}
        return lambda q_, k_, v_, w_: jax.grad(
            lambda *a: (pallas_ops.flash_attention.raw(
                *a, causal=True, **kw) * w_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q_, k_, v_)

    texts = [jax.jit(grads(window)).lower(q, kv, kv, q).as_text()
             for window in ("absent", None)]
    assert texts[0] == texts[1]
    assert hashlib.sha256(texts[0].encode()).hexdigest() == \
        _PARENTS_TEXT[shape]
    assert jax.jit(grads(64)).lower(q, kv, kv, q).as_text() != texts[0]
