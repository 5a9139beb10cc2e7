"""``ops/ssm.py`` on the CPU at small sizes: the chunked scan and its
hand-cut backward pass against the recurrence a position at a time (the
benchmark's reference, ``families/granite_hybrid.py:selective_scan``),
values and every gradient, over one chunk, many, a chunk the length of
the sequence, one group of heads and several; the causal convolution
against its definition; what a sequence that is no whole number of
chunks gets (it is refused); and that the backward pass is given the
inputs and nothing else.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu  # noqa: E402,F401 — turns on x64: the scan must survive it
from paddle_tpu.ops import ssm                                # noqa: E402
from benchmarks.families import granite_hybrid as family      # noqa: E402


def scan_inputs(seq, heads, width, groups, state, seed=0,
                dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa
    return (normal(seq, heads, width),
            jax.nn.softplus(jnp.asarray(rng.standard_normal((seq, heads)),
                                        jnp.float32)),
            -jnp.exp(jnp.asarray(rng.standard_normal((heads,)),
                                 jnp.float32)),
            normal(seq, groups, state), normal(seq, groups, state),
            jnp.asarray(rng.standard_normal((heads,)), jnp.float32),
            normal(seq, heads, width))


@pytest.mark.parametrize("seq, chunk, at_once, heads, groups", [
    pytest.param(64, 64, 4, 4, 1, id="chunk_is_the_sequence"),
    pytest.param(64, 16, 1, 4, 1, id="four_chunks-one_at_once"),
    pytest.param(96, 8, 4, 4, 1, id="twelve_chunks-four_at_once"),
    pytest.param(96, 8, 5, 4, 1, id="twelve_chunks-at_once_no_divisor"),
    pytest.param(64, 16, 2, 6, 2, id="two_groups_of_three_heads"),
    pytest.param(48, 16, 4, 4, 4, id="a_group_a_head"),
])
def test_chunked_scan_is_the_recurrence_values_and_gradients(
        monkeypatch, seq, chunk, at_once, heads, groups):
    monkeypatch.setattr(ssm, "CHUNKS_AT_ONCE", at_once)
    *inputs, w = scan_inputs(seq, heads, 8, groups, 16)

    def weighted(scan):
        return jax.value_and_grad(
            lambda *a: (scan(*a) * w).sum(), argnums=tuple(range(6)))(*inputs)

    got = weighted(lambda *a: ssm.ssd_scan(*a, chunk))
    want = weighted(family.selective_scan)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    np.testing.assert_allclose(
        ssm.ssd_scan(*inputs, chunk),
        family.selective_scan(*inputs), rtol=1e-4, atol=1e-4)
    for name, a, b in zip("x dt A B C D".split(), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5 * scale,
                                   err_msg=name)


def test_bf16_inputs_keep_float32_decays_and_sums():
    """Products take bf16 inputs; y and the gradients stay within a few
    bf16 roundings of the float32 recurrence on the same bf16 inputs."""
    *inputs, w = scan_inputs(128, 4, 16, 1, 16, dtype=jnp.bfloat16)
    as_f32 = [a.astype(jnp.float32) for a in inputs]
    got = jax.grad(lambda *a: (ssm.ssd_scan(*a, 32) * w).astype(
        jnp.float32).sum(), argnums=tuple(range(6)))(*inputs)
    want = jax.grad(lambda *a: (family.selective_scan(*a) * w.astype(
        jnp.float32)).sum(), argnums=tuple(range(6)))(*as_f32)
    y = ssm.ssd_scan(*inputs, 32)
    assert y.dtype == jnp.bfloat16
    for a, b, like in zip((y,) + got, (family.selective_scan(*as_f32),)
                          + want, [inputs[0]] + inputs):
        assert a.dtype == like.dtype
        err = float(jnp.abs(a.astype(jnp.float32) - b).max()
                    / jnp.abs(b).max())
        assert err < 2e-2, err


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    """Refused, not padded: what a caller pads with decides what the
    state sees (dt = 0 leaves it alone), so the caller pads."""
    *inputs, _ = scan_inputs(40, 2, 4, 1, 8)
    with pytest.raises(ValueError, match="no whole number of chunks of 16"):
        ssm.ssd_scan(*inputs, 16)
    # padded by the caller with dt = 0: the first 40 positions are the
    # unpadded recurrence's
    pad = lambda a: jnp.pad(a, ((0, 8),) + ((0, 0),) * (a.ndim - 1))  # noqa
    x, dt, A, B, C, D = inputs
    y = ssm.ssd_scan(pad(x), pad(dt), A, pad(B), pad(C), D, 16)
    np.testing.assert_allclose(y[:40], family.selective_scan(*inputs),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="heads in"):
        ssm.ssd_scan(x, dt, A, jnp.zeros((40, 3, 8)), jnp.zeros((40, 3, 8)),
                     D, 8)


def test_the_backward_pass_is_given_the_inputs_and_nothing_else():
    """No ``[heads, chunks, Q, Q]`` array crosses from the forward to the
    backward pass: the residuals are the six inputs."""
    *inputs, _ = scan_inputs(64, 4, 8, 1, 16)
    _, residuals = ssm._ssd_scan_fwd(*inputs, 16, 2)
    assert len(residuals) == 6
    for kept, given in zip(residuals, inputs):
        assert kept is given
    jaxpr = jax.make_jaxpr(jax.vjp(lambda *a: ssm.ssd_scan(*a, 16),
                                   *inputs)[1])(
        jnp.ones((64, 4, 8), jnp.float32))
    square = [v.aval.shape for v in jaxpr.jaxpr.constvars
              if len(v.aval.shape) >= 2 and v.aval.shape[-2:] == (16, 16)]
    assert square == []


def test_chunks_at_once_takes_the_largest_divisor():
    assert [ssm._at_once(32, k) for k in (1, 4, 5, 32, 100)] == \
        [1, 4, 4, 32, 32]
    assert ssm._at_once(12, 5) == 4 and ssm._at_once(7, 4) == 1
    assert ssm.scan_chunks(8192, 64, 256) == 2048
    assert ssm.scan_state_bytes(8192, 64, 64, 128, 256) == 32 * 64 * 64 \
        * 128 * 4 == 67_108_864


@pytest.mark.parametrize("width, bias", [(4, True), (4, False), (1, True),
                                         (3, True)])
def test_causal_conv1d_is_its_definition(width, bias):
    rng = np.random.default_rng(2)
    seq, channels = 12, 5
    x = rng.standard_normal((seq, channels)).astype(np.float32)
    w = rng.standard_normal((channels, width)).astype(np.float32)
    b = rng.standard_normal(channels).astype(np.float32) if bias else None
    want = np.zeros((seq, channels), np.float32)
    for t in range(seq):
        for k in range(width):
            if t - (width - 1) + k >= 0:
                want[t] += w[:, k] * x[t - (width - 1) + k]
    if bias:
        want += b
    got = ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                            None if b is None else jnp.asarray(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # causal: a later position moves no earlier output
    moved = x.copy()
    moved[7:] += 1.0
    again = ssm.causal_conv1d(jnp.asarray(moved), jnp.asarray(w),
                              None if b is None else jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(again[:7]), np.asarray(got[:7]))
    assert ssm.causal_conv1d(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w),
                             None).dtype == jnp.bfloat16


def test_causal_conv1d_gradients_are_the_references():
    rng = np.random.default_rng(3)
    x, w, b, g = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                  for s in ((10, 3), (3, 4), (3,), (10, 3)))
    got = jax.grad(lambda *a: (ssm.causal_conv1d(*a) * g).sum(),
                   argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(lambda *a: (family.causal_conv(*a) * g).sum(),
                    argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the Mosaic kernels (ops/ssm_kernels.py), interpreted on the CPU
# --------------------------------------------------------------------------
def _visits(kind):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(
        "ssm_scan_kernel_visits_total", labels={"kind": kind}).collect()


def _value_and_grads(scan, inputs, w):
    return jax.value_and_grad(
        lambda *a: (scan(*a) * w).astype(jnp.float32).sum(),
        argnums=tuple(range(6)))(*inputs)


@pytest.mark.parametrize("seq, heads, width, groups", [
    pytest.param(128, 2, 64, 1, id="one_chunk-one_lane_group"),
    pytest.param(384, 4, 64, 1, id="three_chunks-heads_in_one_group"),
    pytest.param(256, 4, 64, 2, id="two_groups_of_a_lane_group"),
    pytest.param(256, 4, 32, 1, id="four_heads_a_lane_group"),
    pytest.param(256, 2, 128, 2, id="a_head_a_lane_group_and_a_group"),
])
def test_kernels_are_the_xla_form_and_the_recurrence(
        monkeypatch, seq, heads, width, groups):
    """y and the six gradients through the kernels, against the XLA form
    and against the recurrence a position at a time."""
    chunk, state = 128, 128
    *inputs, w = scan_inputs(seq, heads, width, groups, state)
    assert ssm.scan_form(seq, heads, width, groups, state, chunk) == "xla"
    xla = _value_and_grads(lambda *a: ssm.ssd_scan(*a, chunk), inputs, w)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.scan_form(seq, heads, width, groups, state,
                         chunk) == "kernels"
    before = _visits("fwd"), _visits("bwd")
    y = ssm.ssd_scan(*inputs, chunk)
    # a visit is a chunk, for all its heads
    assert _visits("fwd") - before[0] == seq // chunk
    got = _value_and_grads(lambda *a: ssm.ssd_scan(*a, chunk), inputs, w)
    # differentiated, the forward pass runs once more (it hands the
    # backward pass the states the chunks start from)
    assert _visits("fwd") - before[0] == 2 * (seq // chunk)
    assert _visits("bwd") - before[1] == seq // chunk
    want = _value_and_grads(family.selective_scan, inputs, w)
    y_want = family.selective_scan(*inputs)
    np.testing.assert_allclose(y, y_want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(y_want).max()))
    for other in (xla, want):
        assert float(got[0]) == pytest.approx(float(other[0]), rel=1e-4)
        for name, a, b in zip("x dt A B C D".split(), got[1], other[1]):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=3e-5 * scale,
                                       err_msg=name)


def test_kernels_keep_bf16_inputs_float32_decays_and_sums(monkeypatch):
    """As the XLA form: y and the gradients within a few bf16 roundings
    of the float32 recurrence on the same bf16 inputs, in the dtypes of
    what they are gradients of."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    *inputs, w = scan_inputs(256, 4, 64, 1, 128, dtype=jnp.bfloat16)
    as_f32 = [a.astype(jnp.float32) for a in inputs]
    got = _value_and_grads(lambda *a: ssm.ssd_scan(*a, 128), inputs, w)[1]
    want = _value_and_grads(family.selective_scan, as_f32,
                            w.astype(jnp.float32))[1]
    y = ssm.ssd_scan(*inputs, 128)
    assert y.dtype == jnp.bfloat16
    for a, b, like in zip((y,) + got, (family.selective_scan(*as_f32),)
                          + want, [inputs[0]] + inputs):
        assert a.dtype == like.dtype
        err = float(jnp.abs(a.astype(jnp.float32) - b).max()
                    / jnp.abs(b).max())
        assert err < 2e-2, err


@pytest.mark.parametrize("interpreted, shape, form", [
    # (seq, heads, width, groups, state, chunk)
    pytest.param(True, (8192, 64, 64, 1, 128, 256), "kernels",
                 id="the_cells_shape"),
    pytest.param(True, (128, 2, 64, 1, 128, 128), "kernels",
                 id="one_chunk_one_lane_group"),
    pytest.param(True, (512, 8, 32, 2, 256, 256), "kernels",
                 id="four_heads_a_lane_group"),
    pytest.param(False, (8192, 64, 64, 1, 128, 256), "xla",
                 id="the_cells_shape_on_the_cpu"),
    pytest.param(True, (8192 + 128, 64, 64, 1, 128, 256), "xla",
                 id="no_whole_number_of_chunks"),
    pytest.param(True, (8192, 64, 64, 1, 128, 64), "xla",
                 id="chunk_no_multiple_of_128"),
    pytest.param(True, (8192, 64, 64, 1, 64, 256), "xla",
                 id="state_no_multiple_of_128"),
    pytest.param(True, (8192, 64, 48, 1, 128, 256), "xla",
                 id="width_packs_into_no_lane_group"),
    pytest.param(True, (8192, 3, 64, 1, 128, 256), "xla",
                 id="heads_fill_no_lane_group"),
    pytest.param(True, (8192, 4, 64, 4, 128, 256), "xla",
                 id="a_lane_group_over_two_groups"),
    pytest.param(True, (8192, 256, 64, 1, 128, 256), "xla",
                 id="a_chunk_of_all_heads_fills_vmem"),
    pytest.param(True, (64, 8, 8, 1, 16, 16), "xla", id="the_tests_sizes"),
])
def test_scan_form_reads_platform_and_shape(monkeypatch, interpreted, shape,
                                            form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.scan_form(*shape) == form


def test_kernels_shapes_refuse_a_sequence_of_no_whole_chunks(monkeypatch):
    """Under the kernels' platform too, with today's message."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    *inputs, _ = scan_inputs(192, 2, 64, 1, 128)
    with pytest.raises(ValueError,
                       match="no whole number of chunks of 128"):
        ssm.ssd_scan(*inputs, 128)


def test_visit_counter_stays_where_the_xla_form_runs(monkeypatch):
    """``ssm_scan_kernel_visits_total{kind}`` counts a traced kernel
    call's visits (a chunk, for all its heads): the cell's shape reads
    32 forward and 32 backward; the XLA form adds 0."""
    x = jax.ShapeDtypeStruct((8192, 64, 64), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((8192, 64), jnp.float32)
    a = jax.ShapeDtypeStruct((64,), jnp.float32)
    b = jax.ShapeDtypeStruct((8192, 1, 128), jnp.bfloat16)

    def traced():
        before = _visits("fwd"), _visits("bwd")
        jax.eval_shape(jax.grad(
            lambda *args: ssm.ssd_scan(*args, 256).astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 3, 4, 5)),
            x, dt, a, b, b, a)
        return _visits("fwd") - before[0], _visits("bwd") - before[1]

    assert traced() == (0, 0)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced() == (32, 32)


def test_the_kernels_backward_pass_is_given_inputs_and_states(monkeypatch):
    """The six inputs and the float32 states the chunks start from,
    ``[chunks * N, H * P]``; nothing ``[Q, Q]``."""
    from paddle_tpu.ops import ssm_kernels
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    *inputs, _ = scan_inputs(256, 2, 64, 1, 128)
    y, residuals = ssm_kernels._scan_fwd(*inputs, 128)
    assert len(residuals) == 7
    for kept, given in zip(residuals, inputs):
        assert kept is given
    starts = residuals[6]
    assert starts.shape == (2 * 128, 2 * 64) and starts.dtype == jnp.float32
    assert not np.asarray(starts[:128]).any()       # the first: nothing
    np.testing.assert_array_equal(y, ssm_kernels.scan(*inputs, 128))
