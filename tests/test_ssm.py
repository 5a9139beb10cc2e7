"""``ops/ssm.py`` on the CPU at small sizes: the chunked scan and its
hand-cut backward pass against the recurrence a position at a time (the
benchmark's reference, ``families/granite_hybrid.py:selective_scan``),
values and every gradient, over one chunk, many, a chunk the length of
the sequence, one group of heads and several; the causal convolution
against its definition; what a sequence that is no whole number of
chunks gets (it is refused); and that the backward pass is given the
inputs and nothing else.  Then the Mosaic kernels of both, interpreted:
the scan's against the XLA form and the recurrence, the convolution's
(taps, bias, SiLU and split in one call) against ``causal_conv1d`` +
SiLU + split, alone and through ``Mamba2Mixer``; and Mamba-1's selective
scan, its XLA forms and its kernels (``ops/ssm_s6_kernels.py``), each
against the benchmark's recurrence (``families/sambay.py``).
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


# --------------------------------------------------------------------------
# the convolution's Mosaic kernels (ops/ssm_conv_kernels.py), interpreted
# --------------------------------------------------------------------------
def _conv_calls(kind):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(
        "ssm_conv_kernel_calls_total", labels={"kind": kind}).collect()


def conv_inputs(seq, inner, bc, start, tail, dtype=jnp.float32, seed=5):
    """The in-projection's result ``[S, start + C + tail]``, the taps
    ``[C, 4]``, the bias, and a weight for each of x, B and C."""
    rng = np.random.default_rng(seed)
    channels = inner + 2 * bc
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa
    return (normal(seq, start + channels + tail),
            jnp.asarray(rng.uniform(-.5, .5, (channels, 4)), jnp.float32),
            jnp.asarray(rng.uniform(-.5, .5, (channels,)), jnp.float32),
            (normal(seq, inner), normal(seq, bc), normal(seq, bc)))


def _conv_of_columns(proj, start, weight, bias, inner, groups, state):
    """As the mixer calls it: xBC a slice of the projection's result, and
    where it lies there."""
    xbc = proj[:, start:start + weight.shape[0]]
    return ssm.conv_silu_split(xbc, weight, bias, inner, groups, state,
                               lies_in=(proj, start))


def _conv_value_and_grads(proj, weight, bias, ws, start, inner, groups,
                          state):
    def weighted(p, w, b):
        outs = _conv_of_columns(p, start, w, b, inner, groups, state)
        return sum((o * g).astype(jnp.float32).sum()
                   for o, g in zip(outs, ws))
    return jax.value_and_grad(weighted, argnums=(0, 1, 2))(proj, weight,
                                                           bias)


@pytest.mark.parametrize("seq, inner, groups, state, start, dtype", [
    pytest.param(512, 4096, 1, 128, 4096, jnp.float32,
                 id="granites_widths-two_tiles"),
    pytest.param(256, 4096, 8, 128, 4096, jnp.float32,
                 id="nemotrons_widths"),
    pytest.param(768, 256, 2, 128, 256, jnp.float32,
                 id="three_tiles-a_halo_over_each_edge"),
    pytest.param(48, 256, 1, 128, 256, jnp.float32,
                 id="three_tiles_of_sixteen_rows"),
    pytest.param(512, 256, 1, 128, 100, jnp.float32,
                 id="an_offset_of_no_whole_block-a_slice_in"),
    pytest.param(512, 256, 1, 256, 0, jnp.float32,
                 id="b_as_wide_as_x-no_offset"),
    pytest.param(512, 256, 1, 128, 256, jnp.bfloat16, id="bf16"),
])
def test_conv_kernels_are_causal_conv1d_silu_and_split(
        monkeypatch, seq, inner, groups, state, start, dtype):
    """x, B and C and the three gradients (of xBC where it lies in the
    projection's result, of the taps, of the bias) through the kernels,
    against the XLA form; the first three rows, which read zeros before
    the sequence, against the definition."""
    bc = groups * state
    channels = inner + 2 * bc
    proj, weight, bias, ws = conv_inputs(seq, inner, bc, start, 64, dtype)
    args = (proj, weight, bias, ws, start, inner, groups, state)
    assert ssm.conv_form(seq, channels, inner, groups, state, 4) == "xla"
    want = _conv_value_and_grads(*args)
    want_outs = _conv_of_columns(proj, start, weight, bias, inner, groups,
                                 state)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.conv_form(seq, channels, inner, groups, state,
                         4) == "kernels"
    before = _conv_calls("fwd"), _conv_calls("bwd")
    outs = _conv_of_columns(proj, start, weight, bias, inner, groups, state)
    got = _conv_value_and_grads(*args)
    assert (_conv_calls("fwd") - before[0],
            _conv_calls("bwd") - before[1]) == (2, 1)
    # bf16: the XLA form rounds the convolution before its SiLU and the
    # kernels do not: a rounding apart
    close = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))           # noqa: E731
    for a, b, width in zip(outs, want_outs, (inner, bc, bc)):
        assert a.shape == (seq, width) and a.dtype == b.dtype == dtype
        np.testing.assert_allclose(f32(a), f32(b), **close)
    xbc = f32(proj)[:, start:start + channels]
    first = np.asarray(bias) + sum(
        np.asarray(weight)[:, k] * np.pad(xbc, ((3, 0), (0, 0)))[k:k + 3]
        for k in range(4))
    np.testing.assert_allclose(
        np.concatenate([f32(o)[:3] for o in outs], 1),
        first / (1 + np.exp(-first)), **close)
    if dtype != jnp.bfloat16:       # a sum of 4e5 rounded terms otherwise
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for name, a, b in zip(("d_proj", "d_weight", "d_bias"), got[1],
                          want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(f32(b)).max())
        np.testing.assert_allclose(
            f32(a), f32(b), rtol=1e-3,
            atol=(2e-2 if dtype == jnp.bfloat16 else 2e-5) * scale,
            err_msg=name)
    # nothing outside xBC's columns is given a gradient
    d_proj = f32(got[1][0])
    assert not d_proj[:, :start].any()
    assert not d_proj[:, start + channels:].any()


@pytest.mark.parametrize("interpreted, shape, form", [
    # (seq, channels, inner, groups, state, width)
    pytest.param(True, (8192, 4352, 4096, 1, 128, 4), "kernels",
                 id="granites_shape"),
    pytest.param(True, (8192, 6144, 4096, 8, 128, 4), "kernels",
                 id="nemotrons_shape"),
    pytest.param(False, (8192, 4352, 4096, 1, 128, 4), "xla",
                 id="granites_shape_on_the_cpu"),
    pytest.param(True, (8192, 4096 + 2 * 96, 4096, 1, 96, 4), "xla",
                 id="b_and_c_no_whole_lane_group"),
    pytest.param(True, (8192, 4032 + 256, 4032, 1, 128, 4), "xla",
                 id="x_no_whole_lane_group"),
    pytest.param(True, (8192, 384 + 512, 384, 2, 128, 4), "xla",
                 id="x_no_whole_number_of_bs_blocks"),
    pytest.param(True, (8192, 4352 + 128, 4096, 1, 128, 4), "xla",
                 id="channels_that_are_not_x_b_and_c"),
    pytest.param(True, (8192 + 8, 4352, 4096, 1, 128, 4), "xla",
                 id="no_whole_number_of_sublane_tiles"),
    pytest.param(True, (8192, 4352, 4096, 1, 128, 10), "xla",
                 id="taps_further_back_than_eight_rows"),
    pytest.param(True, (8192, 8 * 4352, 8 * 4096, 8, 128, 4), "xla",
                 id="a_tile_of_all_channels_fills_vmem"),
    pytest.param(True, (64, 80, 64, 1, 8, 4), "xla", id="the_tests_sizes"),
])
def test_conv_form_reads_platform_and_shape(monkeypatch, interpreted, shape,
                                            form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.conv_form(*shape) == form


def test_conv_kernel_calls_are_counted_as_they_are_traced(monkeypatch):
    """``ssm_conv_kernel_calls_total{kind}``: a recomputed layer's trace
    at the cell's shape reads two forward calls (the forward pass, and
    the forward again for the backward pass) and one backward; the XLA
    form adds 0."""
    proj = jax.ShapeDtypeStruct((8192, 8512), jnp.bfloat16)
    weight = jax.ShapeDtypeStruct((4352, 4), jnp.float32)
    bias = jax.ShapeDtypeStruct((4352,), jnp.float32)

    def traced():
        @jax.checkpoint         # anew: a trace that is cached counts nothing
        def layer(p, w, b):
            return sum(o.astype(jnp.float32).sum() for o in
                       _conv_of_columns(p, 4096, w, b, 4096, 1, 128))

        before = _conv_calls("fwd"), _conv_calls("bwd")
        jax.eval_shape(jax.grad(layer, argnums=(0, 1, 2)), proj, weight,
                       bias)
        return _conv_calls("fwd") - before[0], _conv_calls("bwd") - before[1]

    assert traced() == (0, 0)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced() == (2, 1)


def test_the_conv_kernels_backward_pass_is_given_its_inputs(monkeypatch):
    """The projection's result, the taps and the bias: neither the slice
    the gradient is taken by nor the convolution's own result is kept, as
    the XLA form's ``jax.checkpoint`` keeps none."""
    from paddle_tpu.ops import ssm_conv_kernels
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    proj, weight, bias, _ = conv_inputs(32, 128, 128, 128, 0)
    outs, kept = ssm_conv_kernels._conv_fwd(proj[:, 128:], weight, bias,
                                            proj, 128, 128, 128)
    assert len(kept) == 3
    for residual, given in zip(kept, (proj, weight, bias)):
        assert residual is given
    assert [o.shape for o in outs] == [(32, 128)] * 3


def _through_the_mixer(mixer, data, w):
    """The mixer's output and the gradients of a weighted sum of it by
    the input and by every parameter."""
    from paddle_tpu.nn import functional_call as F

    def weighted(x, params):
        out, _ = F.functional_call(mixer, params, F.buffer_dict(mixer),
                                   (paddle_tpu.to_tensor(x),))
        return (out._value * w).sum(), out._value

    (_, out), grads = jax.value_and_grad(weighted, argnums=(0, 1),
                                         has_aux=True)(
        jnp.asarray(data), dict(F.param_dict(mixer)))
    return out, grads[0], grads[1]


def test_a_batch_of_two_sequences_through_the_mixer(monkeypatch):
    """``Mamba2Mixer`` on ``[2, 256, 64]`` with the convolution's kernels
    (and the scan's) against the same mixer's XLA forms: the output and
    every gradient; under the XLA form the output is, to the bit, what
    the mixer gave when it ran ``causal_conv1d`` + SiLU + split on the
    slice itself."""
    from paddle_tpu.models import mamba2
    paddle_tpu.seed(11)
    # d_inner 256, one group of 128 states: 512 channels under the taps
    mixer = mamba2.Mamba2Mixer(64, 4, 64, 128, 1, 4, 128, 1e-5, 0.02, 0.02,
                               0)
    rng = np.random.default_rng(0)
    data = rng.standard_normal((2, 256, 64)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal((2, 256, 64)), jnp.float32)
    assert ssm.conv_form(256, 512, 256, 1, 128, 4) == "xla"
    xla = _through_the_mixer(mixer, data, w)

    def as_the_parent(xbc, weight, bias, inner, groups, state, lies_in):
        return tuple(jnp.split(ssm._conv_silu(xbc, weight, bias),
                               (inner, inner + groups * state), -1))

    with monkeypatch.context() as m:
        m.setattr(ssm, "conv_silu_split", as_the_parent)
        parent = _through_the_mixer(mixer, data, w)
    np.testing.assert_array_equal(xla[0], parent[0])
    np.testing.assert_array_equal(xla[1], parent[1])

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.conv_form(256, 512, 256, 1, 128, 4) == "kernels"
    before = _conv_calls("fwd"), _conv_calls("bwd")
    got = _through_the_mixer(mixer, data, w)
    # a call a sequence, forward and backward
    assert (_conv_calls("fwd") - before[0],
            _conv_calls("bwd") - before[1]) == (2, 2)
    np.testing.assert_allclose(got[0], xla[0], rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(xla[0]).max()))
    np.testing.assert_allclose(got[1], xla[1], rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(xla[1]).max()))
    assert got[2].keys() == xla[2].keys() and len(got[2]) == 8
    for name, b in xla[2].items():
        np.testing.assert_allclose(got[2][name], b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()),
                                   err_msg=name)


# --------------------------------------------------------------------------
# the scan of Mamba-1: a step size a channel, a decay a channel and state
# --------------------------------------------------------------------------
from benchmarks.families import sambay as s6_family           # noqa: E402


def s6_inputs(seq, channels, state, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa
    return (normal(seq, channels),
            jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1e-1),
                                            (seq, channels)), jnp.float32)),
            -jnp.asarray(rng.uniform(1.0, 16.0, (channels, state)),
                         jnp.float32),
            normal(seq, state), normal(seq, state),
            jnp.asarray(rng.standard_normal((channels,)), jnp.float32),
            normal(seq, channels))


@pytest.mark.parametrize("seq, chunk, bytes_at_once, form", [
    pytest.param(64, 64, 1 << 28, "sequential", id="chunk_is_the_sequence"),
    pytest.param(64, 16, 1 << 28, "chunked", id="four_chunks-all_at_once"),
    pytest.param(96, 8, 1, "chunked", id="twelve_chunks-one_at_once"),
    pytest.param(96, 8, 5 * 8 * 24 * 4 * 4, "chunked",
                 id="twelve_chunks-at_once_no_divisor"),
    pytest.param(64, 1, 1 << 28, "chunked", id="a_position_a_chunk"),
    pytest.param(60, 16, 1 << 28, "sequential", id="no_whole_chunks"),
])
def test_selective_scan_is_the_recurrence_values_and_six_gradients(
        monkeypatch, seq, chunk, bytes_at_once, form):
    """``selective_scan`` against the benchmark's reference, the
    recurrence a position at a time: y and the gradients by x, dt, A, B,
    C and D, over several chunks, some at a time in the walk back."""
    monkeypatch.setattr(ssm, "SELECTIVE_BYTES_AT_ONCE", bytes_at_once)
    channels, state = 24, 4
    assert ssm.selective_scan_form(seq, chunk) == form
    *inputs, w = s6_inputs(seq, channels, state)

    def weighted(scan):
        return jax.value_and_grad(
            lambda *a: (scan(*a) * w).sum(), argnums=tuple(range(6)))(*inputs)

    got_y, got = weighted(lambda *a: ssm.selective_scan(*a, chunk))
    want_y, want = weighted(s6_family.selective_scan)
    assert float(got_y) == pytest.approx(float(want_y), rel=1e-5)
    for name, a, b in zip(("dx", "ddt", "dA", "dB", "dC", "dD"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)
    np.testing.assert_allclose(
        ssm.selective_scan(*inputs, chunk), s6_family.selective_scan(*inputs),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("channels, state, form", [
    pytest.param(16, 4, "chunked", id="chunked"),
    pytest.param(256, 16, "kernels", id="kernels"),
])
def test_selective_scan_keeps_float32_states_for_bf16_inputs(
        monkeypatch, channels, state, form):
    """x, B and C in bf16 (as under O2), dt and A float32: y comes back
    bf16, the state and the sums are float32, so the result is the
    float32 recurrence's on the same rounded inputs to bf16's last
    place; the gradients keep their inputs' dtypes.  The same limits for
    the XLA form and for the kernels."""
    if form == "kernels":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    x, dt, A, B, C, D, w = s6_inputs(128, channels, state, seed=1)
    xb, Bb, Cb = (a.astype(jnp.bfloat16) for a in (x, B, C))
    assert ssm.selective_scan_form(128, 16, channels, state, 2) == form
    y = ssm.selective_scan(xb, dt, A, Bb, Cb, D, 16)
    assert y.dtype == jnp.bfloat16
    want = s6_family.selective_scan(*(a.astype(jnp.float32)
                                      for a in (xb, dt, A, Bb, Cb, D)))
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: (ssm.selective_scan(*a, 16) * w).astype(
        jnp.float32).sum(), argnums=tuple(range(6)))(xb, dt, A, Bb, Cb, D)
    assert [g.dtype for g in grads] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.float32]
    ref = jax.grad(lambda *a: (s6_family.selective_scan(*a) * w).sum(),
                   argnums=tuple(range(6)))(*(a.astype(jnp.float32) for a in (
                       xb, dt, A, Bb, Cb, D)))
    for name, a, b in zip(("dx", "ddt", "dA", "dB", "dC", "dD"), grads, ref):
        np.testing.assert_allclose(a.astype(jnp.float32), b, rtol=2e-2,
                                   atol=2e-2 * float(jnp.abs(b).max()),
                                   err_msg=name)


@pytest.mark.parametrize("seq, channels, state, chunk, form", [
    pytest.param(64, 8, 4, 8, "chunked", id="chunked"),
    pytest.param(64, 128, 8, 16, "kernels", id="kernels"),
])
def test_selective_scan_survives_steps_that_forget_everything(
        monkeypatch, seq, channels, state, chunk, form):
    """A step of 50 at a decay of -16 is exp(-800): the state forgets
    all it held, and nothing overflows on the way (no exponential of a
    positive sum anywhere, and no division by a decay)."""
    if form == "kernels":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    x, dt, A, B, C, D, w = s6_inputs(seq, channels, state, seed=2)
    dt = dt.at[10:20].set(50.0)
    assert ssm.selective_scan_form(seq, chunk, channels, state, 4) == form
    y, vjp = jax.vjp(lambda *a: ssm.selective_scan(*a, chunk), x, dt, A, B,
                     C, D)
    want, ref_vjp = jax.vjp(s6_family.selective_scan, x, dt, A, B, C, D)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    for a, b in zip(vjp(w), ref_vjp(w)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()))


def test_selective_scans_backward_pass_is_given_inputs_and_chunk_starts():
    """What the forward pass keeps: the six inputs and the state each
    chunk starts from, ``[chunks, N, C]``; no state of any other
    position."""
    *inputs, _ = s6_inputs(64, 24, 4)
    _, kept = ssm._selective_scan_fwd(*inputs, 16)
    assert [k.shape for k in kept[:6]] == [a.shape for a in inputs]
    assert kept[6].shape == (4, 4, 24) and len(kept) == 7


@pytest.mark.parametrize("seq,chunk,chunks", [
    (8192, 64, 128), (2048, 32, 64), (128, 8, 16), (64, 8, 8), (16, 4, 4),
    (60, 4, 15), (61, 4, 1), (1, 1, 1)])
def test_the_chunk_follows_from_the_sequence(seq, chunk, chunks):
    """The power of two at or under the root of the length, so that the
    steps inside a chunk and from chunk to chunk are fewest together; a
    length that is no whole number of them goes a position at a time."""
    assert ssm.selective_chunk(seq) == chunk
    assert ssm.selective_scan_chunks(seq) == chunks
    assert ssm.selective_scan_state_bytes(seq, 24, 4) == chunks * 24 * 4 * 4
    x, dt, A, B, C, D, _ = s6_inputs(min(seq, 128), 8, 4)
    np.testing.assert_array_equal(
        ssm.selective_scan(x, dt, A, B, C, D),
        ssm.selective_scan(x, dt, A, B, C, D,
                           ssm.selective_chunk(x.shape[0])))


@pytest.mark.parametrize("interpreted, chunk, chunks, kept", [
    # the XLA form: 128 chunks of 64, 5120 channels of 16 states
    pytest.param(False, 64, 128, 41943040, id="chunked"),
    # the kernels: 64 chunks of 128
    pytest.param(True, 128, 64, 20971520, id="kernels"),
])
def test_the_cells_scan_keeps_a_state_a_chunk(monkeypatch, interpreted,
                                              chunk, chunks, kept):
    """What ``models/sambay.py`` counts, with the arguments it gives: the
    chunks of the form that runs and the bytes of their starting
    states."""
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.selective_chunk(8192) == chunk
    assert ssm.selective_scan_chunks(8192) == chunks
    assert ssm.selective_scan_state_bytes(8192, 5120, 16) == kept


# --------------------------------------------------------------------------
# the scan of Mamba-1: the kernels, interpreted
# --------------------------------------------------------------------------
from paddle_tpu.ops import ssm_s6_kernels                     # noqa: E402


def _s6_visits(kind):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(
        "s6_scan_kernel_visits_total", labels={"kind": kind}).collect()


@pytest.mark.parametrize("seq, chunk, channels, state, room, blocks", [
    pytest.param(64, 16, 256, 8, None, 1, id="four_chunks-two_lane_groups"),
    pytest.param(64, 16, 256, 8, 700000, 2,
                 id="four_chunks-two_blocks_of_channels"),
    pytest.param(48, 16, 384, 16, 1300000, 3,
                 id="three_chunks-three_blocks-sixteen_states"),
    pytest.param(32, 32, 512, 8, None, 1,
                 id="chunk_is_the_sequence-four_lane_groups"),
    pytest.param(256, None, 128, 8, None, 1, id="the_chunk_the_length_gives"),
])
def test_selective_scan_kernels_are_the_recurrence_values_and_six_gradients(
        monkeypatch, seq, chunk, channels, state, room, blocks):
    """The two Mosaic kernels, interpreted, against the benchmark's
    reference, the recurrence a position at a time, in float32: y and the
    gradients by x, dt, A, B, C and D; over more than two chunks (the
    state and ``g`` carried in scratch), more than one block of channels
    (dB and dC summed from the blocks' shares), lane groups side by side,
    and the chunk the length gives."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if room is not None:
        monkeypatch.setattr(ssm_s6_kernels, "_ROOM", room)
    q = chunk or ssm.selective_chunk(seq)
    assert ssm.selective_scan_form(seq, q, channels, state, 4) == "kernels"
    assert channels // ssm_s6_kernels.block_of(q, channels, state,
                                               4) == blocks
    *inputs, w = s6_inputs(seq, channels, state)

    def weighted(scan):
        return jax.value_and_grad(
            lambda *a: (scan(*a) * w).sum(), argnums=tuple(range(6)))(*inputs)

    before = _s6_visits("forward"), _s6_visits("backward")
    got_y, got = weighted(lambda *a: ssm.selective_scan(*a, chunk))
    # a visit a chunk and block, forward and backward
    assert (_s6_visits("forward") - before[0],
            _s6_visits("backward") - before[1]) == (seq // q * blocks,) * 2
    want_y, want = weighted(s6_family.selective_scan)
    assert float(got_y) == pytest.approx(float(want_y), rel=1e-5)
    for name, a, b in zip(("dx", "ddt", "dA", "dB", "dC", "dD"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)
    np.testing.assert_allclose(
        ssm.selective_scan(*inputs, chunk), s6_family.selective_scan(*inputs),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("interpreted, shape, form", [
    # (seq, chunk, channels, state, bytes of an element of x)
    pytest.param(True, (8192, 128, 5120, 16, 2), "kernels",
                 id="the_cells_shape"),
    pytest.param(True, (8192, 64, 5120, 16, 2), "kernels",
                 id="the_cells_shape-a_chunk_of_64"),
    pytest.param(False, (8192, 128, 5120, 16, 2), "chunked",
                 id="no_tpu_and_no_interpreter"),
    pytest.param(True, (8192, 128, 5100, 16, 2), "chunked",
                 id="channels_that_are_no_lane_groups"),
    pytest.param(True, (8192 + 64, 128, 5120, 16, 2), "sequential",
                 id="a_ragged_sequence"),
    pytest.param(True, (8192, 8, 5120, 16, 2), "chunked",
                 id="a_chunk_of_half_a_turn"),
    pytest.param(True, (8192, 128, 5120, 8, 2), "chunked",
                 id="bf16_states_that_are_half_a_tile"),
    pytest.param(True, (8192, 128, 5120, 8, 4), "kernels",
                 id="float32_and_eight_states"),
    pytest.param(True, (8192, 128, 5120, 4096, 2), "chunked",
                 id="a_lane_group_that_fills_vmem"),
    pytest.param(True, (64, 8, 24, 4, 4), "chunked", id="the_tests_sizes"),
])
def test_selective_scan_form_reads_platform_and_shape(monkeypatch,
                                                      interpreted, shape,
                                                      form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm.selective_scan_form(*shape) == form


@pytest.mark.parametrize("seq, channels, state, form", [
    pytest.param(192, 100, 8, "chunked",
                 id="channels_that_are_no_lane_group"),
    pytest.param(72, 128, 8, "chunked", id="a_sequence_of_no_whole_turns"),
    pytest.param(61, 128, 8, "sequential", id="a_ragged_sequence"),
])
def test_a_shape_the_kernels_refuse_takes_an_xla_form_and_agrees(
        monkeypatch, seq, channels, state, form):
    """With the kernels on: the refused shape's result is the XLA form's,
    to the bit, values and gradients, and no visit is counted."""
    *inputs, w = s6_inputs(seq, channels, state, seed=3)

    def both(chunk=None):
        return jax.value_and_grad(
            lambda *a: (ssm.selective_scan(*a, chunk) * w).sum(),
            argnums=tuple(range(6)))(*inputs)

    with monkeypatch.context() as m:
        m.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        chunk = ssm.selective_chunk(seq)
        assert ssm.selective_scan_form(seq, chunk, channels, state,
                                       4) == form
        before = _s6_visits("forward"), _s6_visits("backward")
        with_kernels_on = both()
        assert (_s6_visits("forward"), _s6_visits("backward")) == before
    for a, b in zip(jax.tree_util.tree_leaves(with_kernels_on),
                    jax.tree_util.tree_leaves(both(chunk))):
        np.testing.assert_array_equal(a, b)
    want = jax.grad(lambda *a: (s6_family.selective_scan(*a) * w).sum(),
                    argnums=tuple(range(6)))(*inputs)
    for a, b in zip(with_kernels_on[1], want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()))


def test_s6_kernel_visits_are_counted_as_the_calls_are_traced(monkeypatch):
    """``s6_scan_kernel_visits_total{kind}``: a recomputed layer's trace
    at the cell's shape reads two forward calls (the forward pass, and the
    forward again for the backward pass) and one backward, each the
    grid's 64 visits (one block of all 5120 channels, 64 chunks of 128);
    an XLA form adds 0."""
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((8192, 5120), jnp.bfloat16), ((8192, 5120), f32),
        ((5120, 16), f32), ((8192, 16), jnp.bfloat16),
        ((8192, 16), jnp.bfloat16), ((5120,), f32))]

    def traced():
        @jax.checkpoint         # anew: a trace that is cached counts nothing
        def layer(*a):
            return ssm.selective_scan(*a).astype(f32).sum()

        before = _s6_visits("forward"), _s6_visits("backward")
        jax.eval_shape(jax.grad(layer, argnums=tuple(range(6))), *shapes)
        return (_s6_visits("forward") - before[0],
                _s6_visits("backward") - before[1])

    assert traced() == (0, 0)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ssm_s6_kernels.block_of(128, 5120, 16, 2) == 5120
    assert traced() == (128, 64)


def test_the_s6_kernels_backward_pass_is_given_inputs_and_chunk_starts(
        monkeypatch):
    """What the kernels' forward pass keeps: the six inputs themselves
    and the state each chunk starts from, ``[chunks * N, C]`` float32; no
    float32 copy of x, no state of any other position."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    *inputs, _ = s6_inputs(64, 128, 8)
    inputs[0] = inputs[0].astype(jnp.bfloat16)
    y, kept = ssm_s6_kernels._scan_fwd(*inputs, 16)
    assert y.shape == (64, 128) and len(kept) == 7
    for residual, given in zip(kept[:6], inputs):
        assert residual is given
    assert kept[6].shape == (4 * 8, 128) and kept[6].dtype == jnp.float32
    # the first chunk starts from nothing
    np.testing.assert_array_equal(kept[6][:8], 0.0)


def test_causal_conv_silu_is_the_convolution_and_silu():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    weight = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    np.testing.assert_allclose(
        ssm.causal_conv_silu(x, weight, bias),
        jax.nn.silu(s6_family.causal_conv(x, weight, bias)), rtol=1e-5,
        atol=1e-6)
