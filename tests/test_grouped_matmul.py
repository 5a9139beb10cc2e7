"""The grouped products' Mosaic kernels (``ops/grouped_matmul.py``),
interpreted on the CPU, against ``jax.lax.ragged_dot`` and its
``jax.vjp`` at the same operands; which form runs; the counter; and the
expert layer of ``moe/grouped.py`` through them, overflow branch
taken."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — turns on x64, which the kernels must survive
from paddle_tpu.incubate.distributed.models.moe import grouped
from paddle_tpu.ops import grouped_matmul


def _calls(kind):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(
        "moe_grouped_kernel_calls_total", labels={"kind": kind}).collect()


def _operands(rows, k, n, held, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(s), dtype)
                 for s in ((rows, k), (held, k, n), (rows, n)))


def _reference(lhs, rhs, d_out, sizes):
    """(out, d_lhs, d_rhs) of ``jax.lax.ragged_dot``."""
    out, back = jax.vjp(
        functools.partial(jax.lax.ragged_dot, group_sizes=sizes), lhs, rhs)
    return (out,) + tuple(back(d_out))


def _kernels(lhs, rhs, d_out, sizes):
    return {"fwd": lambda: grouped_matmul.dot(lhs, rhs, sizes),
            "dlhs": lambda: grouped_matmul.dot(d_out, rhs, sizes,
                                               transposed=True),
            "drhs": lambda: grouped_matmul.dot_weights(lhs, d_out, sizes)}


# rows, K, N, sizes: a visit is a tile of 128 rows, so a group boundary
# at any other row lies inside a row tile
_CASES = {
    "uneven_groups": (1024, 128, 256, [100, 412, 37, 475]),
    "an_empty_group_between": (1024, 128, 128, [300, 0, 0, 724]),
    "a_boundary_inside_a_row_tile": (512, 128, 128, [200, 312]),
    "three_groups_in_one_tile_of_128": (384, 128, 128, [40, 50, 30, 264]),
    "count_below_the_rows": (1536, 128, 128, [100, 156, 37]),
    "count_zero": (512, 128, 128, [0, 0, 0]),
    "last_groups_empty": (512, 128, 128, [512, 0, 0]),
    # the small twins of 2688 x 1856 and of 1856 x 2688: the first is
    # taken as the chip stores it, [held, N, K]
    "a_width_of_no_whole_lane_group": (512, 256, 192, [100, 0, 156, 37]),
    "the_same_width_contracted": (512, 192, 256, [1, 2, 3, 250]),
    "neither_width_whole_lane_groups": (512, 192, 320, [300, 0, 0, 200]),
}


@pytest.mark.parametrize("kind", ["fwd", "dlhs", "drhs"])
@pytest.mark.parametrize("case", list(_CASES))
def test_kernels_are_ragged_dot_and_its_gradients(monkeypatch, case, kind):
    """Each product against ``jax.lax.ragged_dot`` or its ``jax.vjp``;
    the rows past the last group exactly 0 in the first two, and no part
    of the third though ``lhs`` and ``d_out`` hold numbers there."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rows, k, n, sizes = _CASES[case]
    lhs, rhs, d_out = _operands(rows, k, n, len(sizes))
    assert grouped_matmul.form(lhs, rhs) == "kernels"
    sizes = jnp.asarray(sizes, jnp.int32)
    want = dict(zip(("fwd", "dlhs", "drhs"),
                    _reference(lhs, rhs, d_out, sizes)))[kind]
    before = _calls(kind)
    got = _kernels(lhs, rhs, d_out, sizes)[kind]()
    assert _calls(kind) == before + 1
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    count = int(sizes.sum())
    if kind != "drhs":
        assert not np.asarray(got[count:]).any()
    else:
        empty = np.asarray(sizes) == 0
        assert not np.asarray(got)[empty].any()


@pytest.mark.parametrize("kind", ["fwd", "dlhs", "drhs"])
def test_kernels_take_bf16_sum_in_float32_and_round_once(monkeypatch, kind):
    """bf16 operands give the dtype ``ragged_dot`` and its vjp give, and
    the float32 sum of the whole contraction rounded once: the float32
    reference on the same bf16 numbers, rounded, is met to a bf16 ulp."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    sizes = jnp.asarray([100, 0, 156, 37], jnp.int32)
    lhs, rhs, d_out = _operands(512, 192, 256, 4, jnp.bfloat16, seed=1)
    names = ("fwd", "dlhs", "drhs")
    want_dtype = dict(zip(names, _reference(lhs, rhs, d_out, sizes)))[kind]
    exact = dict(zip(names, _reference(
        *(a.astype(jnp.float32) for a in (lhs, rhs, d_out)), sizes)))[kind]
    got = _kernels(lhs, rhs, d_out, sizes)[kind]()
    assert got.dtype == want_dtype.dtype == jnp.bfloat16
    got, exact = np.asarray(got, np.float32), np.asarray(exact)
    assert np.abs(got - exact).max() <= 2.0 ** -8 * np.abs(exact).max()
    np.testing.assert_allclose(got, exact, rtol=2.0 ** -7, atol=1e-2)


@pytest.mark.parametrize("tile", [256, 512])
def test_every_row_tile_gives_the_same_products(monkeypatch, tile):
    """The kernels take any row tile: the two that the sweep on the
    chip ran beside 128 visit other pairs of group and tile and give
    the same numbers."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(grouped_matmul, "_TILE", tile)
    sizes = jnp.asarray([100, 412, 0, 37, 300], jnp.int32)
    lhs, rhs, d_out = _operands(1024, 128, 192, 5, seed=tile)
    calls = (grouped_matmul._rows_call, grouped_matmul._weights_call)
    # the calls are jitted on shapes alone: this tile's are traced anew
    for call in calls:
        call.clear_cache()
    try:
        got = [f() for f in _kernels(lhs, rhs, d_out, sizes).values()]
    finally:
        for call in calls:
            call.clear_cache()
    for a, b in zip(got, _reference(lhs, rhs, d_out, sizes)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_visits_are_the_tiles_that_hold_pairs():
    """Groups of 100, 412, 0 and 37 rows in tiles of 128: the first
    group's one tile, the second's four (the first shared with it), the
    fourth's one (shared too), then the tiles past the count (549 rows:
    five tiles covered, three not), then visits that stay put."""
    sizes = jnp.asarray([100, 412, 0, 37], jnp.int32)
    group, tile, starts, ends, info = grouped_matmul._visits(
        sizes, 1024, 128, every_group=False)
    assert group.dtype == tile.dtype == info.dtype == jnp.int32
    assert list(info) == [6, 3, 4]
    assert list(group) == [0, 1, 1, 1, 1, 3, 3, 3, 3, 3, 3]
    assert list(tile) == [0, 0, 1, 2, 3, 4, 5, 6, 7, 7, 7]
    assert list(starts) == [0, 100, 512, 512] and list(ends) == [
        100, 512, 512, 549]
    # the weights' gradient visits the empty group too, to write zeros
    group, tile, _, _, info = grouped_matmul._visits(
        sizes, 1024, 128, every_group=True)
    assert list(info) == [7, 3, 4]
    assert list(group[:7]) == [0, 1, 1, 1, 1, 2, 3]
    assert list(tile[:7]) == [0, 0, 1, 2, 3, 4, 4]
    assert set(np.asarray(group[7:])) == {3}


def _shape(rows, k, n, held, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((rows, k), dtype),
            jax.ShapeDtypeStruct((held, k, n), dtype))


@pytest.mark.parametrize("interpreted, disabled, operands, form", [
    pytest.param(True, False, _shape(6144, 2688, 1856, 8), "kernels",
                 id="the_nemotron_cells_first_matrix"),
    pytest.param(True, False, _shape(6144, 1856, 2688, 8), "kernels",
                 id="the_nemotron_cells_second_matrix"),
    pytest.param(True, False, _shape(16384, 2048, 768, 16), "kernels",
                 id="the_keye_cells_first_matrix"),
    pytest.param(False, False, _shape(6144, 2688, 1856, 8), "xla",
                 id="the_cpu"),
    pytest.param(True, True, _shape(6144, 2688, 1856, 8), "xla",
                 id="PADDLE_TPU_DISABLE_PALLAS"),
    pytest.param(True, False, _shape(1000, 128, 128, 4), "xla",
                 id="rows_of_no_whole_tile"),
    pytest.param(True, False, _shape(6144, 8192, 8192, 8), "xla",
                 id="a_matrix_that_does_not_fit_vmem"),
    pytest.param(True, False, _shape(512, 128, 128, 4, jnp.int32), "xla",
                 id="integers"),
    pytest.param(True, False,
                 (_shape(512, 128, 128, 4)[0],
                  _shape(512, 128, 128, 4, jnp.float32)[1]), "xla",
                 id="two_dtypes"),
])
def test_form_reads_platform_shape_and_dtype(monkeypatch, interpreted,
                                             disabled, operands, form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if disabled:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert grouped_matmul.form(*operands) == form


# --------------------------------------------------------------------------
# the expert layer through the kernels
# --------------------------------------------------------------------------
def _layer(matrices, tokens=512, d=128, f=192, experts=8, held=2, seed=0):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((tokens, experts)),
                         jnp.float32)
    shapes = [(held, d, f)] * (matrices - 1) + [(held, f, d)]
    weights = tuple(jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
                    for s in shapes)
    return y, logits, weights


def _layer_loss(y, logits, weights, k=2, experts=8):
    chosen, gates = grouped.route(logits, k)
    out, sizes = grouped.experts_forward(y, chosen, gates, weights, 0,
                                         experts)
    return (out ** 2).sum(), sizes


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["first_window_only", "overflow_branch_taken"])
@pytest.mark.parametrize("matrices", [2, 3], ids=["relu2", "swiglu"])
def test_both_expert_forms_run_through_the_kernels(monkeypatch, matrices,
                                                   overflow):
    """``experts_forward`` and its gradients under the interpreter (the
    kernels, traced: the counter says so) against the XLA form of the
    same operands; with the routers' logits biased to the two held
    experts, 1024 pairs overflow the window of 512 rows and the later
    window runs, forward and backward."""
    y, logits, weights = _layer(matrices)
    if overflow:
        logits = logits.at[:, :2].add(100.0)
    run = jax.value_and_grad(_layer_loss, argnums=(0, 1, 2), has_aux=True)
    (want, sizes), want_g = run(y, logits, weights)
    assert grouped.usual_rows(512, 2, 2, 8) == 512
    assert (int(sizes.sum()) > 512) == overflow
    before = [_calls(kind) for kind in ("fwd", "dlhs", "drhs")]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (got, got_sizes), got_g = run(y, logits, weights)
    traced = [_calls(kind) - b for kind, b in zip(("fwd", "dlhs", "drhs"),
                                                  before)]
    # the first window's products and the later windows' under the scan;
    # the backward pass runs the later windows' forward again
    assert traced == [matrices * 3, matrices * 2, matrices * 2]
    np.testing.assert_array_equal(got_sizes, sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # float32 sums in another order: to 1e-5 of a leaf's largest value
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_call_counter_stays_where_the_xla_form_runs(monkeypatch):
    """``moe_grouped_kernel_calls_total{kind}`` counts a kernel call as
    it is traced: the Nemotron cell's expert block (two matrices, the
    first window and the overflow branch's scan body, which traces the
    forward pass twice) reads 6 forward, 4 and 4 backward; the XLA form
    adds 0."""
    shapes = (jax.ShapeDtypeStruct((8192, 2688), jnp.bfloat16),
              jax.ShapeDtypeStruct((8192, 128), jnp.float32),
              (jax.ShapeDtypeStruct((8, 2688, 1856), jnp.bfloat16),
               jax.ShapeDtypeStruct((8, 1856, 2688), jnp.bfloat16)))

    def traced():
        before = [_calls(kind) for kind in ("fwd", "dlhs", "drhs")]
        jax.eval_shape(jax.grad(
            lambda *a: _layer_loss(*a, k=6, experts=128)[0],
            argnums=(0, 2)), *shapes)
        return [_calls(kind) - b
                for kind, b in zip(("fwd", "dlhs", "drhs"), before)]

    assert grouped.usual_rows(8192, 6, 8, 128) == 6144
    assert traced() == [0, 0, 0]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced() == [6, 4, 4]
